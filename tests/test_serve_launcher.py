"""``python -m repro.launch.serve --mode extract`` end to end on a tiny
cube: answers come from the device payload, a failing client fails the
launcher, and the compile cache goes where the environment says."""

import jax
import numpy as np
import pytest

import repro.dataplane.weather as weather
from repro.core import Request, Select
from repro.launch import DEFAULT_COMPILE_CACHE, serve, use_compile_cache

ARGS = ["--mode", "extract", "--grid-n", "32", "--n-times", "2",
        "--n-levels", "3", "--requests", "24", "--threads", "3",
        "--shards", "2"]


@pytest.fixture
def no_cache_change(monkeypatch):
    """Leave the process's compile-cache setting as it was."""
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)


def test_answers_equal_payload_at_plan_offsets(capsys):
    args = serve.build_parser().parse_args(ARGS)
    wc = weather.WeatherCube(n=32, n_times=2, n_levels=3,
                             dtype=np.dtype(np.float32))
    host, payload = serve.load_payload(wc, args.seed)
    assert isinstance(payload, jax.Array) and payload.dtype == np.float32

    row, answers = serve.run_extract(args, payload)
    assert len(answers) == row["requests"] == 24
    for res in answers:
        np.testing.assert_array_equal(res.values, host[res.plan.offsets])
    assert "served 24 requests from 3 threads" in capsys.readouterr().out


def test_failing_request_exits_nonzero(monkeypatch, no_cache_change,
                                       capsys):
    bad = Request([Select("time", ["not-a-time"])])
    real = weather.request_population
    # rank 0 is the most frequent Zipf draw, so the bad request is sent
    monkeypatch.setattr(weather, "request_population",
                        lambda wc: [bad] + real(wc))
    with pytest.raises(SystemExit) as exc:
        serve.main(ARGS)
    assert exc.value.code == 1
    out, err = capsys.readouterr()
    assert "client threads failed" in err
    assert "served" not in out          # a failed run reports no result


@pytest.mark.parametrize("env", [None, "cache-from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(DEFAULT_COMPILE_CACHE)
    else:
        want = str(tmp_path / env)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_COMPILE_CACHE.name == ".jax_cache"
    assert (DEFAULT_COMPILE_CACHE.parent / "chip_smoke.py").exists()

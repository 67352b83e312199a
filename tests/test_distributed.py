"""Multi-device SPMD behaviour (8 fake CPU devices via subprocess —
jax pins the device count at first import, so these run out of process)
— plus the consistent-hash ring that routes plan-cache keys to shards.

The SPMD classes carry the ``slow`` marker individually (JAX-compile
heavy; the fast lane runs ``-m 'not slow'``); the HashRing classes are
pure-python and run everywhere.  The hypothesis classes deepen the ring
properties when hypothesis is installed and skip cleanly otherwise.
"""

import hashlib
import json
import os
import subprocess
import sys
import textwrap
from collections import Counter
from pathlib import Path

import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_spmd(body: str, n_dev: int = 8) -> dict:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_dev}"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
class TestShardingRules:
    def test_lm_rules_specs(self):
        import jax
        import numpy as np

        from repro.configs import get_arch
        from repro.distributed import sharding as shd
        from repro.models import transformer as tf

        arch = get_arch("glm4-9b")
        import jax.numpy as jnp

        cfg = tf.TransformerConfig(name="t", vocab=160, d_model=32,
                                   n_layers=2, n_heads=4, n_kv_heads=2,
                                   d_head=8, d_ff=64)
        avals = jax.eval_shape(
            lambda k: tf.init_params(k, cfg), jax.random.PRNGKey(0))
        specs = shd.param_specs(avals, shd.lm_rules)
        flat = {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                         for p in path): s
                for path, s in
                jax.tree_util.tree_flatten_with_path(
                    specs, is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))[0]}
        assert flat["embed/table"] == jax.sharding.PartitionSpec(
            "model", None)
        # stacked layer weights get a leading None for the scan dim
        assert flat["groups/0/attn/wq"][0] is None
        assert "model" in flat["groups/0/attn/wq"]

    def test_sanitize_drops_undivisible_and_missing(self):
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import sanitize_specs

        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        specs = {"a": P("model", "data"), "b": P(("data", "pod")),
                 "c": P("data")}
        avals = {"a": jax.ShapeDtypeStruct((7, 4), "float32"),
                 "b": jax.ShapeDtypeStruct((8, 2), "float32"),
                 "c": jax.ShapeDtypeStruct((3,), "float32")}
        out = sanitize_specs(specs, avals, mesh)
        assert out["a"] == P(None, "data")   # 'model' axis missing
        assert out["b"] == P("data")          # 'pod' dropped from tuple
        assert out["c"] == P("data")          # 3 % 1 == 0 → kept


@pytest.mark.slow
class TestSPMDExecution:
    def test_sharded_train_step_matches_single_device(self):
        res = run_spmd("""
            from repro.train.optimizer import OptimizerConfig
            from repro.train.train_state import (init_train_state,
                                                 make_train_step)
            from repro.distributed.context import mesh_context

            def loss_fn(params, batch):
                pred = batch["x"] @ params["w"]
                return jnp.mean((pred - batch["y"]) ** 2), {}

            cfg = OptimizerConfig(kind="adamw", lr=0.05,
                                  weight_decay=0.0, warmup_steps=0,
                                  total_steps=10_000)
            key = jax.random.PRNGKey(0)
            params = {"w": jax.random.normal(key, (16, 8))}
            batch = {"x": jax.random.normal(key, (32, 16)),
                     "y": jax.random.normal(key, (32, 8))}
            step = make_train_step(loss_fn, cfg)

            # single-device reference
            s0 = init_train_state(params, cfg)
            ref, _ = jax.jit(step)(s0, batch)

            mesh = jax.make_mesh((2, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
            P_ = P
            with mesh_context(mesh):
                sspec = {"params": {"w": NamedSharding(mesh,
                                                       P_(None, "model"))},
                         "opt": {"m": {"w": NamedSharding(mesh,
                                                          P_("data",
                                                             "model"))},
                                 "v": {"w": NamedSharding(mesh,
                                                          P_("data",
                                                             "model"))},
                                 "step": NamedSharding(mesh, P_())}}
                bspec = {"x": NamedSharding(mesh, P_("data", None)),
                         "y": NamedSharding(mesh, P_("data", None))}
                s1 = init_train_state(params, cfg)
                out, _ = jax.jit(step, in_shardings=(sspec, bspec))(
                    s1, batch)
            err = float(jnp.max(jnp.abs(out["params"]["w"]
                                        - ref["params"]["w"])))
            print(json.dumps({"err": err}))
        """)
        assert res["err"] < 1e-5

    def test_quantized_psum_shard_map(self):
        res = run_spmd("""
            from functools import partial
            from repro.distributed.compression import quantized_psum

            mesh = jax.make_mesh((8,), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
            x = jnp.arange(64.0).reshape(8, 8) / 7.0

            @partial(jax.shard_map, mesh=mesh,
                     in_specs=P("data", None), out_specs=P("data", None))
            def f(xs):
                return quantized_psum(xs, "data")[None] * jnp.ones(
                    (1, 1)) + xs * 0

            out = f(x)
            exact = jnp.sum(x, axis=0)
            err = float(jnp.max(jnp.abs(out[0] - exact)))
            rel = err / float(jnp.max(jnp.abs(exact)))
            print(json.dumps({"rel": rel}))
        """)
        assert res["rel"] < 0.05   # int8 quantisation error bound

    def test_row_sharded_embedding_lookup(self):
        """Row-sharded table + psum lookup == dense lookup (the recsys
        table sharding pattern)."""
        res = run_spmd("""
            from repro.distributed.context import mesh_context
            mesh = jax.make_mesh((8,), ("model",),
                axis_types=(jax.sharding.AxisType.Auto,))
            table = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
            ids = jnp.asarray([0, 5, 63, 17, 33])
            ref = table[ids]
            tsh = jax.device_put(table,
                                 NamedSharding(mesh, P("model", None)))
            with mesh_context(mesh):
                out = jax.jit(lambda t, i: jnp.take(t, i, axis=0))(
                    tsh, ids)
            err = float(jnp.max(jnp.abs(out - ref)))
            print(json.dumps({"err": err}))
        """)
        assert res["err"] == 0.0

    def test_elastic_checkpoint_reshard(self):
        """Save on a (4,2) mesh, restore onto (2,4) — elastic restore."""
        res = run_spmd("""
            import tempfile
            from repro.train.checkpoint import (restore_checkpoint,
                                                save_checkpoint)
            w = jnp.arange(256.0).reshape(16, 16)
            m1 = jax.make_mesh((4, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
            m2 = jax.make_mesh((2, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
            ws = jax.device_put(w, NamedSharding(m1, P("data", "model")))
            with tempfile.TemporaryDirectory() as d:
                save_checkpoint(d, 1, {"w": ws})
                out = restore_checkpoint(
                    d, 1, {"w": jax.ShapeDtypeStruct((16, 16),
                                                     "float32")},
                    {"w": NamedSharding(m2, P("data", "model"))})
            err = float(jnp.max(jnp.abs(out["w"] - w)))
            nsh = len(out["w"].sharding.device_set)
            print(json.dumps({"err": err, "ndev": nsh}))
        """)
        assert res["err"] == 0.0
        assert res["ndev"] == 8


@pytest.mark.slow
class TestDryRunEntry:
    def test_dryrun_cheap_cell_subprocess(self, tmp_path):
        """E2E guard on the dry-run entrypoint: one cheap cell must
        lower + compile on the production 256-chip mesh."""
        env = dict(os.environ, PYTHONPATH=SRC)
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "deepfm", "--shape", "serve_p99",
             "--out", str(tmp_path / "d.json")],
            env=env, capture_output=True, text=True, timeout=560,
            cwd=str(Path(SRC).parent))
        assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
        rec = json.loads((tmp_path / "d.json").read_text())
        cell = rec["deepfm|serve_p99|sp"]
        assert cell["ok"]
        assert cell["n_devices"] == 256
        assert cell["cost"]["flops_per_device"] > 0


# ---------------------------------------------------------------------------
# Consistent-hash routing (plan-cache sharding, DESIGN.md §4)
# ---------------------------------------------------------------------------

def _keys(n, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [hashlib.sha256(rng.bytes(16)).hexdigest() for _ in range(n)]


class TestHashRing:
    def test_route_is_deterministic_and_member(self):
        from repro.distributed.sharding import HashRing

        ring = HashRing(["a", "b", "c", "d"])
        for k in _keys(100, 0):
            owner = ring.route(k)
            assert owner in ring.nodes
            assert ring.route(k) == owner

    def test_key_point_is_64bit_prefix(self):
        from repro.distributed.sharding import (PREFIX_HEX, RING_SPACE,
                                                key_point)

        k = hashlib.sha256(b"polytope").hexdigest()
        assert key_point(k) == int(k[:PREFIX_HEX], 16)
        assert 0 <= key_point(k) < RING_SPACE

    def test_balance_within_tolerance(self):
        from repro.distributed.sharding import HashRing

        ring = HashRing([f"s{i}" for i in range(4)], replicas=64)
        counts = Counter(ring.route(k) for k in _keys(4000, 7))
        for node in ring.nodes:
            share = counts[node] / 4000
            assert 0.10 <= share <= 0.45, f"{node}: {share:.3f}"

    def test_add_node_minimal_directed_remap(self):
        from repro.distributed.sharding import HashRing

        keys = _keys(4000, 11)
        ring = HashRing([f"s{i}" for i in range(4)], replicas=64)
        before = {k: ring.route(k) for k in keys}
        ring.add_node("s4")
        moved = [k for k in keys if ring.route(k) != before[k]]
        frac = len(moved) / len(keys)
        assert 0.10 <= frac <= 0.35, f"remap fraction {frac:.3f}"
        # keys only ever move TO the added node
        assert all(ring.route(k) == "s4" for k in moved)

    def test_remove_node_only_moves_orphans(self):
        from repro.distributed.sharding import HashRing

        keys = _keys(1000, 13)
        ring = HashRing([f"s{i}" for i in range(5)], replicas=64)
        before = {k: ring.route(k) for k in keys}
        ring.remove_node("s2")
        for k in keys:
            if before[k] != "s2":
                assert ring.route(k) == before[k]
            else:
                assert ring.route(k) != "s2"

    def test_topology_errors(self):
        from repro.distributed.sharding import HashRing

        ring = HashRing(["a"])
        with pytest.raises(ValueError):
            ring.add_node("a")
        with pytest.raises(KeyError):
            ring.remove_node("zz")
        with pytest.raises(ValueError):
            HashRing(replicas=0)
        empty = HashRing()
        with pytest.raises(RuntimeError):
            empty.route("ff" * 32)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
class TestHashRingProperties:
    """Property-style deepening of the consistent-hashing contract."""

    if HAVE_HYPOTHESIS:
        key_lists = st.lists(
            st.binary(min_size=4, max_size=32), min_size=100,
            max_size=300, unique=True).map(
                lambda bs: [hashlib.sha256(b).hexdigest() for b in bs])

        @given(n_nodes=st.integers(2, 8), keys=key_lists)
        @settings(max_examples=25, deadline=None)
        def test_balance(self, n_nodes, keys):
            from repro.distributed.sharding import HashRing

            ring = HashRing([f"s{i}" for i in range(n_nodes)],
                            replicas=64)
            counts = Counter(ring.route(k) for k in keys)
            cap = min(1.0, 3.5 / n_nodes)
            assert max(counts.values()) / len(keys) <= cap

        @given(n_nodes=st.integers(2, 8), keys=key_lists)
        @settings(max_examples=25, deadline=None)
        def test_add_moves_keys_only_to_new_node(self, n_nodes, keys):
            from repro.distributed.sharding import HashRing

            ring = HashRing([f"s{i}" for i in range(n_nodes)],
                            replicas=64)
            before = {k: ring.route(k) for k in keys}
            ring.add_node("added")
            moved = [k for k in keys if ring.route(k) != before[k]]
            assert all(ring.route(k) == "added" for k in moved)
            # minimal remap: well under a full reshuffle
            assert len(moved) / len(keys) <= min(1.0,
                                                 4.0 / (n_nodes + 1))

        @given(n_nodes=st.integers(3, 8), keys=key_lists,
               victim=st.integers(0, 7))
        @settings(max_examples=25, deadline=None)
        def test_remove_never_touches_survivors_keys(self, n_nodes,
                                                     keys, victim):
            from repro.distributed.sharding import HashRing

            node = f"s{victim % n_nodes}"
            ring = HashRing([f"s{i}" for i in range(n_nodes)],
                            replicas=64)
            before = {k: ring.route(k) for k in keys}
            ring.remove_node(node)
            for k in keys:
                if before[k] != node:
                    assert ring.route(k) == before[k]

"""Static verification layer (repro.analysis, DESIGN.md §5).

Three analyzer families, each tested two ways:

* fixture corpus — a known-bad snippet per rule, required to fire
  exactly one diagnostic with the expected rule id (and a known-good
  twin required to stay silent);
* the real tree — the analyzers must run clean over src/repro, i.e. the
  CI gate `python -m repro.analysis --all` holds.

Plus plan-check mutation tests: real planner output is mutated (drop an
offset, corrupt a run length, push an offset past 2³¹, …) and every
mutation must be caught.
"""

from __future__ import annotations

import pickle
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (check_lock_source, check_plan, lint_source,
                            lint_tree, verify_plan)
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.concurrency import check_lock_discipline
from repro.analysis.plan_check import PlanVerificationError
from repro.core import (Box, OrderedAxis, Polygon, PolytopeExtractor,
                        Request, Select, TensorDatacube)

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"


def small_cube() -> TensorDatacube:
    return TensorDatacube([
        OrderedAxis("t", np.arange(4.0)),
        OrderedAxis("x", np.arange(16.0)),
        OrderedAxis("y", np.arange(16.0)),
    ])


def small_plan():
    cube = small_cube()
    req = Request([Select("t", [1.0]),
                   Box(("x", "y"), [2.0, 3.0], [9.0, 12.0])])
    plan, stats = PolytopeExtractor(cube).plan(req)
    assert plan.n_points > 2
    return cube, plan, stats


# ---------------------------------------------------------------------------
# plan_check
# ---------------------------------------------------------------------------
class TestPlanCheck:
    def test_clean_plan_verifies(self):
        cube, plan, stats = small_plan()
        assert check_plan(plan, datacube=cube, stats=stats) == []
        verify_plan(plan, datacube=cube, stats=stats)  # must not raise

    def test_polygon_plan_verifies(self):
        cube = small_cube()
        tri = np.array([[2.0, 1.0], [14.0, 5.0], [7.0, 15.0]])
        req = Request([Select("t", [0.0]), Polygon(("x", "y"), tri)])
        plan, stats = PolytopeExtractor(cube).plan(req)
        assert check_plan(plan, datacube=cube, stats=stats) == []

    def _rules(self, plan, cube=None, n_elements=None):
        return {d.rule for d in check_plan(plan, datacube=cube,
                                           n_elements=n_elements)}

    def test_dropped_offset_breaks_run_tiling(self):
        cube, plan, _ = small_plan()
        mid = plan.n_points // 2
        bad = replace(plan, offsets=np.delete(plan.offsets, mid), coords={})
        assert "plan-runs-tile" in self._rules(bad, cube)

    def test_corrupt_run_length_is_caught(self):
        cube, plan, _ = small_plan()
        lengths = plan.run_lengths.copy()
        lengths[0] += 1
        bad = replace(plan, run_lengths=lengths)
        assert "plan-runs-tile" in self._rules(bad, cube)

    def test_zero_run_length_is_caught(self):
        cube, plan, _ = small_plan()
        lengths = plan.run_lengths.copy()
        lengths[0] = 0
        bad = replace(plan, run_lengths=lengths)
        assert "plan-run-length" in self._rules(bad, cube)

    def test_offset_past_2_31_is_caught(self):
        _, plan, _ = small_plan()
        offs = plan.offsets.copy()
        offs[-1] = 2 ** 31 + 5
        bad = replace(plan, offsets=offs, coords={})
        diags = check_plan(bad, n_elements=2 ** 32)
        rules = {d.rule for d in diags}
        assert "plan-i32" in rules
        [i32] = [d for d in diags if d.rule == "plan-i32"]
        assert "int32" in i32.message and "4294967296" in i32.message

    def test_out_of_bounds_offset_is_caught(self):
        cube, plan, _ = small_plan()
        offs = plan.offsets.copy()
        offs[-1] = cube.n_elements + 7
        bad = replace(plan, offsets=offs, coords={})
        assert "plan-bounds" in self._rules(bad, cube)

    def test_negative_offset_is_caught(self):
        cube, plan, _ = small_plan()
        offs = plan.offsets.copy()
        offs[0] = -3
        bad = replace(plan, offsets=offs, coords={})
        assert "plan-bounds" in self._rules(bad, cube)

    def test_unsorted_offsets_are_caught(self):
        cube, plan, _ = small_plan()
        offs = plan.offsets.copy()
        offs[[0, -1]] = offs[[-1, 0]]
        bad = replace(plan, offsets=offs, coords={})
        assert "plan-sorted" in self._rules(bad, cube)

    def test_duplicate_offset_is_caught(self):
        cube, plan, _ = small_plan()
        offs = plan.offsets.copy()
        offs[1] = offs[0]
        bad = replace(plan, offsets=offs, coords={})
        assert "plan-dedup" in self._rules(bad, cube)

    def test_coords_length_mismatch_is_caught(self):
        cube, plan, _ = small_plan()
        bad = replace(plan, coords={"x": np.arange(plan.n_points - 1)})
        assert "plan-coords" in self._rules(bad, cube)

    def test_verify_plan_raises_with_diagnostics(self):
        cube, plan, _ = small_plan()
        bad = replace(plan, offsets=np.delete(plan.offsets, 0), coords={})
        with pytest.raises(PlanVerificationError) as e:
            verify_plan(bad, datacube=cube)
        assert e.value.diagnostics

    def test_slice_bound_violation_is_caught(self):
        cube, plan, stats = small_plan()
        stats.n_slices = 10 ** 9
        assert "plan-slice-bound" in {
            d.rule for d in check_plan(plan, datacube=cube, stats=stats)}


class TestPlanCheckProperty:
    """Hypothesis deepening: every structured mutation of a real plan is
    caught by at least one plan-check rule."""

    def test_random_mutations_are_caught(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings
        from hypothesis import strategies as st

        cube, plan, _ = small_plan()
        n = plan.n_points

        @settings(max_examples=60, deadline=None)
        @given(kind=st.sampled_from(
                   ["drop", "dup", "swap", "oob", "i32", "runlen"]),
               pos=st.integers(min_value=0, max_value=n - 1),
               delta=st.integers(min_value=1, max_value=5))
        def run(kind, pos, delta):
            offs = plan.offsets.copy()
            lengths = plan.run_lengths.copy()
            if kind == "drop":
                offs = np.delete(offs, pos)
            elif kind == "dup":
                offs[pos] = offs[(pos + 1) % n] if n > 1 else offs[pos]
                offs = np.sort(offs)
            elif kind == "swap":
                offs[[0, -1]] = offs[[-1, 0]]
            elif kind == "oob":
                offs[pos] = cube.n_elements + delta
            elif kind == "i32":
                offs[pos] = 2 ** 31 + delta
            elif kind == "runlen":
                lengths[pos % len(lengths)] += delta
            bad = replace(plan, offsets=offs, run_lengths=lengths,
                          coords={})
            assert check_plan(bad, datacube=cube) != []

        run()


# ---------------------------------------------------------------------------
# lint — one bad snippet per rule, each firing exactly one diagnostic
# ---------------------------------------------------------------------------
class TestLintFixtures:
    def test_float32_literal_in_planner_fires_once(self):
        bad = ("import numpy as np\n"
               "def f(x):\n"
               "    return np.asarray(x, dtype=np.float32)\n")
        diags = lint_source(bad, "core/geometry.py")
        assert [d.rule for d in diags] == ["planner-float32"]

    def test_float32_string_dtype_fires_once(self):
        bad = "def f(x):\n    return x.astype('float32')\n"
        diags = lint_source(bad, "core/slicer.py")
        assert [d.rule for d in diags] == ["planner-float32"]

    def test_float64_planner_is_clean(self):
        good = ("import numpy as np\n"
                "def f(x):\n"
                "    return np.asarray(x, dtype=np.float64)\n")
        assert lint_source(good, "core/hull.py") == []

    def test_float32_outside_planner_files_is_allowed(self):
        ok = ("import jax.numpy as jnp\n"
              "def f(x):\n"
              "    return x.astype(jnp.float32)\n")
        assert lint_source(ok, "models/layers.py") == []

    def test_direct_boolean_mask_subscript_fires_once(self):
        bad = ("def load(cube, threshold):\n"
               "    field = cube.read_all()\n"
               "    return field[field > threshold]\n")
        diags = lint_source(bad, "dataplane/foo.py")
        assert [d.rule for d in diags] == ["load-then-filter"]

    def test_mask_variable_subscript_fires_once(self):
        bad = ("def load(cube, threshold):\n"
               "    field = cube.read_all()\n"
               "    mask = field > threshold\n"
               "    return field[mask]\n")
        diags = lint_source(bad, "dataplane/foo.py")
        assert [d.rule for d in diags] == ["load-then-filter"]

    def test_plan_first_dataplane_is_clean(self):
        good = ("def load(cube, request, data):\n"
                "    plan, _ = cube.plan(request)\n"
                "    return data[plan.offsets]\n")
        assert lint_source(good, "dataplane/foo.py") == []

    def test_mask_filter_outside_dataplane_is_allowed(self):
        ok = "def f(x):\n    return x[x > 0]\n"
        assert lint_source(ok, "benchmarks_helper.py") == []

    def test_unguarded_i32_cast_fires_once(self):
        bad = ("import numpy as np\n"
               "def f(offsets):\n"
               "    return offsets.astype(np.int32)\n")
        diags = lint_source(bad, "core/foo.py")
        assert [d.rule for d in diags] == ["unchecked-i32-cast"]

    def test_i32_constructor_cast_fires_once(self):
        bad = ("import jax.numpy as jnp\n"
               "def f(off):\n"
               "    return jnp.int32(off)\n")
        diags = lint_source(bad, "serve/foo.py")
        assert [d.rule for d in diags] == ["unchecked-i32-cast"]

    def test_raw_cast_in_paged_attn_fires_once(self):
        bad = ("import jax.numpy as jnp\n"
               "def f(block_table):\n"
               "    return block_table.astype(jnp.int32)\n")
        diags = lint_source(bad, "kernels/paged_attn/kernel.py")
        assert [d.rule for d in diags] == ["unchecked-i32-cast"]

    def test_checked_cast_in_paged_attn_is_clean(self):
        good = ("from repro.kernels._casting import checked_cast_i32\n"
                "def f(block_table, n_pages):\n"
                "    return checked_cast_i32(block_table,\n"
                "                            n_elements=n_pages,\n"
                "                            allow_negative_one=True)\n")
        assert lint_source(good, "kernels/paged_attn/kernel.py") == []

    def test_raw_cast_in_segment_fires_once(self):
        bad = ("import numpy as np\n"
               "def f(segment_ids):\n"
               "    return np.int32(segment_ids)\n")
        diags = lint_source(bad, "kernels/segment/kernel.py")
        assert [d.rule for d in diags] == ["unchecked-i32-cast"]

    def test_checked_cast_in_segment_is_clean(self):
        good = ("from repro.kernels._casting import checked_cast_i32\n"
                "def f(segment_ids, num_segments):\n"
                "    return checked_cast_i32(segment_ids,\n"
                "                            n_elements=num_segments,\n"
                "                            allow_negative_one=True)\n")
        assert lint_source(good, "kernels/segment/kernel.py") == []

    def test_raw_cast_in_slice_fires_once(self):
        bad = ("import jax.numpy as jnp\n"
               "def f(plane_rows):\n"
               "    return plane_rows.astype(jnp.int32)\n")
        diags = lint_source(bad, "kernels/slice/ref.py")
        assert [d.rule for d in diags] == ["unchecked-i32-cast"]

    def test_raw_cast_in_plan_fires_once(self):
        bad = ("import jax.numpy as jnp\n"
               "def f(run_starts):\n"
               "    return jnp.int32(run_starts)\n")
        diags = lint_source(bad, "kernels/plan/kernel.py")
        assert [d.rule for d in diags] == ["unchecked-i32-cast"]

    def test_typed_arange_in_plan_is_clean(self):
        # dtype= arguments are not casts — the plan pipeline builds its
        # int32 ramps this way (offsets validated upstream by
        # ensure_i32_addressable / checked_cast_i32).
        good = ("import jax.numpy as jnp\n"
                "def f(ok, n0, n1):\n"
                "    rowoff = jnp.arange(0, n0 * n1, n1, dtype=jnp.int32)\n"
                "    return rowoff, jnp.cumsum(ok, dtype=jnp.int32)\n")
        assert lint_source(good, "kernels/plan/ref.py") == []

    def test_cast_in_uncovered_kernel_dir_is_allowed(self):
        ok = ("import jax.numpy as jnp\n"
              "def f(x):\n"
              "    return x.astype(jnp.int32)\n")
        assert lint_source(ok, "kernels/experimental/foo.py") == []

    def test_cast_in_helper_module_is_allowed(self):
        ok = ("import numpy as np\n"
              "def checked_cast_i32(x):\n"
              "    return x.astype(np.int32)\n")
        assert lint_source(ok, "kernels/_casting.py") == []

    def test_pragma_suppresses_rule(self):
        ok = ("import numpy as np\n"
              "def f(ids):\n"
              "    return ids.astype(np.int32)  "
              "# lint-ok: unchecked-i32-cast\n")
        assert lint_source(ok, "core/foo.py") == []

    def test_i64_cast_is_allowed(self):
        ok = ("import numpy as np\n"
              "def f(offsets):\n"
              "    return offsets.astype(np.int64)\n")
        assert lint_source(ok, "core/foo.py") == []


# ---------------------------------------------------------------------------
# concurrency — lock-discipline fixtures
# ---------------------------------------------------------------------------
LOCKED_BAD = """
import threading

class Service:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0

    def bump(self):
        with self._lock:
            self.count += 1

    def peek(self):
        return self.count
"""

LOCKED_GOOD = LOCKED_BAD.replace(
    "    def peek(self):\n        return self.count\n",
    "    def peek(self):\n        with self._lock:\n"
    "            return self.count\n")

LOCKED_PRAGMA = LOCKED_BAD.replace(
    "        return self.count\n",
    "        return self.count  # unlocked-ok: monotonic probe\n")


class TestLockDiscipline:
    def test_unguarded_read_fires_once(self):
        diags = check_lock_source(LOCKED_BAD, "serve/foo.py")
        assert [d.rule for d in diags] == ["lock-discipline"]
        assert "Service.count" in diags[0].message

    def test_guarded_read_is_clean(self):
        assert check_lock_source(LOCKED_GOOD, "serve/foo.py") == []

    def test_pragma_waives_with_reason(self):
        assert check_lock_source(LOCKED_PRAGMA, "serve/foo.py") == []

    def test_init_writes_are_exempt(self):
        src = ("import threading\n"
               "class S:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self.state = {}\n"
               "    def put(self, k, v):\n"
               "        with self._lock:\n"
               "            self.state[k] = v\n")
        assert check_lock_source(src, "serve/foo.py") == []

    def test_attribute_chain_root_is_protected(self):
        src = ("import threading\n"
               "class S:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self.cache = object()\n"
               "    def record(self):\n"
               "        with self._lock:\n"
               "            self.cache.stats.hits += 1\n"
               "    def probe(self):\n"
               "        return self.cache.stats.hits\n")
        diags = check_lock_source(src, "serve/foo.py")
        assert [d.rule for d in diags] == ["lock-discipline"]

    def test_unguarded_write_fires(self):
        src = ("import threading\n"
               "class S:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self.n = 0\n"
               "    def a(self):\n"
               "        with self._lock:\n"
               "            self.n = 1\n"
               "    def b(self):\n"
               "        self.n = 2\n")
        diags = check_lock_source(src, "serve/foo.py")
        assert [d.rule for d in diags] == ["lock-discipline"]

    def test_lockless_class_is_ignored(self):
        src = ("class Plain:\n"
               "    def __init__(self):\n"
               "        self.x = 0\n"
               "    def f(self):\n"
               "        self.x += 1\n")
        assert check_lock_source(src, "dataplane/foo.py") == []


# The bug PR 5 fixed: PlanCache guarded its writes (put/get under the
# service lock) but left keys()/__contains__ reading the OrderedDict
# bare — an iterating reader races a concurrently mutating writer.
# This fixture is the pre-fix shape; the checker must flag it so the
# regression cannot quietly come back.
PLAN_CACHE_RACE = """
import threading
from collections import OrderedDict

class PlanCache:
    def __init__(self, capacity=1024):
        self._lock = threading.Lock()
        self._od = OrderedDict()
        self.capacity = capacity

    def put(self, key, plan):
        with self._lock:
            self._od[key] = plan

    def __contains__(self, key):
        return key in self._od

    def keys(self):
        return list(self._od)
"""

PLAN_CACHE_FIXED = PLAN_CACHE_RACE.replace(
    "    def __contains__(self, key):\n"
    "        return key in self._od\n",
    "    def __contains__(self, key):\n"
    "        with self._lock:\n"
    "            return key in self._od\n").replace(
    "    def keys(self):\n"
    "        return list(self._od)\n",
    "    def keys(self):\n"
    "        with self._lock:\n"
    "            return list(self._od)\n")


class TestPlanCacheLockRegression:
    def test_unsynchronized_cache_reads_are_flagged(self):
        diags = check_lock_source(PLAN_CACHE_RACE, "serve/extraction.py")
        assert diags and all(d.rule == "lock-discipline" for d in diags)
        # both bare readers fire: __contains__ and keys()
        assert len(diags) == 2
        assert all("PlanCache._od" in d.message for d in diags)

    def test_guarded_cache_reads_are_clean(self):
        assert check_lock_source(PLAN_CACHE_FIXED,
                                 "serve/extraction.py") == []

    def test_real_plan_cache_state_is_inferred(self):
        # The shipped PlanCache must expose its state to the checker:
        # _od and stats inferred protected, every access lock-guarded.
        import ast

        from repro.analysis.concurrency import _ProtectedCollector

        src = (SRC / "serve" / "extraction.py").read_text()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.ClassDef) and node.name == "PlanCache":
                c = _ProtectedCollector()
                for stmt in node.body:
                    c.visit(stmt)
                assert "_lock" in c.locks
                assert "_od" in c.protected
                assert "stats" in c.protected
                return
        pytest.fail("PlanCache not found")


# ---------------------------------------------------------------------------
# the real tree must be clean (the CI gate)
# ---------------------------------------------------------------------------
class TestRepoTreeClean:
    def test_lint_clean_on_src(self):
        assert [str(d) for d in lint_tree(SRC)] == []

    def test_lock_discipline_clean_on_src(self):
        assert [str(d) for d in check_lock_discipline(SRC)] == []

    def test_service_lock_state_is_inferred(self):
        # The checker must actually see the admission queue's protected
        # state — guard against the rule silently matching nothing.
        import ast

        from repro.analysis.concurrency import _ProtectedCollector

        src = (SRC / "serve" / "sharded.py").read_text()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.ClassDef) \
                    and node.name == "AdmissionQueue":
                c = _ProtectedCollector()
                for stmt in node.body:
                    c.visit(stmt)
                assert "_lock" in c.locks
                assert {"_pending", "_stamps", "_closed",
                        "stats"} <= c.protected
                return
        pytest.fail("AdmissionQueue not found")


# ---------------------------------------------------------------------------
# the command line (python -m repro.analysis)
# ---------------------------------------------------------------------------
class TestCli:
    def test_all_is_clean_on_the_tree(self, capsys):
        # the CI gate: lint + locks + planner self-check
        assert analysis_main(["--all"]) == 0
        assert "all checks clean" in capsys.readouterr().out

    def test_no_check_asked_is_a_usage_error(self, capsys):
        assert analysis_main([]) == 2
        assert "usage" in capsys.readouterr().out

    @pytest.mark.parametrize("form,rc", [("envelope", 0), ("bare", 0),
                                         ("truncated", 1)])
    def test_plan_file(self, tmp_path, capsys, form, rc):
        from repro.serve.sharded import serialize_plan

        cube, plan, _ = small_plan()
        blob = serialize_plan("key", plan, n_elements=cube.n_elements)
        if form == "bare":
            blob = pickle.dumps(plan)
        elif form == "truncated":
            blob = blob[:len(blob) // 2]
        path = tmp_path / "plan.pkl"
        path.write_bytes(blob)
        assert analysis_main(["--plan", str(path)]) == rc
        if rc:
            assert "plan-file" in capsys.readouterr().err

    def test_lint_diagnostic_fails_the_command(self, tmp_path, capsys):
        bad = tmp_path / "core" / "slicer.py"
        bad.parent.mkdir()
        bad.write_text("def f(x):\n    return x.astype('float32')\n")
        assert analysis_main(["--lint", "--root", str(tmp_path)]) == 1
        assert "1 diagnostic(s)" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# checked_cast_i32 — the helper the lint rule funnels everything through
# ---------------------------------------------------------------------------
class TestCheckedCast:
    def test_valid_offsets_cast(self):
        from repro.kernels import checked_cast_i32

        out = checked_cast_i32(np.array([0, 5, 9], np.int64), n_elements=10)
        assert out.dtype == np.int32
        np.testing.assert_array_equal(out, [0, 5, 9])

    def test_overflow_raises_naming_cube_size(self):
        from repro.kernels import checked_cast_i32

        with pytest.raises(OverflowError, match="int32"):
            checked_cast_i32(np.array([2 ** 31 + 3], np.int64))

    def test_index_space_overflow_raises_before_values(self):
        from repro.kernels import checked_cast_i32

        with pytest.raises(OverflowError, match="2147483647"):
            checked_cast_i32(np.array([0], np.int64),
                             n_elements=2 ** 31 + 1)

    def test_out_of_bounds_raises(self):
        from repro.kernels import checked_cast_i32

        with pytest.raises(IndexError):
            checked_cast_i32(np.array([10], np.int64), n_elements=10)

    def test_negative_rejected_unless_padding(self):
        from repro.kernels import checked_cast_i32

        with pytest.raises(IndexError):
            checked_cast_i32(np.array([-1, 3], np.int64), n_elements=10)
        out = checked_cast_i32(np.array([-1, 3], np.int64), n_elements=10,
                               allow_negative_one=True)
        np.testing.assert_array_equal(out, [-1, 3])
        with pytest.raises(IndexError):
            checked_cast_i32(np.array([-2], np.int64), n_elements=10,
                             allow_negative_one=True)

    def test_gather_ref_rejects_oob_rows(self):
        jnp = pytest.importorskip("jax.numpy")
        from repro.kernels.gather import ref

        table = jnp.arange(12.0).reshape(4, 3)
        with pytest.raises(IndexError):
            ref.gather_rows(table, jnp.array([0, 4]))


# ---------------------------------------------------------------------------
# verify=True end-to-end (acceptance: PR 3 weather example, zero diags)
# ---------------------------------------------------------------------------
class TestServiceVerify:
    def test_irregular_weather_round_trip_verified(self):
        from repro.dataplane.weather import IrregularWeatherCube
        from repro.serve.sharded import ShardedExtractionService

        iwc = IrregularWeatherCube(n_lat=48, n_lon=96)
        data = iwc.field_data(seed=3)
        svc = ShardedExtractionService(iwc.cube, shards=1, verify=True)
        for req in (iwc.country_request("uk"),
                    iwc.seam_box_request(40.0, 60.0, -20.0, 20.0),
                    iwc.timeseries_request(51.5, 0.0, 43200.0,
                                           86400.0 + 43200.0)):
            res = svc.extract(req, data)
            assert res.plan.n_points > 0
            np.testing.assert_array_equal(res.values,
                                          data[res.plan.offsets])

    def test_verify_rejects_corrupted_plan(self):
        cube, plan, stats = small_plan()
        from repro.core.slicer import Slicer

        slicer = Slicer(cube, verify=True)
        # sanity: verified planning works
        p2, _ = slicer.extract_plan(
            Request([Select("t", [0.0]),
                     Box(("x", "y"), [1.0, 1.0], [3.0, 3.0])]))
        assert p2.n_points == 9

"""Observability layer: spans, metrics registry, run manifests.

The two load-bearing guarantees tested here:

* enabling observability is invisible to the compiler — the lowered HLO of
  a distributed solve is bit-identical with obs on vs off (spans live at
  trace time, metric emission is tracer-guarded);
* the collective counts the launch path emits into ``events.jsonl`` match
  the HLO ground truth recomputed from the same lowering, across
  {bicgstab, pipelined_bicgstab} x {blocking, overlap} on a real
  multi-device fabric.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.obs import manifest, metrics, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- spans --


class TestSpans:
    def test_disabled_returns_noop_singleton(self):
        trace.disable()
        s1, s2 = trace.span("a"), trace.span("b", k=1)
        assert s1 is s2  # one shared _NullSpan: no per-call allocation
        with s1 as sp:
            assert sp.block(123) == 123
            sp.set(x=1)
        assert trace.events() == []

    def test_nesting_and_monotonic_timing(self):
        trace.enable()
        with trace.span("outer"):
            with trace.span("inner", k=2):
                time.sleep(0.002)
        evs = trace.events()
        # inner exits (and records) first
        assert [e["name"] for e in evs] == ["inner", "outer"]
        inner, outer = evs
        assert inner["parent"] == "outer" and inner["depth"] == 1
        assert outer["parent"] is None and outer["depth"] == 0
        assert inner["attrs"]["k"] == 2
        assert outer["ts_us"] <= inner["ts_us"]
        assert inner["dur_us"] > 0
        assert outer["dur_us"] >= inner["dur_us"]

    def test_set_attaches_attrs_after_entry(self):
        trace.enable()
        with trace.span("s") as sp:
            sp.set(result="ok")
        assert trace.events()[-1]["attrs"]["result"] == "ok"

    def test_chrome_trace_document(self):
        trace.enable()
        with trace.span("x", tag="t"):
            pass
        doc = trace.chrome_trace()
        assert doc["traceEvents"]
        ev = doc["traceEvents"][0]
        assert ev["ph"] == "X" and ev["name"] == "x"
        assert {"ts", "dur", "pid", "tid", "args"} <= set(ev)
        assert ev["args"]["tag"] == "t"
        json.dumps(doc)  # Perfetto needs plain JSON

    def test_block_syncs_only_when_enabled(self):
        import jax.numpy as jnp

        trace.enable(sync=True)
        with trace.span("s") as sp:
            out = sp.block(jnp.ones(3) * 2)
        np.testing.assert_array_equal(np.asarray(out), 2.0)


# -------------------------------------------------------------- metrics --


class TestMetrics:
    def test_counters_gauges_histograms(self):
        metrics.counter("c").inc()
        metrics.counter("c").inc(2)
        metrics.gauge("g").set(3.5)
        h = metrics.histogram("h")
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        snap = metrics.snapshot()
        assert snap["counters"]["c"] == 3
        assert snap["gauges"]["g"] == 3.5
        hs = snap["histograms"]["h"]
        assert hs["count"] == 3 and hs["min"] == 1.0 and hs["max"] == 3.0

    def test_reset_isolation_between_tests(self):
        # the autouse conftest fixture wiped the previous test's registry
        snap = metrics.snapshot()
        assert "c" not in snap["counters"] and "g" not in snap["gauges"]

    def test_events_are_ordered_dicts(self):
        metrics.event("e1", a=1)
        metrics.event("e2", kind="payload-field")  # 'kind' as a data field
        evs = metrics.events()
        assert evs[-2]["event"] == "e1" and evs[-2]["a"] == 1
        assert evs[-1]["event"] == "e2" and evs[-1]["kind"] == "payload-field"

    def test_count_collectives(self):
        text = "all-reduce x all_reduce y collective-permute collective_permute"
        assert metrics.count_collectives(text) == {
            "allreduce_total": 2, "ppermute_total": 2}

    def test_is_concrete_rejects_tracers(self):
        import jax
        import jax.numpy as jnp

        assert metrics.is_concrete(np.ones(3))
        assert metrics.is_concrete(jnp.ones(3))
        seen = []

        @jax.jit
        def f(x):
            seen.append(metrics.is_concrete(x))
            return x
        f(jnp.ones(3))
        assert seen == [False]

    def test_emit_solve_metrics_end_to_end(self):
        import jax

        from repro.core import bicgstab, precision, stencil
        from repro.core.solvers.common import emit_solve_metrics
        from repro.launch.mesh import make_mesh_for_devices

        shape = (8, 8, 8)
        cf = stencil.poisson(shape)
        x_true = jax.random.normal(jax.random.PRNGKey(0), shape)
        b = stencil.rhs_for_solution(cf, x_true)
        res = bicgstab.solve_distributed(
            make_mesh_for_devices(), cf, b, tol=1e-5, maxiter=100,
            policy=precision.F32)
        emit_solve_metrics(res, wall_s=0.1, solver="bicgstab")
        snap = metrics.snapshot()
        assert snap["counters"]["solve.total"] == 1
        assert snap["counters"]["solve.rhs_converged"] == 1
        assert snap["gauges"]["solve.iterations_max"] >= 1
        ev = [e for e in metrics.events() if e["event"] == "solve"][-1]
        assert ev["solver"] == "bicgstab" and ev["converged"] == [True]


# --------------------------------------------------- HLO invariance -----


class TestHLOInvariance:
    def test_obs_enabled_hlo_is_bit_identical(self):
        """The acceptance guarantee: spans/metrics insert no ops."""
        import jax
        import jax.numpy as jnp

        from repro.core import bicgstab, precision, stencil
        from repro.launch.mesh import make_mesh_for_devices

        mesh = make_mesh_for_devices()
        shape = (8, 8, 8)
        cf = stencil.poisson(shape)
        b = jnp.ones(shape, jnp.float32)

        def f(c, v):
            return bicgstab.solve_distributed(
                mesh, c, v, tol=0.0, maxiter=4, policy=precision.F32,
                schedule="overlap")

        trace.disable()
        off = jax.jit(f).lower(cf, b).as_text()
        trace.enable(sync=True)
        on = jax.jit(f).lower(cf, b).as_text()
        assert off == on


# ------------------------------------------------------------ manifests --


class TestManifest:
    def test_round_trip(self, tmp_path):
        trace.enable()
        with trace.span("unit.work"):
            pass
        metrics.counter("unit.count").inc()
        run_dir = str(tmp_path / "run")
        ctx = manifest.start_run("unittest", config={"a": 1, "shape": (4, 4)},
                                 run_dir=run_dir)
        man = manifest.finish_run(ctx, extra={"note": "x"})

        assert manifest.validate_manifest(man) == []
        loaded = manifest.load_manifest(run_dir)
        assert manifest.validate_manifest(loaded) == []
        assert loaded["kind"] == "unittest"
        assert loaded["config"] == {"a": 1, "shape": [4, 4]}
        assert loaded["note"] == "x"
        assert loaded["metrics"]["counters"]["unit.count"] == 1

        with open(os.path.join(run_dir, "events.jsonl")) as f:
            evs = [json.loads(line) for line in f]
        assert evs[0]["event"] == "run_start"
        assert evs[-1]["event"] == "run_finish"
        assert evs[0]["run_id"] == man["run_id"]

        with open(os.path.join(run_dir, "trace.json")) as f:
            doc = json.load(f)
        assert any(e["name"] == "unit.work" for e in doc["traceEvents"])

    def test_validate_catches_missing_fields(self):
        problems = manifest.validate_manifest({"schema": "bogus"})
        assert any("run_id" in p for p in problems)
        assert any("bogus" in p for p in problems)


# ------------------------------- emitted counts vs HLO ground truth -----


_COUNTS_SNIPPET = """
import json, os, tempfile
import jax, jax.numpy as jnp
from repro.core import bicgstab, precision, stencil
from repro.launch.mesh import make_mesh_for_devices
from repro.obs import manifest, metrics, trace

trace.enable()
mesh = make_mesh_for_devices(8)
shape = (8, 8, 8)
cf = stencil.poisson(shape)
b = jnp.ones(shape, jnp.float32)
run_dir = tempfile.mkdtemp()
ctx = manifest.start_run("hlo-counts", run_dir=run_dir)
truth = {}
for solver in ("bicgstab", "pipelined_bicgstab"):
    for schedule in ("blocking", "overlap"):
        def f(c, v, solver=solver, schedule=schedule):
            return bicgstab.solve_distributed(
                mesh, c, v, tol=0.0, maxiter=6, policy=precision.F32,
                solver=solver, schedule=schedule)
        text = jax.jit(f).lower(cf, b).as_text()
        truth[f"{solver}/{schedule}"] = metrics.count_collectives(text)
        metrics.record_collectives(text, solver=solver, schedule=schedule)
manifest.finish_run(ctx)
with open(os.path.join(run_dir, "events.jsonl")) as f:
    events = [json.loads(line) for line in f if line.strip()]
emitted = {
    f"{e['solver']}/{e['schedule']}": {
        "allreduce_total": e["allreduce_total"],
        "ppermute_total": e["ppermute_total"]}
    for e in events if e["event"] == "collectives"}
print(json.dumps({"truth": truth, "emitted": emitted}))
"""


def test_emitted_collective_counts_match_hlo(subproc):
    """events.jsonl collective counts == HLO ground truth, and the totals
    match the analytic schedule: 1 setup AllReduce + per-iteration
    {bicgstab: 3, pipelined_bicgstab: 1}; ppermutes schedule-independent."""
    out = subproc(_COUNTS_SNIPPET, n_devices=8)
    data = json.loads(out.strip().splitlines()[-1])
    truth, emitted = data["truth"], data["emitted"]

    assert emitted == truth  # what we logged IS what the compiler lowered
    want_allreduce = {"bicgstab": 1 + 3, "pipelined_bicgstab": 1 + 1}
    for solver, want in want_allreduce.items():
        for schedule in ("blocking", "overlap"):
            c = emitted[f"{solver}/{schedule}"]
            assert c["allreduce_total"] == want, (solver, schedule, c)
            assert c["ppermute_total"] > 0, (solver, schedule, c)
        # overlap restructures the halo exchange but must not add messages
        assert (emitted[f"{solver}/blocking"]["ppermute_total"]
                == emitted[f"{solver}/overlap"]["ppermute_total"])


# --------------------------------------------------------- compare_runs --


class TestCompareRuns:
    def _bundle(self, path, iters):
        metrics.reset()
        trace.reset()
        metrics.gauge("solve.iterations_max").set(iters)
        ctx = manifest.start_run("solve", run_dir=str(path))
        manifest.finish_run(ctx)

    def _compare(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "compare_runs.py"),
             *map(str, argv)],
            capture_output=True, text=True, cwd=REPO)

    def test_equal_runs_exit_zero(self, tmp_path):
        self._bundle(tmp_path / "base", 10)
        out = self._compare(tmp_path / "base", tmp_path / "base")
        assert out.returncode == 0, out.stdout + out.stderr

    def test_injected_iteration_regression_exits_nonzero(self, tmp_path):
        self._bundle(tmp_path / "base", 10)
        self._bundle(tmp_path / "cand", 15)
        out = self._compare(tmp_path / "base", tmp_path / "cand")
        assert out.returncode == 1, out.stdout + out.stderr
        assert "REGRESSION" in out.stdout
        assert "solve.iterations_max" in out.stderr

    def test_threshold_waives_regression(self, tmp_path):
        self._bundle(tmp_path / "base", 10)
        self._bundle(tmp_path / "cand", 15)
        out = self._compare(tmp_path / "base", tmp_path / "cand",
                            "--max-iter-increase-pct", "60")
        assert out.returncode == 0, out.stdout + out.stderr

"""Process-local metrics registry: counters, gauges, histograms, events.

One global :class:`Registry` collects everything a run emits — solver
iterations and per-RHS convergence (from ``SolveResult``), residual
histories, AllReduce/ppermute counts (the HLO-counting idiom the tests
use, lifted here as :func:`count_collectives`), ``kernels/stencil_nd``
launch counts and tuning-cache hit/miss/stale.

The registry is always on (counter bumps are a dict lookup + integer
add); *spans* are the opt-in part of observability.  Tests get a clean
slate from the autouse reset fixture in ``tests/conftest.py``.

Instrumented code must only feed **concrete** values: inside jit the
fields of a ``SolveResult`` are tracers, so :func:`record_solve` guards
with :func:`is_concrete` and silently no-ops under tracing — emission
happens at the driver level where results are real arrays.
"""

from __future__ import annotations

import threading
import time


def is_concrete(x) -> bool:
    """True when ``x`` can be read as a host value (not a jax tracer)."""
    import numpy as np

    try:
        np.asarray(x)
        return True
    except Exception:
        return False


class Counter:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = None

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Streaming summary + a bounded reservoir of raw observations."""

    MAX_SAMPLES = 1024
    __slots__ = ("count", "total", "min", "max", "last", "samples")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self.last = None
        self.samples: list[float] = []

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        self.last = v
        if len(self.samples) < self.MAX_SAMPLES:
            self.samples.append(v)

    def summary(self) -> dict:
        mean = self.total / self.count if self.count else None
        return {"count": self.count, "total": self.total, "mean": mean,
                "min": self.min, "max": self.max, "last": self.last}


class Registry:
    """Process-local named metrics plus an append-only event log."""

    MAX_EVENTS = 100_000

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: dict[str, Counter] = {}
        self.gauges: dict[str, Gauge] = {}
        self.histograms: dict[str, Histogram] = {}
        self.events: list[dict] = []

    def counter(self, name: str) -> Counter:
        with self._lock:
            return self.counters.setdefault(name, Counter())

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            return self.gauges.setdefault(name, Gauge())

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            return self.histograms.setdefault(name, Histogram())

    def event(self, kind: str, /, **fields) -> dict:
        ev = {"ts": time.time(), "event": kind, **fields}
        with self._lock:
            if len(self.events) < self.MAX_EVENTS:
                self.events.append(ev)
        return ev

    def snapshot(self) -> dict:
        """JSON-ready view of every metric (events excluded — they go to
        ``events.jsonl`` via the run manifest)."""
        with self._lock:
            return {
                "counters": {k: c.value for k, c in self.counters.items()},
                "gauges": {k: g.value for k, g in self.gauges.items()},
                "histograms": {k: h.summary()
                               for k, h in self.histograms.items()},
            }

    def reset(self) -> None:
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()
            self.events.clear()


REGISTRY = Registry()

# Module-level conveniences bound to the global registry.
counter = REGISTRY.counter
gauge = REGISTRY.gauge
histogram = REGISTRY.histogram
event = REGISTRY.event
snapshot = REGISTRY.snapshot
reset = REGISTRY.reset


def events() -> list[dict]:
    with REGISTRY._lock:
        return list(REGISTRY.events)


# ---------------------------------------------------------------------------
# HLO collective counting — the exact idiom the tests/benchmarks assert with
# (both mnemonic spellings appear across StableHLO/HLO dumps).

def count_collectives(hlo_text: str) -> dict:
    """AllReduce / ppermute totals in lowered HLO (or StableHLO) text."""
    return {
        "allreduce_total": (hlo_text.count("all_reduce")
                            + hlo_text.count("all-reduce")),
        "ppermute_total": (hlo_text.count("collective_permute")
                           + hlo_text.count("collective-permute")),
    }


def record_collectives(hlo_text: str, **labels) -> dict:
    """Count collectives in ``hlo_text``, mirror into gauges, and append a
    ``collectives`` event carrying the labels (solver, schedule, nrhs...)."""
    counts = count_collectives(hlo_text)
    prefix = labels.get("solver", "solve")
    gauge(f"collectives.{prefix}.allreduce_total").set(
        counts["allreduce_total"])
    gauge(f"collectives.{prefix}.ppermute_total").set(
        counts["ppermute_total"])
    event("collectives", **labels, **counts)
    return counts


# ---------------------------------------------------------------------------
# Per-solve emission from a SolveResult (solver-agnostic: the pipelined
# solvers share the generic history semantics — see core/solvers/pipelined).

def record_solve(result, *, wall_s: float | None = None, **labels) -> dict | None:
    """Emit iterations / convergence / residual metrics for one solve.

    ``result`` is any ``SolveResult``-shaped object.  No-ops (returns
    ``None``) when the fields are tracers, i.e. when called under jit —
    emission belongs at the driver level where values are concrete.
    """
    import numpy as np

    if not is_concrete(result.iterations):
        return None
    iters = np.asarray(result.iterations)
    conv = np.asarray(result.converged)
    rel = np.asarray(result.rel_residual)
    brk = np.asarray(result.breakdown)
    n_rhs = int(iters.size)

    counter("solve.total").inc()
    counter("solve.rhs_total").inc(n_rhs)
    counter("solve.rhs_converged").inc(int(conv.sum()))
    counter("solve.breakdowns").inc(int(brk.sum()))
    for it in iters.reshape(-1):
        histogram("solve.iterations").observe(float(it))
    gauge("solve.iterations_max").set(float(iters.max()))
    gauge("solve.rel_residual_max").set(float(rel.max()))
    if wall_s is not None:
        histogram("solve.wall_s").observe(wall_s)
        gauge("solve.solves_per_sec").set(n_rhs / wall_s if wall_s else 0.0)

    ev = {
        "iterations": np.asarray(iters).reshape(-1).astype(int).tolist(),
        "converged": conv.reshape(-1).astype(bool).tolist(),
        "rel_residual": rel.reshape(-1).astype(float).tolist(),
        "breakdown": brk.reshape(-1).astype(bool).tolist(),
        "n_rhs": n_rhs,
    }
    if wall_s is not None:
        ev["wall_s"] = wall_s
    hist = getattr(result, "history", None)
    if hist is not None and is_concrete(hist):
        h = np.asarray(hist, dtype=float)
        # history[k] = relative residual after iteration k+1 (all solvers)
        ev["history"] = h[: int(iters.max())].tolist()
    return event("solve", **labels, **ev)

"""Unified observability layer: spans, metrics, and run manifests.

Three small, dependency-light modules threaded through the solver stack:

* :mod:`repro.obs.trace` — the solve's layer scopes (``jax.named_scope``
  names inside the compiled program) and nestable context-manager spans
  with opt-in ``block_until_ready`` device-sync timing, Chrome-trace-event
  (Perfetto-loadable) export, and a ``jax.profiler.TraceAnnotation`` each
  on the profiler's clock.  Spans live *outside* jit: enabling them
  cannot change lowered HLO (asserted in tests/test_obs.py).
* :mod:`repro.obs.metrics` — a process-local registry of counters,
  gauges, histograms and structured events: solver iterations, per-RHS
  convergence, AllReduce/ppermute counts, kernel launch counts,
  tuning-cache hit/miss/stale.
* :mod:`repro.obs.manifest` — run bundles under
  ``results/runs/<run_id>/{manifest.json,events.jsonl,trace.json}``
  with a versioned ``repro.obs.v1`` schema (config cell, git SHA,
  jax/jaxlib versions, device topology, XLA/env flags).

Nothing in this package imports from ``repro.core`` — the core modules
import *us*, so the dependency edge only points one way.
"""

from repro.obs import manifest, metrics, trace

__all__ = ["manifest", "metrics", "trace"]

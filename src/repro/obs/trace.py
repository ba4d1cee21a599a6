"""Nestable spans with Chrome-trace export; zero-cost no-ops when disabled.

Two instruments, one for each side of jit:

* **Scopes** name the solve's layers *inside* the compiled program.
  :data:`SCOPES` lists them; each is a ``jax.named_scope`` opened (via
  :func:`scope`) at the one place where that work is composed, so every
  HLO instruction it lowers to carries the name in its ``op_name``
  metadata.  Scopes change metadata only, never the program.
* **Spans** time host-side regions (compile, a whole solve, a CFD outer
  step).  They are plain Python context managers and insert no jaxprs,
  which is why enabling observability cannot change lowered HLO (tests
  assert bit-identical HLO text with obs on/off).  While enabled, each
  span also enters a ``jax.profiler.TraceAnnotation`` of its name, so a
  profiler trace shows it on the host lines of the same ``.xplane.pb``
  as the device's operations, on the profiler's clock.

To time device work, opt into sync timing (``enable(sync=True)`` or a
per-span ``sync=True``) and hand the span the values to wait on::

    with trace.span("solve", solver="bicgstab") as sp:
        res = solve(...)
        sp.block(res.x)        # block_until_ready iff sync timing is on

``chrome_trace()`` returns the completed spans as Chrome trace events
(``ph: "X"``, microsecond timestamps) — load the exported ``trace.json``
at https://ui.perfetto.dev.  ``profile(dir)`` wraps a region in
``jax.profiler.trace`` for the ``--profile`` launch flag.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time

#: The solve's layers, each opened by :func:`scope` where its work is
#: composed: ``spmv`` around every backend's ``LinearOperator.apply``,
#: ``halo`` around ``core.halo.gather_halo`` (pads, slab slices,
#: ppermutes; nested in ``spmv``), ``dots`` around the local partials and
#: their AllReduce, ``update`` around the AXPY family and the fused update
#: kernels, ``precond`` around a right preconditioner's apply.
SCOPES = ("spmv", "halo", "dots", "update", "precond")

_ENABLED = False
_SYNC = False
_EVENTS: list[dict] = []
_LOCK = threading.Lock()
_TLS = threading.local()
# Process epoch: Chrome trace timestamps are relative microseconds.
_EPOCH = time.perf_counter()


def enable(*, sync: bool = False) -> None:
    """Turn span recording on; ``sync=True`` makes ``Span.block`` wait on
    device values so span durations include device execution."""
    global _ENABLED, _SYNC
    _ENABLED = True
    _SYNC = bool(sync)


def disable() -> None:
    global _ENABLED, _SYNC
    _ENABLED = False
    _SYNC = False


def is_enabled() -> bool:
    return _ENABLED


def reset() -> None:
    """Drop all recorded spans (and any dangling thread-local stacks)."""
    with _LOCK:
        _EVENTS.clear()
    _TLS.stack = []


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`SCOPES`: the operations
    traced inside it carry ``name`` in their HLO ``op_name``."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; have {SCOPES}")
    import jax

    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the function runs inside :func:`scope` ``(name)``."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


class Span:
    """A single recorded span.  Use via :func:`span`, not directly."""

    __slots__ = ("name", "attrs", "t0", "depth", "parent", "annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.depth = 0
        self.parent = None
        self.annotation = None

    def __enter__(self) -> "Span":
        from jax.profiler import TraceAnnotation

        st = _stack()
        self.parent = st[-1].name if st else None
        self.depth = len(st)
        st.append(self)
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.annotation.__exit__(*exc)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        ev = {
            "name": self.name,
            "ts_us": (self.t0 - _EPOCH) * 1e6,
            "dur_us": (t1 - self.t0) * 1e6,
            "depth": self.depth,
            "parent": self.parent,
            "thread": threading.get_ident(),
        }
        if self.attrs:
            ev["attrs"] = self.attrs
        with _LOCK:
            _EVENTS.append(ev)

    def block(self, value):
        """``jax.block_until_ready(value)`` iff sync timing is on; always
        returns ``value`` so call sites can write ``x = sp.block(x)``."""
        if _SYNC or self.attrs.get("sync"):
            import jax

            value = jax.block_until_ready(value)
        return value

    def set(self, **attrs) -> None:
        """Attach extra attributes to the span after entry."""
        self.attrs.update(attrs)


class _NullSpan:
    """Singleton stand-in when tracing is disabled: every method is a no-op
    so instrumented code pays one predicate check and nothing else."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def block(self, value):
        return value

    def set(self, **attrs) -> None:
        pass


_NULL = _NullSpan()


def span(name: str, **attrs):
    """Open a (nestable) span.  Returns the no-op singleton when disabled."""
    if not _ENABLED:
        return _NULL
    return Span(name, attrs)


def events() -> list[dict]:
    """Completed spans, oldest first (a copy)."""
    with _LOCK:
        return list(_EVENTS)


def chrome_trace() -> dict:
    """Completed spans as a Chrome trace-event document (Perfetto-loadable)."""
    pid = os.getpid()
    out = []
    with _LOCK:
        for ev in _EVENTS:
            out.append({
                "name": ev["name"],
                "ph": "X",
                "ts": ev["ts_us"],
                "dur": ev["dur_us"],
                "pid": pid,
                "tid": ev["thread"],
                "args": dict(ev.get("attrs", {}), depth=ev["depth"]),
            })
    out.sort(key=lambda e: e["ts"])
    return {"traceEvents": out, "displayTimeUnit": "ms"}


@contextlib.contextmanager
def profile(log_dir: str):
    """Wrap a region in ``jax.profiler.trace`` (the ``--profile`` hook).

    Degrades to a plain pass-through if the profiler is unavailable in
    this jax build rather than failing the run.
    """
    try:
        import jax

        ctx = jax.profiler.trace(log_dir)
    except Exception:  # pragma: no cover - profiler missing/broken build
        ctx = contextlib.nullcontext()
    with ctx:
        yield

"""From a profiler trace to per-device intervals, and the arithmetic on them.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes: the
operations each device ran (the ``XLA Ops`` and ``Async XLA Ops`` lines of
every ``/device:...`` plane; an asynchronous collective runs on the second
while other operations run on the first) and the benchmark's own host
spans (``TraceAnnotation`` names that start with ``bench.``).  ``reduce``
turns those into the numbers the per-layer readers take: busy time,
collective time and the part of it that no other operation hides, the
traced window, the operations that took most time and the longest idle
gaps with the host span they fall in.  Every time is in seconds.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

#: HLO op-name stems of the collectives.  An op is classified by the part
#: of its name before the first ``.`` (``all-reduce.7``, ``collective-permute-done.2``).
COLLECTIVE_STEMS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute", "collective-broadcast", "send", "recv")

HOST_PREFIX = "bench."
OP_LINES = ("XLA Ops", "Async XLA Ops")


@dataclasses.dataclass
class Trace:
    ops: dict          # device id -> [(name, start_s, end_s)], leaf ops only
    host: list         # [(name, start_s, end_s)] of the benchmark's host spans
    collectives: set = dataclasses.field(default_factory=set)  # op names whose
    #                    instruction is a collective (``psum.40 = ... all-reduce(...)``)


def op_name(event_name: str) -> str:
    """The HLO op's name: a TPU trace names an op by its whole instruction,
    ``%fusion.45 = (f32[], ...) fusion(...)``; the name is ``fusion.45``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


_OPCODE = re.compile(r"\s(?:%s)(?:-start|-done)?\(" % "|".join(map(re.escape, COLLECTIVE_STEMS)))


def is_collective(text: str) -> bool:
    """By the instruction's opcode where the event carries the instruction
    (``%psum.40 = f32[2] all-reduce(...)``), else by the op name's stem."""
    if _OPCODE.search(text):
        return True
    stem = op_name(text).split(".")[0]
    return any(stem == s or stem.startswith(s + "-") for s in COLLECTIVE_STEMS)


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list:
    """The parts of merged intervals ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events) -> list:
    """Drop events that contain a later event of the same line: a ``while``
    or a ``call`` around its body's operations is not busy time of its own."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    keep = []
    for i, (name, s, e) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][1] < e and events[i + 1][2] <= e:
            continue
        keep.append((name, s, e))
    return keep


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, as a :class:`Trace`.

    The device's clock is aligned to the host's: a program cannot start on
    the device before the host began to dispatch it, so where the first
    program (``XLA Modules`` line) starts before the first ``bench.dispatch``
    span, the device's events are shifted by the difference."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, starts, host, collectives = collections.defaultdict(list), {}, [], set()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                evs = [(op_name(ev.name), ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                       for ev in line.events]
                collectives.update(op_name(ev.name) for ev in line.events
                                   if is_collective(ev.name))
                if line.name in OP_LINES and evs:
                    ops[plane.name] += leaves(evs)
                elif line.name == "XLA Modules" and evs:
                    starts[plane.name] = min(s for _, s, _ in evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events if ev.name.startswith(HOST_PREFIX))
    dispatch = min((s for name, s, _ in host if name == HOST_PREFIX + "dispatch"), default=None)
    for dev, evs in ops.items():
        if dispatch is not None and starts.get(dev, dispatch) < dispatch:
            shift = dispatch - starts[dev]
            ops[dev] = [(n, s + shift, e + shift) for n, s, e in evs]
    return Trace(ops=dict(ops), host=sorted(host, key=lambda h: h[1]), collectives=collectives)


@dataclasses.dataclass
class Reduced:
    window_s: float            # first to last benchmark host span
    busy_s: float              # union of op intervals, mean over devices
    compute_busy_s: float      # union of the non-collective ops, mean over devices
    collective_s: float        # union of the collective ops, on the device with most
    exposed_s: float           # collective time no other op overlaps, on the device with most
    n_collective_ops: int
    device_ops: list           # [[name, seconds]] that took most time, mean over devices
    idle_gaps: list            # [[host span, seconds]], longest gaps of all devices


def reduce(trace: Trace, top: int = 10) -> Reduced:
    if not trace.ops:
        raise ValueError("the trace holds no device operations")
    if trace.host:
        lo = min(s for _, s, _ in trace.host)
        hi = max(e for _, _, e in trace.host)
    else:
        lo = min(s for evs in trace.ops.values() for _, s, _ in evs)
        hi = max(e for evs in trace.ops.values() for _, _, e in evs)
    n = len(trace.ops)
    busy = compute = 0.0
    coll, exposed = [], []
    n_coll = 0
    per_op = collections.Counter()
    busy_any = []
    for evs in trace.ops.values():
        evs = [(name, max(s, lo), min(e, hi), name in trace.collectives or is_collective(name))
               for name, s, e in evs if e > lo and s < hi]
        c_iv = union((s, e) for _, s, e, c in evs if c)
        o_iv = union((s, e) for _, s, e, c in evs if not c)
        all_iv = union(c_iv + o_iv)
        n_coll += sum(c for *_, c in evs)
        busy += length(all_iv)
        compute += length(o_iv)
        coll.append(length(c_iv))
        exposed.append(length(subtract(c_iv, o_iv)))
        for name, s, e, _ in evs:
            per_op[name] += e - s
        busy_any.extend(all_iv)
    gaps = subtract([(lo, hi)], union(busy_any))      # no device busy
    ranked = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Reduced(
        window_s=hi - lo, busy_s=busy / n, compute_busy_s=compute / n,
        collective_s=max(coll), exposed_s=max(exposed), n_collective_ops=n_coll,
        device_ops=[[name, t / n] for name, t in per_op.most_common(top)],
        idle_gaps=[[host_span(trace.host, s, e), e - s] for s, e in ranked])


def host_span(host, start: float, end: float) -> str:
    """The benchmark host span that overlaps ``[start, end)`` most."""
    best, name = 0.0, "outside the benchmark's spans"
    for h, s, e in host:
        o = min(e, end) - max(s, start)
        if o > best:
            best, name = o, h
    return name

#!/usr/bin/env python3
"""Per-layer device time of the solve, read from the names the program
gives its layers inside the compiled program.

The program opens a ``jax.named_scope`` for each of its layers
(``repro.obs.trace.SCOPES``: ``spmv``, ``halo``, ``dots``, ``update``,
``precond``), so every HLO instruction carries its layer in the
``op_name`` of its metadata.  :func:`layer_map` reads the compiled solve's
HLO text (``compiled.as_text()``) and gives each instruction one layer:

* an instruction of the entry computation, outside the solver's loop, is
  ``setup``, whatever its metadata says;
* an instruction inside the loop takes the innermost scope in its
  ``op_name``: a layer's self time, so a ``halo`` pad inside ``spmv`` is
  ``halo``;
* a fusion takes the highest-ranked scope among its fused instructions
  (:data:`RANK`): the fusion that streams the coefficient fields always
  counts as SpMV, an AXPY pass with a dot epilogue counts as ``update``,
  and ``dots`` keeps the reductions that stand alone;
* an instruction the compiler adds to the loop without a name of its own
  (a copy of a loop-carried vector, the halves of an asynchronous copy, a
  constant buffer it sinks into the loop) takes the layer of the value it
  moves, through the loop's carry
  where it moves one, else that of the nearest instruction that reads
  it; one inside a computation that another instruction calls takes its
  caller's;
* anything else is ``unscoped``.

Joined with a profiler trace by op name (``devtrace.op_name``),
:func:`readings` gives the per-layer numbers.  Run on the chip, the
script traces a cell's solves as ``bench/harness.run_cell`` does and
prints them with the trace's breakdown by layer::

    python3 bench/scopes.py --workload star7-608-1chip --seed 7 [--keep DIR]

``--keep`` writes the ``.xplane.pb`` and the HLO text to ``DIR``; with
``--hlo`` and ``--xplane`` the script reads such a pair again, anywhere.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import json
import math
import os
import re
import sys

#: the program's scopes, strongest first: a fusion takes the first of
#: these among its fused instructions
RANK = ("spmv", "halo", "update", "dots", "precond")
SETUP = "setup"
UNSCOPED = "unscoped"


# ---------------------------------------------------------------------------
# the HLO text
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    operands: list
    called: list        # computations this instruction calls
    op_name: str
    index: int | None = None    # of a get-tuple-element
    root: bool = False
    elements: int = 0           # of the largest array in the result
    target: str = ""            # of a custom call


_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _closing(text: str, start: int) -> int:
    """Index just past the bracket that closes ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] in "([{":
            depth += 1
        elif text[i] in ")]}":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _instr(name: str, rest: str, root: bool) -> Instr:
    # the result type: a tuple in parentheses, or one token
    end = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
    m = re.match(r"\s*([\w\-]+)\(", rest[end:])
    opcode, operands = "", []
    if m:
        opcode = m.group(1)
        open_at = end + m.end() - 1
        operands = re.findall(r"%([\w.\-]+)", rest[open_at:_closing(rest, open_at)])
    called = _CALLS.findall(rest)
    for group in _CALL_LISTS.findall(rest):
        called += re.findall(r"%?([\w.\-]+)", group)
    op = _OP_NAME.search(rest)
    index = re.search(r"\bindex=(\d+)", rest) if opcode == "get-tuple-element" else None
    elements = max((math.prod(int(d) for d in dims.split(",") if d)
                    for dims in re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", rest[:end])), default=0)
    target = re.search(r'custom_call_target="([^"]*)"', rest)
    return Instr(name, opcode, operands, called, op.group(1) if op else "",
                 int(index.group(1)) if index else None, root, elements,
                 target.group(1) if target else "")


def parse_hlo(text: str) -> tuple[dict, str]:
    """``({computation: [Instr]}, entry computation's name)``."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if cur is None:
            m = _HEAD.match(line)
            if m and not line.startswith((" ", "HloModule")):
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            cur.append(_instr(m.group(2), m.group(3), bool(m.group(1))))
    if entry is None:
        raise ValueError("the HLO text has no ENTRY computation")
    return comps, entry


def scope_of(op_name: str) -> str | None:
    """The innermost scope among the ``/``-separated parts of ``op_name``."""
    for part in reversed(op_name.split("/")):
        if part in RANK:
            return part
    return None


#: opcodes that move a value without computing: an added one without a
#: scope of its own takes the layer of the value it moves
_MOVES = ("get-tuple-element", "bitcast", "copy", "copy-start", "copy-done")


def layer_map(hlo_text: str) -> dict:
    """Instruction name -> layer, for every instruction the device can run:
    those of the entry computation and of every computation they call,
    except a fusion's own computation (the fusion runs as one operation)."""
    comps, entry = parse_hlo(hlo_text)
    fused = {c for instrs in comps.values() for i in instrs if i.opcode == "fusion"
             for c in i.called}
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", hlo_text))

    def fused_scope(comp: str, seen=()) -> str | None:
        best = None
        for i in comps.get(comp, []):
            s = (fused_scope(i.called[0], seen + (comp,))
                 if i.opcode == "fusion" and i.called and i.called[0] not in seen
                 else scope_of(i.op_name))
            if s is not None and (best is None or RANK.index(s) < RANK.index(best)):
                best = s
        return best

    out = {}

    def visit(comp: str, inherited: str | None, loop: bool, seen=()):
        instrs = comps.get(comp, [])
        by_name = {i.name: i for i in instrs}
        root = next((i for i in instrs if i.root), None)
        own = {}
        for i in instrs:
            if not loop:
                own[i.name] = SETUP
                continue
            layer = fused_scope(i.called[0]) if i.opcode == "fusion" and i.called else None
            layer = layer or scope_of(i.op_name)
            if layer is None and i.op_name and i.opcode not in _MOVES:
                layer = inherited
            own[i.name] = layer

        def moved_from(name):
            # follow the first operand back to a value some layer computed;
            # in a loop body a carried value comes from the root's operand
            # of the same index
            visited = set()
            while name in by_name and name not in visited:
                visited.add(name)
                i = by_name[name]
                if own[name] is not None and i.opcode not in _MOVES:
                    return own[name]
                src = i.operands[0] if i.operands else None
                if (i.opcode == "get-tuple-element" and comp in bodies and root
                        and src in by_name and by_name[src].opcode == "parameter"):
                    src = root.operands[i.index] if i.index < len(root.operands) else None
                name = src
            return None

        users = collections.defaultdict(list)
        for i in instrs:
            if i.root and comp in bodies:
                continue        # the carry: read by the next iteration
            for o in i.operands:
                users[o].append(i.name)
        if root is not None and comp in bodies:
            param = next((i.name for i in instrs if i.opcode == "parameter"), None)
            for i in instrs:
                if (i.opcode == "get-tuple-element" and i.operands == [param]
                        and i.index < len(root.operands)):
                    users[root.operands[i.index]].append(i.name)

        def read_by(name):
            # the nearest reader some layer computes with
            queue, visited = list(users[name]), set()
            while queue:
                u = queue.pop(0)
                if u in visited:
                    continue
                visited.add(u)
                if own[u] is not None and by_name[u].opcode not in _MOVES:
                    return own[u]
                queue += users[u]
            return None

        for i in instrs:
            layer = own[i.name]
            if layer is None and loop and (i.opcode in _MOVES
                                           or "/while/body/" not in i.op_name):
                layer = (moved_from(i.operands[0] if i.operands else None)
                         or read_by(i.name))
            out[i.name] = layer or inherited or UNSCOPED
            starts_loop = (comp == entry and i.opcode == "while"
                           and scope_of(i.op_name) is None)
            for c in i.called:
                if c not in fused and c not in seen:
                    visit(c, None if starts_loop else out[i.name] if loop else None,
                          loop or starts_loop, seen + (comp,))

    visit(entry, None, False)
    return out


def unscoped_vectors(hlo_text: str, elements: int) -> list:
    """The instructions of the solver's loop whose result holds ``elements``
    or more elements (a vector of the local block, or larger) and that no
    layer claims: what a sound program has none of."""
    comps, _ = parse_hlo(hlo_text)
    layers = layer_map(hlo_text)
    return [i.name for instrs in comps.values() for i in instrs
            if layers.get(i.name) == UNSCOPED and i.elements >= elements
            and i.opcode not in ("parameter", "constant", "tuple", "get-tuple-element")]


def pallas_kernels(hlo_text: str) -> list:
    """The name of every Pallas kernel the TPU compiler emitted: it names
    a kernel's custom call after the kernel's ``name=`` (``update_p.7``)."""
    comps, _ = parse_hlo(hlo_text)
    return [re.sub(r"\.\d+$", "", i.name) for instrs in comps.values() for i in instrs
            if i.target == "tpu_custom_call"]


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Capture:
    trace: object           # devtrace.Trace, as devtrace.load reads it (+ other prefixes' spans)
    programs: dict          # device -> [(start_s, end_s, run_id)] of the XLA Modules line
    launches: dict          # run_id -> host start of the earliest host event carrying it
    shift: dict             # device -> seconds devtrace.load moved its clock
    allreduces: set = dataclasses.field(default_factory=set)   # op names of all-reduces


_ALL_REDUCE = re.compile(r"\sall-reduce(?:-start|-done)?\(")


def load(trace_dir: str, prefixes=("bench.",)) -> Capture:
    """The newest ``.xplane.pb`` under ``trace_dir``: ``devtrace.load``'s
    reading of it (ops, collectives, the benchmark's host spans, the
    device clock's shift), and besides every program each device ran, on
    the same shifted clock, the host events that share its ``run_id``, the
    all-reduces, and the host spans whose names start with any of
    ``prefixes`` (``("bench.",)`` reads as the benchmark does;
    ``("solve.",)`` reads a ``--profile`` run of ``launch/solve.py``)."""
    import jax

    from bench import devtrace

    trace = devtrace.load(trace_dir)
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    programs, host, allreduces, launches = collections.defaultdict(list), [], set(), {}
    others = tuple(p for p in prefixes if p != devtrace.HOST_PREFIX)
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == "XLA Modules":
                programs[plane.name] += [(ev.start_ns * 1e-9,
                                          (ev.start_ns + ev.duration_ns) * 1e-9,
                                          dict(ev.stats).get("run_id")) for ev in line.events]
            elif device and line.name in devtrace.OP_LINES:
                allreduces.update(devtrace.op_name(ev.name) for ev in line.events
                                  if _ALL_REDUCE.search(ev.name)
                                  or ev.name.startswith("all-reduce"))
            elif not device:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if others and ev.name.startswith(others):
                        host.append((ev.name, s, s + ev.duration_ns * 1e-9))
                    run_id = dict(ev.stats).get("run_id")
                    if run_id is not None:
                        launches[run_id] = min(s, launches.get(run_id, s))
    # devtrace's rule: no program starts before the first dispatch span
    dispatch = min((s for name, s, _ in trace.host
                    if name == devtrace.HOST_PREFIX + "dispatch"), default=None)
    shift = {}
    for dev in trace.ops:
        first = min((s for s, _, _ in programs.get(dev, [])), default=None)
        shift[dev] = (dispatch - first if None not in (dispatch, first)
                      and first < dispatch else 0.0)
    programs = {dev: sorted((s + shift.get(dev, 0.0), e + shift.get(dev, 0.0), r)
                            for s, e, r in progs) for dev, progs in programs.items()}
    if devtrace.HOST_PREFIX in prefixes:
        host += trace.host
    trace = dataclasses.replace(trace, host=sorted(host, key=lambda h: h[1]))
    return Capture(trace, programs, launches, shift, allreduces)


def pair(capture: Capture) -> dict:
    """Device -> ``[(host launch - device start) in seconds]`` for each
    program whose ``run_id`` a host event also carries, on the shifted
    clock: how long after the host began to launch a program it started on
    the device (negative where the clocks disagree)."""
    out = {}
    for dev, progs in capture.programs.items():
        lags = [s - capture.launches[r] for s, _, r in progs if r in capture.launches]
        if lags:
            out[dev] = lags
    return out


# ---------------------------------------------------------------------------
# the readings
# ---------------------------------------------------------------------------

def spmv_least_words_per_point(n_offsets: int) -> int:
    """Storage words per meshpoint that the two SpMVs of a BiCGStab
    iteration have to move: each coefficient field once per SpMV.  The
    field term of ``counts/bicgstab.least_words_per_point``, argued in
    that module's docstring; a floor, so a share of the HBM roofline built
    on it cannot pass 100 % unless the SpMV's time is undercounted."""
    return 2 * n_offsets


def _window(trace):
    if trace.host:
        return min(s for _, s, _ in trace.host), max(e for _, _, e in trace.host)
    return (min(s for evs in trace.ops.values() for _, s, _ in evs),
            max(e for evs in trace.ops.values() for _, _, e in evs))


def layer_times(capture: Capture, layers: dict) -> dict:
    """Per layer, over the window ``devtrace.reduce`` takes: the union of
    its non-collective ops' intervals, mean over devices (``compute``);
    the union of its all-reduce intervals on the device with most
    (``allreduce``); its ops' summed time, mean over devices (``ops``, as
    ``{op: seconds}``)."""
    from bench import devtrace

    trace = capture.trace
    lo, hi = _window(trace)
    n = len(trace.ops)
    compute = collections.Counter()
    allreduce = collections.Counter()
    ops = collections.defaultdict(collections.Counter)
    for evs in trace.ops.values():
        by_layer = collections.defaultdict(list)
        reduces = collections.defaultdict(list)
        for name, s, e in evs:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            layer = layers.get(name, UNSCOPED)
            ops[layer][name] += (e - s) / n
            if name in trace.collectives or devtrace.is_collective(name):
                if name in capture.allreduces or name.startswith("all-reduce"):
                    reduces[layer].append((s, e))
            else:
                by_layer[layer].append((s, e))
        for layer, iv in by_layer.items():
            compute[layer] += devtrace.length(devtrace.union(iv)) / n
        for layer, iv in reduces.items():
            allreduce[layer] = max(allreduce[layer], devtrace.length(devtrace.union(iv)))
    return {"compute": dict(compute), "allreduce": dict(allreduce),
            "ops": {k: dict(v) for k, v in ops.items()}}


def host_gaps(capture: Capture) -> list:
    """``[(device, start_s, end_s, host span)]``: the device's idle time
    between the end of one program and the start of the next, inside the
    window, each with the host span it overlaps most."""
    from bench import devtrace

    lo, hi = _window(capture.trace)
    out = []
    for dev, progs in capture.programs.items():
        for (_, e0, _), (s1, _, _) in zip(progs, progs[1:]):
            s, e = max(e0, lo), min(s1, hi)
            if e > s:
                out.append((dev, s, e, devtrace.host_span(capture.trace.host, s, e)))
    return out


def readings(capture: Capture, layers: dict, *, iterations: int, solves: int,
             least_spmv_bytes_per_iter: float, hbm_bytes_per_s: float | None) -> dict:
    """The per-layer metrics of a traced run of ``solves`` whole solves of
    ``iterations`` iterations in all.  Each is None where the trace holds
    nothing of its layer; ``unscoped_share`` is None where no op of the
    loop carries a scope (a program built before the scopes)."""
    t = layer_times(capture, layers)
    comp = t["compute"]
    loop = {k: v for k, v in comp.items() if k != SETUP}
    scoped = sum(v for k, v in loop.items() if k != UNSCOPED)

    def per_iter(layer):
        return 1e3 * comp[layer] / iterations if comp.get(layer) else None

    roofline = None
    if comp.get("spmv") and hbm_bytes_per_s:
        roofline = 100.0 * least_spmv_bytes_per_iter * iterations / (comp["spmv"]
                                                                    * hbm_bytes_per_s)
    gaps = host_gaps(capture)
    n_dev = max(len(capture.programs), 1)
    return {
        "spmv_ms_per_iter": per_iter("spmv"),
        "update_ms_per_iter": per_iter("update"),
        "dots_ms_per_iter": per_iter("dots"),
        "halo_ms_per_iter": per_iter("halo"),
        "reduce_ms_per_iter": (1e3 * t["allreduce"]["dots"] / iterations
                               if t["allreduce"].get("dots") else None),
        "setup_ms_per_solve": 1e3 * comp[SETUP] / solves if comp.get(SETUP) else None,
        "spmv_roofline": roofline,
        "unscoped_share": (100.0 * loop.get(UNSCOPED, 0.0) / sum(loop.values())
                           if scoped > 0 else None),
        "host_gap_ms_per_solve": (1e3 * sum(e - s for _, s, e, _ in gaps) / n_dev / solves
                                  if capture.programs else None),
    }


def breakdown(capture: Capture, layers: dict, *, iterations: int, solves: int,
              top: int = 6) -> dict:
    """Milliseconds per iteration of each layer (setup: per solve), its
    longest ops, the sum that should meet the non-collective device time
    per iteration, and the host gaps between programs by host span."""
    from bench import devtrace

    t = layer_times(capture, layers)
    red = devtrace.reduce(capture.trace)
    comp = t["compute"]
    loop_ms = sum(v for k, v in comp.items() if k != SETUP) * 1e3 / iterations
    setup_ms = comp.get(SETUP, 0.0) * 1e3 / iterations
    by_span = collections.defaultdict(list)
    for _, s, e, span in host_gaps(capture):
        by_span[span].append(1e3 * (e - s))
    lags = pair(capture)
    flat = [x for v in lags.values() for x in v]
    return {
        "ms_per_iter": {k: 1e3 * v / iterations for k, v in sorted(comp.items())},
        "top_ops_ms_per_iter": {
            layer: [[name, round(1e3 * s / iterations, 4)] for name, s in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]]
            for layer, ops in t["ops"].items()},
        "partition": {"layers_plus_setup_ms_per_iter": loop_ms + setup_ms,
                      "compute_busy_ms_per_iter": 1e3 * red.compute_busy_s / iterations},
        "gaps_ms_by_host_span": {k: {"count": len(v), "total": sum(v), "max": max(v)}
                                 for k, v in by_span.items()},
        "run_id_pairs": len(flat),
        "launch_lag_ms": ({"min": 1e3 * min(flat), "max": 1e3 * max(flat)} if flat else None),
        "shift_ms": {d: 1e3 * s for d, s in capture.shift.items()},
    }


# ---------------------------------------------------------------------------
# on the chip: trace a cell's solves as the harness does
# ---------------------------------------------------------------------------

def capture_cell(cell, *, seed: int, trace_dir: str, log=print):
    """Set the cell up as ``harness.run_cell`` does, trace
    ``harness.TRACE_SECONDS`` of whole solves into ``trace_dir``, and
    return ``(hlo text, iterations, solves, unconverged solves, least SpMV
    bytes per iteration and chip, peak)``."""
    import itertools
    import math
    import time

    import jax
    import numpy as np

    from bench import harness
    from repro.launch.mesh import make_mesh_for_devices

    devices = harness.devices_for(cell.chips)
    peak = harness.peak_of(devices[0].device_kind)
    mesh = make_mesh_for_devices(cell.chips)
    config = cell.config
    problem = harness.build_problem(config, cell.traffic, harness.solve_sharding(mesh))
    cf = harness.program_coeffs(problem.fields)
    order = [int(k) for k in np.random.default_rng(seed).permutation(len(problem.bs))]
    compiled = harness.solve_fn(config, mesh).lower(cf, problem.bs[0]).compile()
    harness._solve_once(compiled, cf, problem.bs[order[0]])           # warm-up
    iterations = solves = unconverged = 0
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for j in itertools.count():
        res, it, ok = harness._solve_once(compiled, cf, problem.bs[order[j % len(order)]])
        iterations += it
        solves += 1
        unconverged += not ok
        del res
        if time.perf_counter() - t0 >= harness.TRACE_SECONDS:
            break
    jax.profiler.stop_trace()
    pts = math.prod(cell.traffic["mesh"]) // cell.chips
    spmv_bytes = (spmv_least_words_per_point(len(problem.fields))
                  * problem.bs[0].dtype.itemsize * pts)
    log(f"traced {solves} solves, {iterations} iterations, {unconverged} unconverged")
    return compiled.as_text(), iterations, solves, unconverged, spmv_bytes, peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="trace this cell of BENCHMARK.json on the chip")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--keep", help="write the trace and the HLO text here")
    ap.add_argument("--hlo", help="read this HLO text instead of tracing")
    ap.add_argument("--xplane", help="... with the trace under this directory")
    ap.add_argument("--meta", help="... and the iterations, solves and counts in this JSON")
    args = ap.parse_args(argv)

    import shutil
    import tempfile

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    if args.workload:
        from bench import harness

        cell = harness.load_cell(args.workload)
        harness.enable_compile_cache()
        trace_dir = args.keep or tempfile.mkdtemp(prefix="bench_scopes_")
        hlo, iterations, solves, unconverged, spmv_bytes, peak = capture_cell(
            cell, seed=args.seed, trace_dir=trace_dir, log=log)
        meta = {"iterations": iterations, "solves": solves,
                "least_spmv_bytes_per_iter": spmv_bytes,
                "hbm_bytes_per_s": peak["hbm_bytes_per_s"]}
        if args.keep:
            with open(os.path.join(args.keep, "solve.hlo.txt"), "w") as f:
                f.write(hlo)
            with open(os.path.join(args.keep, "meta.json"), "w") as f:
                json.dump(meta, f)
    else:
        unconverged = 0
        with open(args.hlo) as f:
            hlo = f.read()
        with open(args.meta) as f:
            meta = json.load(f)
        trace_dir = args.xplane
    try:
        capture = load(trace_dir)
    finally:
        if args.workload and not args.keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    layers = layer_map(hlo)
    out = {"metrics": readings(capture, layers, **meta),
           "breakdown": breakdown(capture, layers, iterations=meta["iterations"],
                                  solves=meta["solves"]),
           "kernels": pallas_kernels(hlo), "meta": meta}
    print(json.dumps(out), flush=True)
    if unconverged:
        log(f"{unconverged} traced solves did not converge: the readings are not a cell's")
    return 1 if unconverged else 0


if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())

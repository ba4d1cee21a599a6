#!/usr/bin/env python3
"""Compare two observability run bundles and fail on regressions.

    python scripts/compare_runs.py <baseline_run_dir> <candidate_run_dir> \
        [--max-iter-increase-pct 0] [--max-collective-increase 0] \
        [--min-solves-per-sec-ratio 0.8]

Each run dir is a ``repro.obs.v1`` bundle written by ``--obs`` launches
(``results/runs/<run_id>/`` with ``manifest.json`` + ``events.jsonl``, see
docs/observability.md).  The script diffs the metrics that matter for the
solver stack:

* **iterations** — ``solve.iterations_max`` gauge.  More iterations than
  baseline (beyond ``--max-iter-increase-pct``) is a convergence
  regression.  On by default (0% slack).
* **collectives** — AllReduce / ppermute totals summed from the
  ``collectives`` events (the HLO-counted ground truth emitted at launch).
  Any growth beyond ``--max-collective-increase`` ops is a communication-
  schedule regression.  On by default (0 slack).
* **solves/sec** — the throughput gauge.  Timing is machine-dependent, so
  this check is OFF by default (ratio 0); enable it with e.g.
  ``--min-solves-per-sec-ratio 0.8`` when comparing runs from the same
  machine.

Exits 0 when the candidate is no worse than the baseline under the active
thresholds, 1 with a regression list otherwise, 2 on malformed bundles.
Stdlib only — runs anywhere, no repo import needed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def load_run(run_dir: str) -> tuple[dict, list[dict]]:
    man_path = os.path.join(run_dir, "manifest.json")
    try:
        with open(man_path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"error: cannot read {man_path}: {e}")
    if manifest.get("schema") != "repro.obs.v1":
        raise SystemExit(f"error: {man_path} is not a repro.obs.v1 manifest "
                         f"(schema={manifest.get('schema')!r})")
    events = []
    ev_path = os.path.join(run_dir, "events.jsonl")
    if os.path.exists(ev_path):
        with open(ev_path) as f:
            for line in f:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    return manifest, events


def gauge(manifest: dict, name: str):
    return manifest.get("metrics", {}).get("gauges", {}).get(name)


def collective_totals(events: list[dict]) -> dict[str, int]:
    """Sum AllReduce / ppermute totals over every `collectives` event."""
    totals = {"allreduce_total": 0, "ppermute_total": 0}
    seen = False
    for e in events:
        if e.get("event") == "collectives":
            seen = True
            for k in totals:
                totals[k] += int(e.get(k, 0))
    return totals if seen else {}


class Comparison:
    def __init__(self) -> None:
        self.rows: list[tuple[str, str, str, str]] = []
        self.regressions: list[str] = []

    def check(self, name, base, cand, ok, detail="") -> None:
        fmt = lambda v: "-" if v is None else (f"{v:.4g}" if isinstance(v, float) else str(v))
        verdict = "skip" if ok is None else ("ok" if ok else "REGRESSION")
        self.rows.append((name, fmt(base), fmt(cand), verdict))
        if ok is False:
            self.regressions.append(f"{name}: baseline={fmt(base)} "
                                    f"candidate={fmt(cand)} {detail}".rstrip())

    def report(self) -> int:
        w = max(len(r[0]) for r in self.rows) if self.rows else 10
        print(f"{'metric':<{w}}  {'baseline':>12}  {'candidate':>12}  verdict")
        for name, base, cand, verdict in self.rows:
            print(f"{name:<{w}}  {base:>12}  {cand:>12}  {verdict}")
        if self.regressions:
            print(f"\n{len(self.regressions)} regression(s):", file=sys.stderr)
            for r in self.regressions:
                print(f"  - {r}", file=sys.stderr)
            return 1
        print("\nno regressions under the active thresholds")
        return 0


def compare(base_dir: str, cand_dir: str, args) -> int:
    base_man, base_ev = load_run(base_dir)
    cand_man, cand_ev = load_run(cand_dir)
    print(f"baseline : {base_man['run_id']} ({base_man['kind']}, "
          f"git {base_man.get('git', {}).get('sha', '?')[:12]})")
    print(f"candidate: {cand_man['run_id']} ({cand_man['kind']}, "
          f"git {cand_man.get('git', {}).get('sha', '?')[:12]})\n")

    cmp = Comparison()

    # -- convergence: solver iterations --------------------------------
    b, c = gauge(base_man, "solve.iterations_max"), gauge(cand_man, "solve.iterations_max")
    if b is None or c is None:
        cmp.check("solve.iterations_max", b, c, None)
    else:
        limit = b * (1.0 + args.max_iter_increase_pct / 100.0)
        cmp.check("solve.iterations_max", b, c, c <= limit,
                  f"(limit {limit:.4g}, --max-iter-increase-pct "
                  f"{args.max_iter_increase_pct:g})")

    # -- communication: HLO-counted collective totals ------------------
    bt, ct = collective_totals(base_ev), collective_totals(cand_ev)
    for key in ("allreduce_total", "ppermute_total"):
        if not bt or not ct:
            cmp.check(f"collectives.{key}", bt.get(key), ct.get(key), None)
        else:
            cmp.check(f"collectives.{key}", bt[key], ct[key],
                      ct[key] <= bt[key] + args.max_collective_increase,
                      f"(--max-collective-increase {args.max_collective_increase})")

    # -- throughput (opt-in: machine-dependent) ------------------------
    name, ratio = "solve.solves_per_sec", args.min_solves_per_sec_ratio
    b, c = gauge(base_man, name), gauge(cand_man, name)
    if ratio <= 0 or b is None or c is None:
        cmp.check(name, b, c, None)
    else:
        cmp.check(name, b, c, c >= b * ratio,
                  f"(floor {b * ratio:.4g}, --min-solves-per-sec-ratio {ratio:g})")

    return cmp.report()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n", 1)[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("baseline", help="baseline run dir (results/runs/<id>)")
    ap.add_argument("candidate", help="candidate run dir to vet")
    ap.add_argument("--max-iter-increase-pct", type=float, default=0.0,
                    help="allowed %% growth in solve.iterations_max")
    ap.add_argument("--max-collective-increase", type=int, default=0,
                    help="allowed growth in AllReduce/ppermute totals (ops)")
    ap.add_argument("--min-solves-per-sec-ratio", type=float, default=0.0,
                    help="candidate/baseline throughput floor (0 = skip)")
    args = ap.parse_args(argv)
    return compare(args.baseline, args.candidate, args)


if __name__ == "__main__":
    sys.exit(main())

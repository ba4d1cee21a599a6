"""Run manifests: every launch/benchmark invocation leaves a reproducible
bundle under ``results/runs/<run_id>/``:

* ``manifest.json`` — versioned ``repro.obs.v1`` record: config cell,
  git SHA, jax/jaxlib/numpy versions, device topology, XLA/env flags,
  wall time, and the final metrics snapshot.
* ``events.jsonl``  — the registry's structured events, one per line.
* ``trace.json``    — completed spans as Chrome trace events (Perfetto).

Usage (what ``--obs`` wires up in the launch CLIs)::

    ctx = manifest.start_run("solve", config=vars(args), profile=args.profile)
    ... run ...
    manifest.finish_run(ctx)

``scripts/compare_runs.py`` diffs two such bundles and CI validates
their schema, so keep :func:`validate_manifest` in sync with the writer.
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform
import sys
import time

from repro.obs import metrics, trace

SCHEMA = "repro.obs.v1"
DEFAULT_ROOT = os.path.join("results", "runs")

# Env vars worth pinning in the manifest: anything that changes lowering,
# device fabric, kernels, or cache behavior.
_ENV_KEYS = ("XLA_FLAGS", "JAX_ENABLE_X64", "JAX_PLATFORMS",
             "JAX_COMPILATION_CACHE_DIR", "REPRO_TUNING_CACHE",
             "LD_PRELOAD", "TF_CPP_MIN_LOG_LEVEL")

_REQUIRED_FIELDS = ("schema", "run_id", "kind", "created_unix", "created",
                    "argv", "config", "git", "versions", "devices", "env",
                    "metrics", "wall_s")


def git_info(root: str = ".") -> dict:
    """Best-effort git SHA/branch of the checkout at ``root``, read from
    ``.git`` directly (a run starts no child process); ``dirty`` is None
    because only git itself can tell."""
    def _read(*parts):
        try:
            with open(os.path.join(root, ".git", *parts)) as f:
                return f.read().strip()
        except OSError:
            return None

    head = _read("HEAD") or ""
    branch, sha = "unknown", head or None
    if head.startswith("ref: "):
        ref = head[5:]
        branch = ref.rsplit("/", 1)[-1]
        sha = _read(*ref.split("/"))
        if sha is None:
            for line in (_read("packed-refs") or "").splitlines():
                if line.endswith(" " + ref):
                    sha = line.split()[0]
    return {"sha": sha or "unknown", "branch": branch, "dirty": None}


def versions() -> dict:
    out = {"python": platform.python_version()}
    for mod in ("jax", "jaxlib", "numpy"):
        try:
            out[mod] = __import__(mod).__version__
        except Exception:
            out[mod] = None
    return out


def device_topology() -> dict:
    """Device platform/count as jax sees it (fake fabrics included)."""
    try:
        import jax

        devs = jax.devices()
        return {
            "platform": devs[0].platform if devs else None,
            "n_devices": len(devs),
            "kinds": sorted({d.device_kind for d in devs}),
            "process_count": jax.process_count(),
        }
    except Exception:
        return {"platform": None, "n_devices": 0, "kinds": [],
                "process_count": None}


def env_flags() -> dict:
    return {k: os.environ[k] for k in _ENV_KEYS if k in os.environ}


def new_run_id(kind: str) -> str:
    stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    return f"{stamp}-{kind}-{os.getpid() % 100000:05d}"


def _jsonable(obj):
    """Coerce argparse namespaces / dataclasses / tuples into JSON."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return _jsonable(dataclasses.asdict(obj))
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


@dataclasses.dataclass
class RunContext:
    run_id: str
    run_dir: str
    kind: str
    config: dict
    t_start: float
    profile: bool = False
    _profiler = None


def start_run(kind: str, *, config: dict | None = None,
              run_dir: str | None = None, root: str = DEFAULT_ROOT,
              profile: bool = False) -> RunContext:
    """Open a run bundle directory (creating it) and optionally start the
    jax profiler into ``<run_dir>/jax_profile``."""
    run_id = new_run_id(kind)
    if run_dir is None:
        run_dir = os.path.join(root, run_id)
    os.makedirs(run_dir, exist_ok=True)
    ctx = RunContext(run_id=run_id, run_dir=run_dir, kind=kind,
                     config=_jsonable(config or {}),
                     t_start=time.time(), profile=profile)
    if profile:
        import jax

        # a profiler that cannot start is an error: --profile must never
        # exit 0 without a trace
        ctx._profiler = jax.profiler.trace(os.path.join(run_dir, "jax_profile"))
        ctx._profiler.__enter__()
    metrics.event("run_start", run_id=run_id, kind=kind)
    return ctx


def finish_run(ctx: RunContext, *, extra: dict | None = None) -> dict:
    """Write ``manifest.json``, ``events.jsonl``, and ``trace.json``."""
    if ctx._profiler is not None:
        profiler, ctx._profiler = ctx._profiler, None
        profiler.__exit__(None, None, None)     # a failed stop raises
    wall = time.time() - ctx.t_start
    metrics.event("run_finish", run_id=ctx.run_id, wall_s=wall)

    man = {
        "schema": SCHEMA,
        "run_id": ctx.run_id,
        "kind": ctx.kind,
        "created_unix": ctx.t_start,
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                 time.gmtime(ctx.t_start)),
        "argv": list(sys.argv),
        "config": ctx.config,
        "git": git_info(),
        "versions": versions(),
        "devices": device_topology(),
        "env": env_flags(),
        "metrics": metrics.snapshot(),
        "wall_s": wall,
    }
    if extra:
        man.update(_jsonable(extra))

    with open(os.path.join(ctx.run_dir, "events.jsonl"), "w") as f:
        for ev in metrics.events():
            f.write(json.dumps(_jsonable(ev)) + "\n")
    with open(os.path.join(ctx.run_dir, "trace.json"), "w") as f:
        json.dump(trace.chrome_trace(), f)
    with open(os.path.join(ctx.run_dir, "manifest.json"), "w") as f:
        json.dump(man, f, indent=2)
    return man


def validate_manifest(man: dict) -> list[str]:
    """Schema check used by tests, CI, and compare_runs.  Returns a list of
    problems (empty == valid)."""
    problems = []
    for field in _REQUIRED_FIELDS:
        if field not in man:
            problems.append(f"missing field: {field}")
    if man.get("schema") != SCHEMA:
        problems.append(f"schema is {man.get('schema')!r}, want {SCHEMA!r}")
    if not isinstance(man.get("metrics"), dict):
        problems.append("metrics is not an object")
    else:
        for sub in ("counters", "gauges", "histograms"):
            if sub not in man["metrics"]:
                problems.append(f"metrics missing {sub!r}")
    git = man.get("git")
    if not (isinstance(git, dict) and "sha" in git):
        problems.append("git.sha missing")
    dev = man.get("devices")
    if not (isinstance(dev, dict) and "n_devices" in dev):
        problems.append("devices.n_devices missing")
    return problems


def load_manifest(run_dir: str) -> dict:
    with open(os.path.join(run_dir, "manifest.json")) as f:
        return json.load(f)

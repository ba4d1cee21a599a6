"""Production training driver: mesh + sharded params + data + checkpoints +
fault-tolerant runner, for any assigned architecture.

CPU-scale usage (smoke config, the default):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2_1_5b --steps 50

Pod-scale usage is identical but with --full and a real TPU runtime; the
driver only touches jax-portable APIs (make_mesh / NamedSharding / jit).
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke
from repro.data import DataConfig, SyntheticLMData
from repro.launch.mesh import make_mesh_for_devices, make_production_mesh
from repro.models import model as M
from repro.optim.adamw import adamw_init
from repro.runtime import FaultTolerantRunner, RunnerConfig


def build(arch: str, *, full: bool = False, seq_len: int = 64,
          global_batch: int = 4, production_mesh: bool = False):
    cfg = get_config(arch) if full else get_smoke(arch)
    mesh = (make_production_mesh() if production_mesh
            else make_mesh_for_devices())
    extras = {}
    if cfg.frontend == "vlm":
        extras["patch_embeds"] = ((cfg.n_frontend_tokens, cfg.d_model), np.float32)
        seq_len_text = seq_len - 0  # image tokens are extra, text len = seq_len
    if cfg.enc_dec:
        extras["frames"] = ((seq_len, cfg.d_model), np.float32)
    data = SyntheticLMData(
        DataConfig(vocab=cfg.vocab, seq_len=seq_len, global_batch=global_batch),
        extras=extras)
    return cfg, mesh, data


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full", action="store_true",
                    help="full published config (pod-scale; default: smoke twin)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    cfg, mesh, data = build(args.arch, full=args.full, seq_len=args.seq,
                            global_batch=args.batch)
    print(f"arch={cfg.name} params={M.n_params(cfg):,} devices={len(jax.devices())}")

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    with jax.sharding.set_mesh(mesh):
        if len(jax.devices()) > 1:
            shardings = M.param_shardings(cfg, mesh)
            params = jax.device_put(params, shardings)
        train_step = jax.jit(M.make_train_step(cfg, total_steps=args.steps))

        def stepper(p, o, batch):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if cfg.frontend == "vlm" or cfg.enc_dec:
                batch = _adapt_modality(cfg, batch)
            return train_step(p, o, batch)

        if args.ckpt_dir:
            runner = FaultTolerantRunner(
                RunnerConfig(total_steps=args.steps,
                             checkpoint_every=args.ckpt_every),
                train_step=stepper, data=data,
                ckpt=CheckpointManager(args.ckpt_dir))
            t0 = time.time()
            params, opt = runner.run(params, opt)
            hist = runner.metrics_history
        else:
            hist = []
            t0 = time.time()
            for step, batch in data.iterate(0):
                if step >= args.steps:
                    break
                params, opt, m = stepper(params, opt, batch)
                hist.append({"step": step, "loss": float(m["loss"])})
                if step % args.log_every == 0:
                    print(f"step {step:5d} loss {float(m['loss']):.4f}")
    dt = time.time() - t0
    losses = [h["loss"] for h in hist]
    print(f"done: {len(losses)} steps in {dt:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")


def _adapt_modality(cfg, batch):
    b = dict(batch)
    if cfg.frontend == "vlm" and "patch_embeds" in b:
        b["patch_embeds"] = b["patch_embeds"].astype(cfg.dtype)
    if cfg.enc_dec and "frames" in b:
        b["frames"] = b["frames"].astype(cfg.dtype)
    return b


if __name__ == "__main__":
    main()

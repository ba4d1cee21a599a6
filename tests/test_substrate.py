"""Substrate tests: data determinism, checkpoint atomicity + resharding,
fault-tolerant restart, optimizer behaviour, gradient compression."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLMData
from repro.optim.adamw import adamw_init, adamw_update, cosine_lr, global_norm
from repro.optim.compress import compress_grads, decompress_grads
from repro.runtime import FaultTolerantRunner, RunnerConfig


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_in_seed_and_step():
    cfg = DataConfig(vocab=256, seq_len=32, global_batch=4, seed=7)
    d1, d2 = SyntheticLMData(cfg), SyntheticLMData(cfg)
    b1, b2 = d1.batch_at(123), d2.batch_at(123)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = d1.batch_at(124)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_data_host_sharding_partitions_batch():
    cfg = DataConfig(vocab=256, seq_len=16, global_batch=8, seed=1)
    h0 = SyntheticLMData(cfg, host_index=0, n_hosts=2)
    h1 = SyntheticLMData(cfg, host_index=1, n_hosts=2)
    assert h0.local_batch == h1.local_batch == 4
    assert not np.array_equal(h0.batch_at(5)["tokens"], h1.batch_at(5)["tokens"])


def test_data_labels_are_shifted_tokens():
    cfg = DataConfig(vocab=256, seq_len=16, global_batch=2)
    b = SyntheticLMData(cfg).batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_data_prefetch_iterator_matches_batch_at():
    cfg = DataConfig(vocab=64, seq_len=8, global_batch=2)
    data = SyntheticLMData(cfg)
    it = data.iterate(start_step=10)
    for want_step in (10, 11, 12):
        step, batch = next(it)
        assert step == want_step
        np.testing.assert_array_equal(batch["tokens"],
                                      data.batch_at(want_step)["tokens"])


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 4), jnp.float32),
            "b": {"x": jnp.arange(6, dtype=jnp.bfloat16)}}


def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    t = _tree()
    cm.save(100, t)
    restored, step = cm.restore_latest(t)
    assert step == 100
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_checkpoint_gc_keeps_last_k(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        cm.save(s, _tree())
    assert cm.all_steps() == [3, 4]


def test_checkpoint_async_then_wait(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(7, _tree(), blocking=False)
    cm.wait()
    assert cm.latest_step() == 7


def test_corrupt_partial_write_is_invisible(tmp_path):
    """A crash mid-write must never surface a loadable-but-bad checkpoint."""
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, _tree())
    # simulate a crash: npz written for step 2 but manifest missing
    import numpy as np_
    np_.savez(os.path.join(str(tmp_path), "step_000000002.npz"), garbage=np_.zeros(3))
    assert cm.latest_step() == 1          # manifest-gated
    restored, step = cm.restore_latest(_tree())
    assert step == 1


def test_checkpoint_reshard_on_load(subproc):
    """Save on 8-device mesh, restore onto 4-device (elastic restart)."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np, os, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh_for_devices
        d = tempfile.mkdtemp()
        mesh8 = make_mesh_for_devices(8)
        sh8 = NamedSharding(mesh8, P("data", "model"))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8), sh8)
        cm = CheckpointManager(d)
        cm.save(5, {"x": x})
        # restore onto a DIFFERENT layout: 4 of the 8 devices, model-only mesh
        from repro.launch.mesh import make_mesh
        mesh4 = make_mesh((4,), ("model",), devices=jax.devices()[:4])
        like = jax.ShapeDtypeStruct((8, 8), jnp.float32,
                                    sharding=NamedSharding(mesh4, P("model", None)))
        (restored, step) = cm.restore(5, {"x": like})
        assert step == 5
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.arange(64.0).reshape(8, 8))
        assert restored["x"].sharding.num_devices == 4
        print("OK")
    """)


# ---------------------------------------------------------------------------
# Fault-tolerant runner
# ---------------------------------------------------------------------------

def _toy_setup(tmp_path, total_steps=12, ckpt_every=4):
    # 1-param "model": learn the mean of token values (decreasing loss)
    def train_step(params, opt, batch):
        def loss_fn(p):
            x = batch["tokens"].astype(jnp.float32) / 256.0
            return jnp.mean((x - p["mu"]) ** 2), jnp.float32(0.0)

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt = adamw_update(params, grads, opt, lr=0.05, weight_decay=0.0)
        return params, opt, {"loss": loss}

    data = SyntheticLMData(DataConfig(vocab=256, seq_len=16, global_batch=2))
    params = {"mu": jnp.zeros((), jnp.float32)}
    opt = adamw_init(params)
    ckpt = CheckpointManager(str(tmp_path))
    cfg = RunnerConfig(total_steps=total_steps, checkpoint_every=ckpt_every,
                       async_checkpoint=False)
    return cfg, train_step, data, ckpt, params, opt


def test_runner_completes_and_checkpoints(tmp_path):
    cfg, step, data, ckpt, params, opt = _toy_setup(tmp_path)
    runner = FaultTolerantRunner(cfg, train_step=jax.jit(step), data=data, ckpt=ckpt)
    p, o = runner.run(params, opt)
    assert ckpt.latest_step() == cfg.total_steps
    losses = [m["loss"] for m in runner.metrics_history]
    assert losses[-1] < losses[0]


def test_runner_survives_injected_failures(tmp_path):
    cfg, step, data, ckpt, params, opt = _toy_setup(tmp_path, total_steps=16,
                                                    ckpt_every=4)
    boom = {"armed": True}

    def failure_hook(s):
        if s == 9 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected preemption at step 9")

    runner = FaultTolerantRunner(cfg, train_step=jax.jit(step), data=data,
                                 ckpt=ckpt, failure_hook=failure_hook)
    runner.run(params, opt)
    assert runner.restarts == 1
    # replay determinism: the metrics after restart re-cover steps 8..9
    steps = [m["step"] for m in runner.metrics_history]
    assert steps.count(8) == 2            # step 8 replayed from the step-8 ckpt
    first = [m["loss"] for m in runner.metrics_history if m["step"] == 8]
    assert abs(first[0] - first[1]) < 1e-6  # bit-deterministic replay


def test_runner_exhausts_restart_budget(tmp_path):
    cfg, step, data, ckpt, params, opt = _toy_setup(tmp_path, total_steps=8)
    cfg.max_restarts = 2

    def always_fail(s):
        if s == 3:
            raise RuntimeError("persistent fault")

    runner = FaultTolerantRunner(cfg, train_step=jax.jit(step), data=data,
                                 ckpt=ckpt, failure_hook=always_fail)
    with pytest.raises(RuntimeError, match="restart budget"):
        runner.run(params, opt)


# ---------------------------------------------------------------------------
# Optimizer + compression
# ---------------------------------------------------------------------------

def test_cosine_schedule_shape():
    # step 0 takes a real (1/warmup) step — a silent-no-op first step was a bug
    assert abs(float(cosine_lr(jnp.int32(0), peak=1.0, warmup=10, total=100)) - 0.1) < 1e-6
    assert abs(float(cosine_lr(jnp.int32(10), peak=1.0, warmup=10, total=100)) - 1.0) < 1e-6
    end = float(cosine_lr(jnp.int32(100), peak=1.0, warmup=10, total=100, floor=0.1))
    assert abs(end - 0.1) < 1e-6


def test_adamw_converges_on_quadratic():
    params = {"w": jnp.array([3.0, -2.0])}
    opt = adamw_init(params)
    for i in range(300):
        grads = {"w": 2 * params["w"]}
        params, opt = adamw_update(params, grads, opt, lr=0.05, weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_grad_clipping_bounds_update():
    params = {"w": jnp.zeros((4,))}
    opt = adamw_init(params)
    grads = {"w": jnp.full((4,), 1e6)}
    p2, _ = adamw_update(params, grads, opt, lr=0.1, clip_norm=1.0, weight_decay=0.0)
    assert float(jnp.abs(p2["w"]).max()) < 1.0


def test_compression_error_feedback_preserves_convergence():
    """int8-compressed gradients with error feedback still drive a quadratic
    to its minimum (the 1000-node DP-traffic trick, tested for correctness)."""
    params = {"w": jnp.array([3.0, -2.0, 1.5, -0.5])}
    opt = adamw_init(params)
    err = None
    for i in range(400):
        grads = {"w": 2 * params["w"]}
        q, scales, err = compress_grads(grads, err)
        grads_hat = decompress_grads(q, scales)
        params, opt = adamw_update(params, grads_hat, opt, lr=0.05, weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 2e-2


def test_compression_is_4x_smaller():
    g = {"w": jnp.ones((1024,), jnp.float32)}
    q, s, e = compress_grads(g, None)
    assert q["w"].dtype == jnp.int8
    assert q["w"].nbytes == g["w"].nbytes // 4


def test_global_norm():
    t = {"a": jnp.ones((3,)), "b": jnp.ones((4, 3))}
    np.testing.assert_allclose(float(global_norm(t)), np.sqrt(15.0), rtol=1e-6)

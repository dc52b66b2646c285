"""Fault tolerance: crash/restart bitwise resume, stragglers, checkpoints,
incremental embedding updates, grad compression."""
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, incremental_embedding_update, latest_step
from repro.data import dlrm_batch_stream
from repro.models import dlrm
from repro.optim import AdamW, TrainState, make_train_step
from repro.optim.compression import (ErrorFeedbackState, compress_int8,
                                     decompress_int8)
from repro.runtime import Trainer, TrainerConfig

ARCH = dlrm.DLRMArch(user_tables=(400,) * 3, item_tables=(400,) * 2,
                     embed_dim=8, bottom_mlp=(16, 8), top_mlp=(16, 1), pooling=4)


def _make(tmpdir, total=24, failure_hook=None):
    params = dlrm.init_params(ARCH, jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    step = jax.jit(make_train_step(lambda p, b: dlrm.loss_fn(p, b, ARCH), opt))
    cfg = TrainerConfig(total_steps=total, ckpt_every=8, ckpt_dir=str(tmpdir))
    return Trainer(step, TrainState(params, opt),
                   lambda s0: dlrm_batch_stream(ARCH, 16, seed=0, start_step=s0),
                   cfg, failure_hook=failure_hook)


def test_crash_restart_bitwise_resume(tmp_path):
    class Boom(RuntimeError):
        pass

    def fail_once(step):
        if step == 13 and not getattr(fail_once, "fired", False):
            fail_once.fired = True
            raise Boom()

    t1 = _make(tmp_path / "a", failure_hook=fail_once)
    with pytest.raises(Boom):
        t1.run()
    t2 = _make(tmp_path / "a")
    out = t2.run()
    assert out["final_step"] == 24

    ref = _make(tmp_path / "b")
    ref.run()
    for a, b in zip(jax.tree.leaves(t2.state), jax.tree.leaves(ref.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"w": jnp.arange(4.0), "step": jnp.array(0)}
    for s in (1, 2, 3):
        mgr.save(state, s)
    assert latest_step(str(tmp_path)) == 3
    kept = sorted(p.name for p in tmp_path.iterdir())
    assert "step_1" not in kept  # gc'd
    restored, step = mgr.restore(state)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(4.0))


def test_restore_with_shardings(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.arange(8.0)}
    mgr.save(state, 5)
    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((1,), ("data",))
    sh = {"w": jax.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))}
    restored, _ = mgr.restore(state, shardings=sh)
    assert restored["w"].sharding == sh["w"]


def test_incremental_embedding_update(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save({"t": jnp.zeros(4)}, 1)
    path = incremental_embedding_update(str(tmp_path), 1,
                                        {"table_0": np.ones((4, 2))}, update_id=7)
    assert "emb_update_7" in path


def test_straggler_detection(tmp_path):
    import time
    t = _make(tmp_path, total=16)
    seen = []
    t.straggler_hook = lambda step, ratio: seen.append((step, ratio))
    slow = {14}

    orig = t.step_fn
    def slow_step(state, batch):
        if int(state["step"]) in slow:
            time.sleep(0.25)
        return orig(state, batch)
    t.step_fn = slow_step
    out = t.run(resume=False)
    assert out["stragglers"], "slow step not detected"


def test_int8_compression_roundtrip_and_error_feedback():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, s = compress_int8(x)
    err1 = x - decompress_int8(q, s)
    assert float(jnp.max(jnp.abs(err1))) <= float(s) * 0.5 + 1e-6
    # error feedback: residual carries quantization error to the next step
    ef = ErrorFeedbackState({"g": x})["g"]
    gc = x + ef
    q2, s2 = compress_int8(gc)
    new_ef = gc - decompress_int8(q2, s2)
    assert float(jnp.mean(jnp.abs(new_ef))) < float(jnp.mean(jnp.abs(x)))

"""Checkpoint/resume: a restored run continues bit-exactly, including on
a sharded mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fishnet_tpu.models.az import AzConfig
from fishnet_tpu.train import AzTrainer, NetConfig, Trainer
from fishnet_tpu.train.checkpoint import restore_checkpoint, save_checkpoint

TINY_NNUE = NetConfig(num_features=512, max_active=8, l1=64, l2=15, l3=32, king_buckets=1)
TINY_AZ = AzConfig(channels=16, blocks=2, value_hidden=16)


def nnue_batch(rng, cfg, batch):
    indices = np.full((batch, 2, cfg.max_active), cfg.num_features, np.int32)
    for b in range(batch):
        for p in range(2):
            indices[b, p, :4] = rng.choice(cfg.num_features, 4, replace=False)
    return {
        "indices": jnp.asarray(indices),
        "buckets": jnp.asarray(rng.integers(0, 8, batch).astype(np.int32)),
        "score_cp": jnp.asarray(rng.normal(0, 100, batch).astype(np.float32)),
        "outcome": jnp.asarray(rng.choice([0.0, 0.5, 1.0], batch).astype(np.float32)),
    }


def test_nnue_resume_bit_exact(tmp_path):
    rng = np.random.default_rng(0)
    trainer = Trainer(cfg=TINY_NNUE)
    batch = nnue_batch(rng, TINY_NNUE, 8)

    # Uninterrupted: 4 steps.
    state = trainer.init(seed=0)
    for _ in range(4):
        state, _ = trainer.step(state, batch)
    reference = jax.device_get(state.params)

    # Interrupted: 2 steps, checkpoint, restore, 2 more.
    state = trainer.init(seed=0)
    for _ in range(2):
        state, _ = trainer.step(state, batch)
    save_checkpoint(tmp_path / "ckpt", state)
    restored = restore_checkpoint(tmp_path / "ckpt", trainer.init(seed=0))
    assert int(restored.step) == 2
    for _ in range(2):
        restored, _ = trainer.step(restored, batch)

    resumed = jax.device_get(restored.params)
    for k in reference:
        np.testing.assert_array_equal(reference[k], resumed[k], err_msg=k)


def test_az_sharded_resume(tmp_path):
    from fishnet_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(devices[:8])
    data, model = mesh.devices.shape
    cfg = AzConfig(channels=8 * model, blocks=2, value_hidden=16)
    trainer = AzTrainer(cfg=cfg, mesh=mesh)

    from test_az_trainer import make_batch

    batch = make_batch(np.random.default_rng(3), 8 * data)
    state = trainer.init(seed=3)
    state, _ = trainer.step(state, batch)
    save_checkpoint(tmp_path / "az", state)
    restored = restore_checkpoint(tmp_path / "az", trainer.init(seed=3))
    assert int(restored.step) == 1
    restored, metrics = trainer.step(restored, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(restored.step) == 2

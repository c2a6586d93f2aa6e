"""AZ policy+value trainer: loss decreases, sharded step on the virtual
mesh, and checkpoint export round-trips into the az-mcts engine."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fishnet_tpu.models.az import AzConfig
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.models.az_encoding import INPUT_PLANES, POLICY_SIZE
from fishnet_tpu.train import AzTrainer

TINY = AzConfig(channels=16, blocks=2, value_hidden=16)
# The sparse-expert trunk through the same trainer (models/trunk.py).
TINY_TRUNK = TrunkConfig(hidden=32, heads=2, head_dim=16, layers=1, experts=4,
                         experts_per_token=2, expert_width=16, value_hidden=16)


def make_batch(rng, batch):
    planes = rng.normal(0, 1, (batch, 8, 8, INPUT_PLANES)).astype(np.float32)
    pol = np.zeros((batch, POLICY_SIZE), np.float32)
    # Concentrated targets on a few "legal" moves per position.
    for b in range(batch):
        idx = rng.choice(POLICY_SIZE, size=8, replace=False)
        w = rng.random(8).astype(np.float32)
        pol[b, idx] = w / w.sum()
    values = rng.uniform(-1, 1, batch).astype(np.float32)
    return {
        "planes": jnp.asarray(planes),
        "policy_target": jnp.asarray(pol),
        "value_target": jnp.asarray(values),
    }


def test_az_training_overfits_small_batch():
    rng = np.random.default_rng(0)
    trainer = AzTrainer(cfg=TINY, learning_rate=3e-3)
    state = trainer.init(seed=0)
    batch = make_batch(rng, 8)
    losses = []
    for _ in range(30):
        state, metrics = trainer.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.9, losses[::10]
    assert int(state.step) == 30


@pytest.mark.parametrize("net", ["tower", "trunk"])
def test_az_training_sharded_mesh(net):
    from fishnet_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(devices[:8])
    data, model = mesh.devices.shape
    cfg = AzConfig(channels=8 * model, blocks=2, value_hidden=16) if net == "tower" else TINY_TRUNK
    trainer = AzTrainer(cfg=cfg, mesh=mesh)
    state = trainer.init(seed=1)
    batch = make_batch(np.random.default_rng(1), 8 * data)
    state, metrics = trainer.step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 1


@pytest.mark.parametrize("net", [TINY, TINY_TRUNK], ids=["tower", "trunk"])
def test_az_export_roundtrip_into_engine(tmp_path, net):
    from fishnet_tpu.models.az import az_config_from_params

    trainer = AzTrainer(cfg=net)
    state = trainer.init(seed=2)
    path = tmp_path / "az.npz"
    trainer.export(state, str(path))

    # As --az-net-file loads it (__main__.py): the arrays of the file and
    # the architecture recovered from them.
    loaded = np.load(path)
    params = {k: jnp.asarray(loaded[k]) for k in loaded.files}
    assert set(state.params) <= set(params) <= set(state.params) | {"trunk_hparams"}
    assert az_config_from_params({k: loaded[k] for k in loaded.files}) == net

    # The exported checkpoint must drive the MCTS pool directly.
    from fishnet_tpu.search.mcts import MctsConfig, MctsPool

    pool = MctsPool(params, MctsConfig(batch_capacity=64, az=net))
    sid = pool.submit(
        "6k1/5ppp/8/8/8/8/5PPP/3R2K1 w - - 0 1", [], visits=200
    )
    for _ in range(5000):
        pool.step()
        if pool.active() == 0:
            break
    assert pool.harvest(sid).best_move == "d1d8"


def test_az_config_recovered_from_checkpoint_shapes(tmp_path):
    """--az-net-file must work for nets trained with any AzConfig: the
    architecture is inferred from parameter shapes (models/az.py), not
    assumed to be the default."""
    from fishnet_tpu.models.az import az_config_from_params

    cfg = AzConfig(channels=24, blocks=3, value_hidden=20)
    trainer = AzTrainer(cfg=cfg)
    state = trainer.init(seed=3)
    path = tmp_path / "az24.npz"
    trainer.export(state, str(path))

    loaded = np.load(path)
    params = {k: loaded[k] for k in loaded.files}
    assert az_config_from_params(params) == cfg


def test_az_config_rejects_non_az_checkpoint():
    from fishnet_tpu.models.az import az_config_from_params

    with pytest.raises(ValueError, match="not an AZ checkpoint"):
        az_config_from_params({"w": np.zeros((3, 3))})

    # Right keys, tampered shape: still a clear error.
    trainer = AzTrainer(cfg=TINY)
    params = {k: np.asarray(v) for k, v in trainer.init(seed=0).params.items()}
    params["value_fc1_w"] = params["value_fc1_w"][:, :-1]
    with pytest.raises(ValueError, match="does not match"):
        az_config_from_params(params)

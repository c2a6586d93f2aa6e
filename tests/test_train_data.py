"""NNUE data pipeline: playouts -> teacher labeling -> trainer step."""

import numpy as np
import pytest

import jax.numpy as jnp

from fishnet_tpu.nnue import spec
from fishnet_tpu.nnue.weights import NnueWeights
from fishnet_tpu.search.service import SearchService
from fishnet_tpu.train import NetConfig, Trainer
from fishnet_tpu.train.data import label_positions, playout_positions

pytestmark = pytest.mark.anyio


def test_playout_positions_shapes():
    positions = playout_positions(n_games=3, max_plies=20, seed=0)
    assert positions
    for fen, score in positions:
        assert score in (0.0, 0.5, 1.0)
        assert len(fen.split()) >= 4


async def test_label_and_train():
    service = SearchService(
        weights=NnueWeights.random(seed=0), pool_slots=64,
        batch_capacity=64, tt_bytes=8 << 20, backend="scalar",
    )
    try:
        positions = playout_positions(n_games=2, max_plies=16, seed=1)[:12]
        batch_np = await label_positions(service, positions, nodes=400)
    finally:
        service.close()

    n = batch_np["indices"].shape[0]
    assert n > 0
    assert batch_np["indices"].shape == (n, 2, 32)
    assert np.all(batch_np["indices"] <= spec.NUM_FEATURES)
    assert np.all(np.abs(batch_np["score_cp"]) <= 30000)
    assert set(np.unique(batch_np["outcome"])) <= {0.0, 0.5, 1.0}

    # The full-spec trainer consumes the batch directly.
    trainer = Trainer(cfg=NetConfig())
    state = trainer.init(seed=0)
    batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
    state, metrics = trainer.step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(metrics["ft_block_misses"]) == 0  # the encoder keeps NetConfig's block contract

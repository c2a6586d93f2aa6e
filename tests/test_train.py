"""Training subsystem: float model, sharded train step, quantization
export consistency with the integer serving path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.nnue import spec
from fishnet_tpu.nnue.jax_eval import evaluate_batch_jit, params_from_weights
from fishnet_tpu.parallel.mesh import factor_mesh, make_mesh
from fishnet_tpu.train import NetConfig, Trainer, forward, init_params, quantize
from fishnet_tpu.train import model as model_lib
from fishnet_tpu.train.data import playout_positions
from fishnet_tpu.train.model import NNUE2SCORE

TINY = NetConfig(num_features=256, max_active=8, l1=32, l2=15, l3=32, king_buckets=1)


def fake_batch(rng, n, cfg):
    indices = np.full((n, 2, cfg.max_active), cfg.num_features, dtype=np.int32)
    for b in range(n):
        k = int(rng.integers(2, cfg.max_active + 1))
        for p in range(2):
            indices[b, p, :k] = np.sort(rng.choice(cfg.num_features, k, replace=False))
    return {
        "indices": jnp.asarray(indices),
        "buckets": jnp.asarray(rng.integers(0, 8, n, dtype=np.int32)),
        "score_cp": jnp.asarray(rng.normal(0, 150, n).astype(np.float32)),
        "outcome": jnp.asarray(rng.choice([0.0, 0.5, 1.0], n).astype(np.float32)),
    }


def test_forward_shapes_and_padding():
    params = init_params(jax.random.PRNGKey(0), TINY)
    rng = np.random.default_rng(0)
    batch = fake_batch(rng, 4, TINY)
    out = forward(params, batch["indices"], batch["buckets"], TINY)
    assert out.shape == (4,)
    assert np.all(np.isfinite(np.asarray(out)))

    # Sentinel-padded slots are no-ops: adding extra padding cannot
    # change the output.
    idx2 = np.asarray(batch["indices"]).copy()
    out2 = forward(params, jnp.asarray(idx2), batch["buckets"], TINY)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-6)


def test_train_step_reduces_loss_single_device():
    trainer = Trainer(cfg=TINY, learning_rate=5e-3)
    state = trainer.init(seed=0)
    rng = np.random.default_rng(1)
    batch = fake_batch(rng, 128, TINY)
    losses = []
    for _ in range(30):
        state, metrics = trainer.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses[:3] + losses[-3:]
    assert int(state.step) == 30


def test_train_step_sharded_matches_single_device():
    mesh = make_mesh()  # 8 virtual CPU devices from conftest
    assert mesh.devices.size == 8
    cfg = NetConfig(num_features=256, max_active=8, l1=64, l2=15, l3=32, king_buckets=1)

    rng = np.random.default_rng(2)
    batch = fake_batch(rng, 64, cfg)

    t_single = Trainer(cfg=cfg, learning_rate=1e-3)
    t_shard = Trainer(cfg=cfg, mesh=mesh, learning_rate=1e-3)
    s_single = t_single.init(seed=3)
    s_shard = t_shard.init(seed=3)

    for _ in range(3):
        s_single, m_single = t_single.step(s_single, batch)
        s_shard, m_shard = t_shard.step(s_shard, batch)

    np.testing.assert_allclose(
        float(m_single["loss"]), float(m_shard["loss"]), rtol=1e-4
    )
    for key in s_single.params:
        np.testing.assert_allclose(
            np.asarray(s_single.params[key]),
            np.asarray(s_shard.params[key]),
            rtol=2e-4,
            atol=2e-6,
            err_msg=key,
        )


# -- the table gradient (model._table_grad) -------------------------------------

BLOCKED = NetConfig(num_features=64, max_active=8, l1=32, l2=15, l3=32, king_buckets=4)


def blocked_indices(rng, blocks):
    """``blocks`` int [B, 2]: each pair's 0..max_active active rows, all
    inside its block, padded with ``num_features``."""
    cfg = BLOCKED
    indices = np.full(blocks.shape + (cfg.max_active,), cfg.num_features, np.int32)
    for pair in np.ndindex(*blocks.shape):
        k = int(rng.integers(0, cfg.max_active + 1))
        indices[pair][:k] = blocks[pair] * cfg.block_rows + rng.choice(cfg.block_rows, k, replace=False)
    return indices


def uneven(rng, n):
    """Block 0 holds more pairs than one tile, block 1 none."""
    blocks = rng.choice([0, 2, 3], size=(n, 2), p=[0.65, 0.05, 0.3])
    counts = np.bincount(blocks.ravel(), minlength=4)
    assert counts[0] > model_lib.GRAD_TILE and counts[1] == 0 and len(set(counts)) == 4
    return blocked_indices(rng, blocks)


def all_padding(rng, n):
    indices = uneven(rng, n)
    indices[:40, 0] = BLOCKED.num_features  # one perspective empty
    indices[20:60] = BLOCKED.num_features  # both
    return indices


def same_and_different(rng, _n):
    return blocked_indices(rng, np.array([[2, 2], [1, 3]]))


def one_block(rng, n):
    return np.asarray(fake_batch(rng, n, TINY)["indices"])


TABLE_GRAD_CASES = {
    "uneven_groups": (BLOCKED, uneven, 512),
    "all_padding_rows": (BLOCKED, all_padding, 512),
    "same_and_different_blocks": (BLOCKED, same_and_different, 2),
    "one_block_arbitrary": (TINY, one_block, 37),
    "not_a_multiple_of_the_tile": (BLOCKED, uneven, 2 * model_lib.GRAD_TILE + 45),
}


@pytest.mark.parametrize("table", ["ft_w", "ft_psqt"])
@pytest.mark.parametrize("case", list(TABLE_GRAD_CASES))
def test_table_grad_matches_gather_and_sum(case, table):
    """The grouped matmul against ``jax.grad`` of the plain gather, mask
    and sum it replaces: float32-faithful, so they agree to rounding."""
    cfg, make_indices, n = TABLE_GRAD_CASES[case]
    rng = np.random.default_rng(sorted(TABLE_GRAD_CASES).index(case))
    indices = jnp.asarray(make_indices(rng, n))
    assert int(model_lib.ft_block_misses(cfg, indices)) == 0
    width = cfg.l1 if table == "ft_w" else cfg.num_buckets
    weights = jnp.asarray(rng.normal(size=(cfg.num_features, width)).astype(np.float32))
    cotangent = jnp.asarray(rng.normal(size=indices.shape[:2] + (width,)).astype(np.float32))

    def plain(w):
        mask = (indices < cfg.num_features)[..., None].astype(w.dtype)
        rows = jnp.take(w, jnp.minimum(indices, cfg.num_features - 1), axis=0) * mask
        return jnp.sum(jnp.sum(rows, axis=2) * cotangent)

    new = jax.grad(lambda w: jnp.sum(model_lib.accumulate(cfg, w, indices) * cotangent))(weights)
    old = jax.grad(plain)(weights)
    assert float(jnp.linalg.norm(old)) > 0
    assert float(jnp.linalg.norm(new - old) / jnp.linalg.norm(old)) <= 1e-6


def test_ft_block_misses_is_zero_on_encoder_output():
    from fishnet_tpu.chess.board import Board

    fens = [fen for fen, _ in playout_positions(n_games=3, max_plies=80, seed=3)]
    indices = jnp.asarray(np.stack([Board(fen).nnue_features()[0] for fen in fens]))
    cfg = NetConfig()
    assert cfg.king_buckets == spec.NUM_KING_BUCKETS and cfg.block_rows == spec.FEATURES_PER_BUCKET
    assert int(jnp.sum(indices < cfg.num_features)) > 4 * len(fens)
    assert int(model_lib.ft_block_misses(cfg, indices)) == 0
    pair_block, _ = model_lib._pair_blocks(cfg, indices)
    assert len(np.unique(np.asarray(pair_block))) > 4  # the kings do move


def test_ft_block_misses_counts_a_violating_batch():
    """One active index moved out of its pair's block: the step's metric
    reads it, and that entry's gradient is the one that is lost."""
    rng = np.random.default_rng(7)
    indices = blocked_indices(rng, np.full((16, 2), 1))
    indices[:, :, 0] = BLOCKED.block_rows + 3  # every pair has an active row in block 1
    assert int(model_lib.ft_block_misses(BLOCKED, jnp.asarray(indices))) == 0
    indices[5, 1, 0] = 2  # block 0
    batch = dict(fake_batch(rng, 16, BLOCKED), indices=jnp.asarray(indices))
    trainer = Trainer(cfg=BLOCKED)
    _, metrics = trainer.step(trainer.init(seed=0), batch)
    assert int(metrics["ft_block_misses"]) == 1
    ones = jnp.ones((BLOCKED.num_features, 1), jnp.float32)
    grad = jax.grad(lambda w: jnp.sum(model_lib.accumulate(BLOCKED, w, batch["indices"])))(ones)
    kept = BLOCKED.block_rows + 3
    assert float(grad[2, 0]) == 0.0 and float(grad[kept, 0]) == np.sum(indices == kept) >= 31


def test_factor_mesh():
    assert factor_mesh(8) == (4, 2)
    assert factor_mesh(1) == (1, 1)
    assert factor_mesh(7) == (7, 1)
    assert factor_mesh(4, max_model=4) == (1, 4)


@pytest.mark.slow
def test_quantize_roundtrip_tracks_float():
    """Quantized integer eval of exported weights tracks the float model
    on full-spec shapes. With random (untrained) weights int8 rounding
    noise accumulates across the 1024-wide l1 contraction, so the bound
    is statistical: high correlation and modest mean error. (Trained
    nets, whose weights co-adapt to the grid via clip_params, sit much
    tighter.)"""
    cfg = NetConfig()
    params = init_params(jax.random.PRNGKey(4), cfg)
    params["ft_psqt"] = (
        jax.random.normal(jax.random.PRNGKey(5), params["ft_psqt"].shape) * 0.02
    )
    weights = quantize(params, cfg)
    qparams = params_from_weights(weights)

    rng = np.random.default_rng(5)
    n = 32
    indices = np.full((n, 2, cfg.max_active), cfg.num_features, dtype=np.int32)
    for b in range(n):
        k = int(rng.integers(8, cfg.max_active + 1))
        for p in range(2):
            indices[b, p, :k] = np.sort(rng.choice(cfg.num_features, k, replace=False))
    buckets = rng.integers(0, 8, n, dtype=np.int32)

    float_cp = np.asarray(
        forward(params, jnp.asarray(indices), jnp.asarray(buckets), cfg)
    ) * NNUE2SCORE
    # Integer path pads with NUM_FEATURES sentinel too.
    int_cp = np.asarray(
        evaluate_batch_jit(qparams, jnp.asarray(indices), jnp.asarray(buckets))
    )
    err = np.abs(float_cp - int_cp)
    corr = np.corrcoef(float_cp, int_cp)[0, 1]
    # Slope ~1 catches any scale-wiring bug (e.g. a wrong psqt or output
    # export scale); corr/mean bound the rounding noise.
    slope = float(np.polyfit(float_cp, int_cp, 1)[0])
    assert 0.8 <= slope <= 1.25, slope
    assert corr > 0.95, (corr, float_cp[:5], int_cp[:5])
    assert float(err.mean()) <= 60.0, err.mean()

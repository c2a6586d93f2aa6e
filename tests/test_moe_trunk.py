"""The sparse-expert square-token trunk (models/trunk.py) at a tiny size
on the CPU: hidden 64, 4 heads x 16, 8 experts top-2, width 32, 2
layers, batch 8. The grouped product runs as the same Pallas kernel as
on the chip, in interpret mode.

The plain reference below is written from the layer equations in
float32 with every expert applied to every token; the program rounds the
operands of its matrix products to bfloat16, so the two differ by the
rounding of 8-bit mantissas carried through two layers (~1% of the
logits' norm, a few % of a gradient tensor's; the readings are beside
each tolerance), and a rounding that swaps a token's second and third
expert moves that token's output by more than any rounding of a product
does (8 positions hold few tokens to average that out). Each tolerance
is shown tight enough by three wrong references (no combine weights,
renormalised top-k, causal attention), which have to miss it by more
than 1.5x.
"""


from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_checkpoint, az_config_from_params, az_forward, init_az_params
from fishnet_tpu.models.trunk import TrunkConfig, trunk_forward
from fishnet_tpu.train.az_trainer import AzTrainer
from trunk_tiny import (  # noqa: E402
    AFMOE,
    BATCH,
    CANCELLING,
    GRAD_ALL_TOL,
    GRAD_CANCELLING_TOL,
    GRAD_TENSOR_TOL,
    LOGITS_TOL,
    MLA,
    TINY,
    VALUE_TOL,
    _norm,
    _rope,
    batch_of,
    conditioned_params,
    rel,
)


def reference_forward(p, planes, cfg, wrong=""):
    """The layer equations, float32, dense over the experts. ``wrong``
    leaves one piece of the mathematics out, for the tolerance's test."""
    b = planes.shape[0]
    x = planes.reshape(b, 64, 19) @ p["embed_w"] + p["embed_b"]
    for i in range(cfg.layers):
        n1 = _norm(x, p["attn_norm"][i], cfg.rms_eps)
        q, k, v = ((n1 @ p[w][i]).reshape(b, 64, cfg.heads, cfg.head_dim) for w in ("wq", "wk", "wv"))
        q = _rope(_norm(q, p["q_norm"][i], cfg.rms_eps), cfg.rope_theta)
        k = _rope(_norm(k, p["k_norm"][i], cfg.rms_eps), cfg.rope_theta)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(cfg.head_dim)
        if wrong == "causal":
            scores = jnp.where(np.tril(np.ones((64, 64), bool)), scores, -1e30)
        mixed = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v).reshape(b, 64, -1)
        x = x + mixed @ p["wo"][i]
        n2 = _norm(x, p["moe_norm"][i], cfg.rms_eps)
        route = jax.nn.softmax(n2 @ p["router_w"][i], -1)
        kth = jnp.sort(route, -1)[..., -cfg.experts_per_token][..., None]
        weights = jnp.where(route >= kth, route, 0.0)
        if wrong == "renormalised":
            weights = weights / weights.sum(-1, keepdims=True)
        if wrong == "unweighted":
            weights = (weights > 0).astype(jnp.float32)
        for e in range(cfg.experts):
            act = jax.nn.silu(n2 @ p["experts_gate"][i, e]) * (n2 @ p["experts_up"][i, e])
            x = x + weights[..., e, None] * (act @ p["experts_down"][i, e])
    x = _norm(x, p["final_norm"], cfg.rms_eps)
    logits = (x @ p["policy_w"][0, 0] + p["policy_b"]).reshape(b, -1)
    v = jax.nn.relu(x @ p["value_w"][0, 0] + p["value_b"]).reshape(b, -1)
    v = jax.nn.relu(v @ p["value_fc1_w"] + p["value_fc1_b"])
    return logits, jnp.tanh(v @ p["value_fc2_w"] + p["value_fc2_b"])[:, 0]


def reference_loss(p, batch, cfg, wrong=""):
    logits, value = reference_forward(p, batch["planes"], cfg, wrong)
    policy = -jnp.mean(jnp.sum(batch["policy_target"] * jax.nn.log_softmax(logits, -1), -1))
    return policy + jnp.mean((value - batch["value_target"]) ** 2)


@pytest.fixture(scope="module")
def program():
    trainer = AzTrainer(TINY)
    forward = jax.jit(lambda p, x: trunk_forward(p, x, TINY))
    grad = jax.jit(jax.grad(lambda p, b: trainer._loss(p, b)[0]))
    return forward, grad


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_forward_matches_the_plain_reference(program, seed):
    params, batch = conditioned_params(seed), batch_of(seed)
    logits, value = program[0](params, batch["planes"])
    assert logits.shape == (BATCH, 4672) and value.shape == (BATCH,) and logits.dtype == value.dtype == jnp.float32
    want_logits, want_value = reference_forward(params, batch["planes"], TINY)
    assert rel(logits, want_logits) < LOGITS_TOL, rel(logits, want_logits)
    assert float(jnp.max(jnp.abs(value - want_value))) < VALUE_TOL
    # az_forward routes a TrunkConfig to the same network
    routed = jax.jit(lambda p, x: az_forward(p, x, TINY))(params, batch["planes"])
    assert jnp.array_equal(routed[0], logits) and jnp.array_equal(routed[1], value)


@pytest.mark.parametrize("wrong", ["unweighted", "renormalised", "causal"])
def test_the_tolerance_catches_left_out_mathematics(program, wrong):
    params, batch = conditioned_params(1), batch_of(1)
    logits, _value = program[0](params, batch["planes"])
    missed = rel(logits, reference_forward(params, batch["planes"], TINY, wrong)[0])
    assert missed > 1.5 * LOGITS_TOL, (wrong, missed)



@pytest.mark.parametrize("seed", [1, 2, 3])
def test_gradient_of_the_trainers_loss_matches_the_plain_reference(program, seed):
    params, batch = conditioned_params(seed), batch_of(seed)
    got = program[1](params, batch)
    want = jax.grad(reference_loss)(params, batch, TINY)
    assert set(got) == set(want)
    diff = np.sqrt(sum(float(jnp.sum((got[k] - want[k]) ** 2)) for k in want))
    assert diff / np.sqrt(sum(float(jnp.sum(want[k] ** 2)) for k in want)) < GRAD_ALL_TOL
    for name in want:
        assert float(jnp.linalg.norm(want[name])) > 0, name  # every tensor has a gradient to compare
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL), name
    wrong = jax.grad(reference_loss)(params, batch, TINY, "renormalised")
    missed = np.sqrt(sum(float(jnp.sum((got[k] - wrong[k]) ** 2)) for k in want) / sum(float(jnp.sum(wrong[k] ** 2)) for k in want))
    assert missed > 1.5 * GRAD_ALL_TOL, missed


def _dense_experts(n2, layer, cfg):
    route = jax.nn.softmax(n2 @ layer["router_w"], -1)
    kth = jnp.sort(route, -1)[:, -cfg.experts_per_token][:, None]
    weights = jnp.where(route >= kth, route, 0.0)
    out = jnp.zeros_like(n2)
    for e in range(cfg.experts):
        act = jax.nn.silu(n2 @ layer["experts_gate"][e]) * (n2 @ layer["experts_up"][e])
        out = out + weights[:, e, None] * (act @ layer["experts_down"][e])
    return out, (weights > 0).sum(0)


@pytest.mark.parametrize("top_k,favoured,expected_load", [
    (1, [0], [512, 0, 0, 0, 0, 0, 0, 0]),        # every token to one expert: group sizes [N*k, 0, 0, ...]
    (2, [2, 5], [0, 0, 512, 0, 0, 512, 0, 0]),   # two experts take everything, six get none
    (2, [], None),                               # the router's own choice: uneven, some small
])
def test_dropless_routing_computes_every_slot(top_k, favoured, expected_load):
    cfg = TrunkConfig(hidden=64, heads=4, head_dim=16, layers=1, experts=8, experts_per_token=top_k, expert_width=32)
    rng = np.random.default_rng(7)
    layer = {k: v[0] for k, v in conditioned_params(7, cfg).items() if k in ("router_w", "experts_gate", "experts_up", "experts_down")}
    n2 = jnp.asarray(np.abs(rng.standard_normal((512, 64))) + 0.1, jnp.float32)  # all positive: a column of ones is a bias
    if favoured:
        layer["router_w"] = layer["router_w"].at[:, jnp.asarray(favoured)].add(1.0)
    got, counters = jax.jit(lambda n, l: trunk._experts(n, l, cfg, "layer00"))(n2, layer)
    want, load = _dense_experts(n2, layer, cfg)
    assert rel(got, want) < 0.02, rel(got, want)
    assert int(load.sum()) == 512 * top_k
    if expected_load is not None:
        assert load.tolist() == expected_load
    assert float(counters["expert_load_max"]) == int(load.max()) and float(counters["expert_load_min"]) == int(load.min())
    assert 0.0 <= float(counters["router_entropy"]) <= np.log(8) + 1e-6


@pytest.mark.parametrize("rows,sizes", [
    (1024, [100, 0, 300, 5, 119, 200, 0, 300]),  # tile 512: groups that straddle tiles, empty groups
    (1024, [1024, 0, 0, 0, 0, 0, 0, 0]),
    (192, [3, 0, 60, 5, 19, 20, 0, 85]),         # tile gcd(192, 512) = 64
    (64, [0, 0, 0, 0, 0, 0, 1, 63]),
])
def test_grouped_matmul_against_a_loop_over_experts(rows, sizes):
    rng = np.random.default_rng(rows + sizes[0])
    x = jnp.asarray(rng.standard_normal((rows, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 64, 32)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    starts = np.concatenate([[0], np.cumsum(sizes)])

    def loop(x, w):
        xb, wb = x.astype(jnp.bfloat16).astype(jnp.float32), w.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.concatenate([xb[starts[e]:starts[e + 1]] @ wb[e] for e in range(8)])

    got = jax.jit(trunk.grouped_matmul)(x, w, group_sizes)
    assert got.shape == (rows, 32) and got.dtype == jnp.bfloat16
    assert rel(got, loop(x, w)) < 4e-3  # the result's own rounding to bfloat16
    cot = jnp.asarray(rng.standard_normal((rows, 32)), jnp.float32)
    got_dx, got_dw = jax.jit(jax.grad(lambda x, w: jnp.sum(trunk.grouped_matmul(x, w, group_sizes).astype(jnp.float32) * cot), (0, 1)))(x, w)
    want_dx, want_dw = jax.grad(lambda x, w: jnp.sum(loop(x, w) * cot), (0, 1))(x, w)
    assert rel(got_dx, want_dx) < 6e-3 and rel(got_dw, want_dw) < 6e-3  # bfloat16 cotangent and results
    assert not np.any(np.asarray(got_dw)[np.asarray(sizes) == 0])  # an expert with no rows has no gradient


@pytest.mark.parametrize("width,most,tile", [(1536, 1024, 768), (2048, 1024, 1024), (768, 1024, 768), (1024, 1024, 1024), (32, 1024, 32), (96, 64, 48)])
def test_the_products_tile_divides_the_width(width, most, tile):
    """Gate and up joined are 1,536 columns at a width of 768: a tile of
    1,024 would be computed twice and half empty the second time."""
    assert trunk._tile(width, most) == tile and width % tile == 0


def test_grouped_matmul_at_joined_columns_the_largest_tile_does_not_divide():
    """``[rows, 128] x [4, 128, 1536]``: gate and up of width 768 joined.
    Columns in two tiles of 768 forward and in ``tgmm``, and as the
    contraction of the transposed product in the rows' gradient, against
    a loop over the groups."""
    rng = np.random.default_rng(768)
    sizes, rows = [200, 0, 300, 12], 512
    x = jnp.asarray(rng.standard_normal((rows, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 128, 1536)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((rows, 1536)), jnp.float32)
    starts = np.concatenate([[0], np.cumsum(sizes)])

    def loop(x, w):
        xb, wb = x.astype(jnp.bfloat16).astype(jnp.float32), w.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.concatenate([xb[starts[e]:starts[e + 1]] @ wb[e] for e in range(4)])

    product = lambda x, w: trunk.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32)).astype(jnp.float32)
    got, (got_dx, got_dw) = jax.jit(lambda x, w: (product(x, w), jax.grad(lambda x, w: jnp.sum(product(x, w) * cot), (0, 1))(x, w)))(x, w)
    want_dx, want_dw = jax.grad(lambda x, w: jnp.sum(loop(x, w) * cot), (0, 1))(x, w)
    assert got.shape == (rows, 1536) and rel(got, loop(x, w)) < 4e-3
    assert rel(got_dx, want_dx) < 6e-3 and rel(got_dw, want_dw) < 6e-3 and not np.any(np.asarray(got_dw)[1])


def _one_bfloat16_apart(got, want, floor: float = 1e-30) -> bool:
    """Equal to the rounding: within one unit of the last of bfloat16's 8
    bits (the kernel and XLA may round a float32 that differs in ITS last
    bit to neighbouring bfloat16 values), or within ``floor`` (where the
    value is a difference that cancels, the two formulas' last float32
    bits are more than a bfloat16 unit of the result)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return bool(np.all(np.abs(got - want) <= 2.0 ** -7 * np.abs(want) + floor))


#: slots, width, extent (None: no extent, the static grid). 1,536 slots are three blocks of the moves' 512; 192 is tiled by 64.
GATE_CASES = [(1536, 128, None), (1536, 128, 0), (1536, 128, 1), (1536, 128, 513), (1536, 128, 1536), (192, 32, None), (192, 32, 70), (64, 16, 64)]


@pytest.mark.parametrize("slots,width,extent", GATE_CASES)
def test_the_gated_activation_kernel_and_its_gradient(slots, width, extent):
    """``expert_gate`` and ``expert_gate_grad`` against ``jax.nn.silu(g) *
    u`` in float32 and ``jax.vjp`` of it, each rounded once to bfloat16.
    Under an extent they cover the rows the moves cover
    (``rows_covered``), whole blocks, and write nothing past them (NaN
    under the interpreter); the operands' tails are NaN here, as the
    products leave them."""
    from fishnet_tpu.ops.expert_gate import gated_activation
    from fishnet_tpu.ops.row_move import rows_covered

    rng = np.random.default_rng(slots + width + (extent or 0))
    held = None if extent is None else jnp.asarray(extent, jnp.int32)
    covered = int(rows_covered(slots, held))
    assert covered == (slots if extent is None else -(-extent // np.gcd(slots, 512)) * np.gcd(slots, 512))
    written = jnp.arange(slots)[:, None] < covered
    gu = jnp.where(written, jnp.asarray(3 * rng.standard_normal((slots, 2 * width)), jnp.bfloat16), jnp.nan)
    d_h = jnp.where(written, jnp.asarray(rng.standard_normal((slots, width)), jnp.bfloat16), jnp.nan)
    plain = lambda gu: jax.nn.silu(gu[:, :width].astype(jnp.float32)) * gu[:, width:].astype(jnp.float32)
    got, pull = jax.vjp(lambda gu: gated_activation(gu, held, True), gu)
    (got_d,) = pull(d_h)
    want, plain_pull = jax.vjp(plain, gu)
    (want_d,) = plain_pull(d_h.astype(jnp.float32))
    assert got.shape == (slots, width) and got_d.shape == gu.shape and got.dtype == got_d.dtype == jnp.bfloat16
    for g, w in ((got, want), (got_d, want_d)):
        g = np.asarray(g, np.float32)
        assert np.all(np.isfinite(g[:covered])) and np.all(np.isnan(g[covered:]))  # the rows it covers, and no other
        assert _one_bfloat16_apart(g[:covered], np.asarray(w.astype(jnp.bfloat16), np.float32)[:covered])


#: slots, width of a float32 ``gu`` ``[slots, 2 x width]``, the rows a grid step of ``expert_gate`` takes: 512 (the moves'
#: tile) while its block stays within 8 MiB, halved past it (a row of 2 x 2,560 float32 columns is 20 KiB: 256 rows), and a
#: divisor of the slots.
WIDE_GATE_CASES = [(256, 64, 256), (1536, 128, 512), (192, 32, 64), (512, 2560, 256)]


@pytest.mark.parametrize("slots,width,tile", WIDE_GATE_CASES)
def test_the_gate_kernels_take_a_float32_product_and_round_once_to_bfloat16(slots, width, tile):
    """``expert_gate`` and ``expert_gate_grad`` as the dense layer calls
    them: ``gu`` float32 as the product leaves it, ``h`` and ``d_gu``
    bfloat16, against the float32 formula and ``jax.vjp`` of it rounded
    once; the gradient kernel's second result is ``h`` itself; the row
    tile follows the operands' row bytes."""
    from fishnet_tpu.ops import expert_gate as gate

    assert gate._row_tile(slots, 2 * width * 4) == tile
    rng = np.random.default_rng(slots + width)
    gu = jnp.asarray(3 * rng.standard_normal((slots, 2 * width)), jnp.float32)
    d_h = jnp.asarray(rng.standard_normal((slots, width)), jnp.bfloat16)
    got = gate.expert_gate(gu, None, True)
    got_d, again = gate.expert_gate_grad(gu, d_h, None, True, with_h=True)
    assert np.array_equal(np.asarray(again, np.float32), np.asarray(got, np.float32))  # ``h`` again from the gradient kernel, bit for bit
    assert np.array_equal(np.asarray(gate.expert_gate_grad(gu, d_h, None, True), np.float32), np.asarray(got_d, np.float32))
    want, pull = jax.vjp(lambda gu: jax.nn.silu(gu[:, :width]) * gu[:, width:], gu)
    (want_d,) = pull(d_h.astype(jnp.float32))
    assert got.shape == (slots, width) and got_d.shape == gu.shape and got.dtype == got_d.dtype == jnp.bfloat16
    # silu's derivative crosses zero near gate = -1.28: of 2.6 M elements a few land within 1e-6 of it, where the kernel's
    # ``s + silu (1 - s)`` and autodiff's form differ in the float32 bits that are left (readings of 1e-7 to 8e-6 apart by 4-7%)
    assert _one_bfloat16_apart(got, want.astype(jnp.bfloat16)) and _one_bfloat16_apart(got_d, want_d.astype(jnp.bfloat16), floor=1e-6)


def test_the_gate_kernels_row_tile_at_the_cells_shapes():
    """The experts' blocks are what they were (``_TM`` rows of bfloat16);
    the dense layer's float32 ``[16384, 2 x 6144]`` takes 128 rows, the
    shared experts' ``[16384, 2 x 1024]`` and ``[.., 2 x 1536]`` 512."""
    from fishnet_tpu.ops.expert_gate import _row_tile

    assert [_row_tile(slots, row) for slots, row in ((262_144, 2048 * 2), (131_072, 2048 * 2), (98_304, 1536 * 2), (49_152, 1920 * 2))] == [512] * 4
    assert [_row_tile(16_384, 2 * width * 4) for width in (1024, 1536, 6144)] == [512, 512, 128]
    assert _row_tile(16_384, 2 * 6144 * 4 + 6144 * 2) == 128  # the gradient's: ``gu`` and the bfloat16 cotangent


def _plain_gated_ffn(n, gate_w, up_w, down_w):
    """What ``_gated_ffn`` was until PR 42: three ``_matmul``s round ``silu x up``, the gradient autodiff's."""
    return trunk._matmul(jax.nn.silu(trunk._matmul(n, gate_w)) * trunk._matmul(n, up_w), down_w)


def _gated_ffn_case(tokens, hidden, width):
    rng = np.random.default_rng(tokens + width)
    n = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    weights = [jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), jnp.float32) for shape in ((hidden, width), (hidden, width), (width, hidden))]
    return n, weights, jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)


#: tokens, hidden, width: the tiny nets' dense layer and shared expert, and a width whose ``gu`` takes more than one grid step.
GATED_FFN_CASES = [(512, 64, 96), (512, 64, 32), (1024, 128, 384)]


@pytest.mark.parametrize("tokens,hidden,width", GATED_FFN_CASES)
@pytest.mark.parametrize("form", ["kernels", "fused"])
def test_the_gated_feed_forward_and_its_four_gradients_match_the_plain_formula(monkeypatch, form, tokens, hidden, width):
    """``_gated_ffn`` on either side of its size rule (the gate's float32
    weight ``[hidden, width]`` against ``_FUSED_GATE_BYTES``: over it
    one joined product, the kernel pair and one gradient rule round the
    whole; up to it XLA's fusion of the plain formula) against the
    three plain products and autodiff, to the tolerance the experts'
    joined product is held to: the result, the gradient to the normed
    tokens and to each of the three weights."""
    n, (gate_w, up_w, down_w), cot = _gated_ffn_case(tokens, hidden, width)
    weight = hidden * width * 4
    monkeypatch.setattr(trunk, "_FUSED_GATE_BYTES", weight - 1 if form == "kernels" else weight)
    calls = []
    monkeypatch.setattr(trunk, "expert_gate", lambda *a, kernel=trunk.expert_gate: calls.append(a[0].shape) or kernel(*a))
    params = {"dense_gate": gate_w, "dense_up": up_w, "dense_down": down_w}
    got, pull = jax.vjp(lambda n, p: trunk._gated_ffn(n, p, "dense"), n, params)
    got_dn, got_dp = pull(cot)
    assert calls == ([(tokens, 2 * width)] if form == "kernels" else [])
    want, plain_pull = jax.vjp(_plain_gated_ffn, n, gate_w, up_w, down_w)
    want_dn, *want_dw = plain_pull(cot)
    assert got.shape == want.shape and got.dtype == got_dn.dtype == jnp.float32
    assert rel(got, want) < 6e-3 and rel(got_dn, want_dn) < 1e-2, (rel(got, want), rel(got_dn, want_dn))
    for name, w in zip(("dense_gate", "dense_up", "dense_down"), want_dw):
        assert got_dp[name].shape == w.shape and got_dp[name].dtype == jnp.float32 and rel(got_dp[name], w) < 1e-2, (name, rel(got_dp[name], w))


def test_the_size_rule_leaves_the_shared_experts_fused_and_takes_the_dense_layer():
    """At the two share cells' widths, hidden 2,048: the shared experts'
    gate weights are 8 and 12 MiB of float32 (XLA's fusion read 0.30 and
    0.42 ms a layer faster there on the chip), the dense layer's 48 MiB
    (the kernel form 10-16 ms faster); every tiny net of the tests is
    under the rule, and no count of tokens moves a net across it."""
    weight = lambda hidden, width: hidden * width * 4
    assert weight(2048, 1024) <= trunk._FUSED_GATE_BYTES and weight(2048, 1536) <= trunk._FUSED_GATE_BYTES < weight(2048, 6144)
    assert max(weight(cfg.hidden, max(cfg.dense_width, cfg.shared_width)) for cfg in (AFMOE, MLA)) <= trunk._FUSED_GATE_BYTES


@pytest.mark.parametrize("swapped", [False, True], ids=["as_joined", "halves_swapped"])
def test_the_gate_and_up_gradients_are_the_two_halves_of_the_joined_products(monkeypatch, swapped):
    """``d [W_g | W_u] = n^T d_gu`` is one product; ``dense_gate`` gets its
    first ``width`` columns and ``dense_up`` the rest. With the weights
    joined the other way round (``[W_u | W_g]``: the kernels then gate
    with the up product) the forward result and both gradients are another
    function's, and the comparison that holds the program shows it."""
    n, (gate_w, up_w, down_w), cot = _gated_ffn_case(512, 64, 96)
    if swapped:
        monkeypatch.setattr(trunk, "_joined", lambda gate, up, joined=trunk._joined: joined(up, gate))
    _, pull = jax.vjp(trunk._gated_products, n, gate_w, up_w, down_w)
    _, got_gate, got_up, _ = pull(cot)
    _, plain_pull = jax.vjp(_plain_gated_ffn, n, gate_w, up_w, down_w)
    _, want_gate, want_up, _ = plain_pull(cot)
    assert rel(want_up, want_gate) > 0.5  # the two are different tensors: a swap cannot hide
    if swapped:
        assert rel(got_gate, want_gate) > 0.5 and rel(got_up, want_up) > 0.5
    else:
        assert rel(got_gate, want_gate) < 1e-2 and rel(got_up, want_up) < 1e-2 and rel(got_up, want_gate) > 0.5


#: the held experts' sizes (3 of them; the slots past their sum are other experts'), 1,024 slots in two tiles of 512:
#: no held row, one, an extent that straddles the tiles, every slot held.
FFN_CASES = {"none": [0, 0, 0], "one": [0, 1, 0], "straddles": [300, 0, 400], "all": [500, 24, 500]}


@pytest.mark.parametrize("sizes", FFN_CASES.values(), ids=FFN_CASES)
def test_a_shares_experts_against_a_loop_over_the_held_experts(sizes):
    """``_expert_ffn`` with the held groups' sizes and the extent, on
    sorted rows whose tail is NaN (what ``rows_out`` leaves there under
    the interpreter) and with a cotangent whose tail is NaN: the value on
    the held rows, the gradient to the held rows and the gradients to the
    three weights are those of a loop over the held experts, all finite.
    Nothing past the extent reaches anything that is read."""
    rng = np.random.default_rng(sum(sizes))
    slots, hidden, width, extent = 1024, 64, 32, sum(sizes)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    held_rows = jnp.arange(slots)[:, None] < extent
    rows = jnp.where(held_rows, jnp.asarray(rng.standard_normal((slots, hidden)), jnp.bfloat16), jnp.nan)
    cot = jnp.where(held_rows, jnp.asarray(rng.standard_normal((slots, hidden)), jnp.bfloat16), jnp.nan)
    weights = [jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[1]), jnp.float32) for shape in ((3, hidden, width), (3, hidden, width), (3, width, hidden))]
    rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)

    def loop(x, gate_w, up_w, down_w):  # the held rows alone, each product's result rounded as the kernels round theirs
        parts = []
        for e in range(3):
            own = rounded(x[starts[e]:starts[e + 1]])
            hidden_rows = rounded(jax.nn.silu(rounded(own @ rounded(gate_w[e]))) * rounded(own @ rounded(up_w[e])))
            parts.append(rounded(hidden_rows @ rounded(down_w[e])))
        return jnp.concatenate(parts)

    ffn = lambda x, *w: trunk._expert_ffn(x, *w, jnp.asarray(sizes, jnp.int32), jnp.asarray(extent, jnp.int32))
    got, pull = jax.vjp(ffn, rows, *weights)
    got_dx, *got_dw = pull(cot)
    want, plain_pull = jax.vjp(loop, rows[:extent].astype(jnp.float32), *weights)
    want_dx, *want_dw = plain_pull(cot[:extent].astype(jnp.float32))
    got, got_dx = np.asarray(got, np.float32)[:extent], np.asarray(got_dx, np.float32)[:extent]
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(got_dx)) and all(np.all(np.isfinite(d)) for d in got_dw)
    if extent == 0:
        assert not any(np.any(np.asarray(d)) for d in got_dw)  # no held row: no gradient, and not a NaN
        return
    assert rel(got, want) < 6e-3 and rel(got_dx, want_dx) < 1e-2, (rel(got, want), rel(got_dx, want_dx))
    for name, d, w in zip(("gate", "up", "down"), got_dw, want_dw):
        assert d.shape == w.shape and d.dtype == jnp.float32 and rel(d, w) < 1e-2, (name, rel(d, w))
        assert not np.any(np.asarray(d)[np.asarray(sizes) == 0])  # a held expert with no rows has no gradient


#: tokens, top-k, hidden, which experts the slots go to, and for a share
#: (first held expert, held slots): the extent of its moves. Slots =
#: tokens x k against the row tile of 512: 64, 192 and 640 are not
#: multiples of it (tiles of 64, 64 and 128), 1024 and 1536 are. The
#: shares' extents: none, one row, one under, on and one over a block's
#: edge, all rows; one share whose experts are not the first; and what the
#: sums over a token's held slots meet (192 tokens are three tiles of 64,
#: 256 two of 128, whose 768 slots at top-6 fill no whole index block):
#: a whole tile of tokens with no held slot, a token with all its slots
#: held.
MOVE_CASES = [
    (64, 1, 64, "random", None),
    (512, 2, 64, "one_expert", None),
    (96, 2, 256, "some_empty", None),
    (128, 8, 256, "random", None),
    (320, 2, 256, "one_expert", None),
    (192, 8, 64, "some_empty", None),
    (24, 8, 2048, "random", None),
    (64, 1, 2048, "some_empty", None),
    (192, 8, 64, "share", (0, 0)),
    (192, 8, 64, "share", (0, 1)),
    (192, 8, 64, "share", (0, 511)),
    (192, 8, 64, "share", (0, 513)),
    (192, 8, 64, "share", (0, 1536)),
    (192, 8, 64, "share", (5, 700)),
    (96, 2, 256, "share", (2, 70)),
    (192, 8, 64, "share", (0, 512)),
    (192, 8, 64, "share_but_one_tile", (0, 300)),
    (192, 8, 64, "share_with_a_whole_token", (3, 40)),
    (256, 6, 256, "share_but_one_tile", (0, 90)),
]
MOVE_IDS = [f"n{n}-k{k}-h{h}-{routing}" + (f"-first{share[0]}-held{share[1]}" if share else "") for n, k, h, routing, share in MOVE_CASES]
HELD_COUNT = 3  # of the 8 experts of a share's move cases


def _sorted_order(rng, tokens: int, top_k: int, routing: str, share=None, weight=None):
    """``order`` as ``_experts`` makes it: the stable sort of each slot's
    expert; for a share, of ``(expert - first) mod 8``, with ``Held``:
    the count of slots on its ``HELD_COUNT`` experts, the slots' mask and
    ``weight`` [tokens, top_k] in sorted order."""
    slots = tokens * top_k
    if share is None:
        experts = {"random": rng.integers(0, 8, slots), "one_expert": np.full(slots, 3),
                   "some_empty": rng.choice([1, 4, 6], slots)}[routing]
        return jnp.argsort(jnp.asarray(experts, jnp.int32), stable=True), None
    first, held_slots = share
    held = (first + rng.integers(0, HELD_COUNT, slots)) % 8
    absent = (first + rng.integers(HELD_COUNT, 8, slots)) % 8
    rank = rng.permutation(slots).reshape(tokens, top_k)  # the held slots are those of the lowest ranks
    tile = np.gcd(tokens, 128)  # the tokens a grid step of ``rows_sum``
    if routing == "share_but_one_tile":  # the second tile of tokens holds nothing
        rank[tile:2 * tile] = slots
        rank = np.argsort(np.argsort(rank.reshape(slots), kind="stable"), kind="stable")
    if routing == "share_with_a_whole_token":
        rank[5] = -1
        rank = np.argsort(np.argsort(rank.reshape(slots), kind="stable"), kind="stable")
    experts = np.where(rank.reshape(slots) < held_slots, held, absent)
    group = jnp.asarray((experts - first) % 8, jnp.int32)
    mask = (group < HELD_COUNT).reshape(tokens, top_k)
    assert int(mask.sum()) == held_slots
    assert routing != "share_but_one_tile" or (tokens >= 2 * tile and not mask[tile:2 * tile].any())
    assert routing != "share_with_a_whole_token" or mask[5].all()
    order = jnp.argsort(group, stable=True)
    scale = jnp.zeros(slots) if weight is None else weight.reshape(slots)[order]
    return order, trunk._held(jnp.asarray(held_slots, jnp.int32), mask, scale)


def _small_integers(rng, shape):
    """Values whose sums of eight are exact in bfloat16, so that the
    order of a sum cannot show."""
    return jnp.asarray(rng.integers(-8, 9, shape), jnp.float32)


def _poisoned(x, held):
    """``x`` [slots, hidden] with NaN in every row past a share's extent:
    what a sorted buffer may hold there."""
    return x if held is None else jnp.where(jnp.arange(x.shape[0])[:, None] < held.extent, x, jnp.nan)


def _covered(slots: int, held, most: int = 512) -> int:
    """The rows a move covers: whole blocks of the row tile."""
    tile = np.gcd(slots, most)
    return slots if held is None else -(-int(held.extent) // tile) * tile


@pytest.mark.parametrize("tokens,top_k,hidden,routing,share", MOVE_CASES, ids=MOVE_IDS)
def test_dispatch_is_plain_indexing_and_its_gradient(tokens, top_k, hidden, routing, share):
    """``rows_out`` without a scale, and ``rows_back`` plus the sum over a
    token's slots as its gradient, against ``x[index]`` and the
    scatter-add that is its autodiff. A share's moves stop at its extent:
    rows past the last moved block stay what the buffer held (NaN under
    the interpreter), and the gradient reads no cotangent past the extent
    (they are NaN here)."""
    rng = np.random.default_rng(tokens + top_k + hidden)
    slots = tokens * top_k
    order, held = _sorted_order(rng, tokens, top_k, routing, share)
    extent = slots if held is None else int(held.extent)
    x = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.bfloat16)
    cot = _small_integers(rng, (slots, hidden))
    plain = lambda x: x[order // top_k]
    got = jax.jit(lambda x: trunk._dispatch(x, order, held))(x)
    assert got.dtype == jnp.bfloat16 and got.shape == (slots, hidden)
    assert np.array_equal(np.asarray(got, np.float32)[:extent], np.asarray(plain(x), np.float32)[:extent])  # bit for bit
    assert np.all(np.isnan(np.asarray(got, np.float32)[max(_covered(slots, held), np.gcd(slots, 512)):]))  # never written (one block always is)
    move = lambda x: trunk._dispatch(x.astype(jnp.bfloat16), order, held).astype(jnp.float32)
    got_dx = jax.jit(lambda x, c: jax.vjp(move, x)[1](c)[0])(x.astype(jnp.float32), _poisoned(cot, held))
    want_dx = jax.vjp(lambda x: plain(x.astype(jnp.bfloat16)).astype(jnp.float32), x.astype(jnp.float32))[1](
        jnp.where(jnp.arange(slots)[:, None] < extent, cot, 0.0))[0]
    assert np.array_equal(np.asarray(got_dx), np.asarray(want_dx))


@pytest.mark.parametrize("tokens,top_k,hidden,routing,share", MOVE_CASES, ids=MOVE_IDS)
def test_combine_is_plain_indexing_a_weighted_sum_and_their_gradient(tokens, top_k, hidden, routing, share):
    """``rows_back`` and the float32 weighted sum, with ``rows_out`` under
    the per-slot scale as the gradient to the rows, against ``jnp.take``
    by the inverse permutation, the same sum, and their autodiff. A share
    reads no row past its extent (they are NaN here), sums a token's held
    slots alone, and leaves the rows' gradient past the last moved block
    unwritten."""
    rng = np.random.default_rng(tokens * top_k + hidden)
    slots = tokens * top_k
    weight = jnp.asarray(rng.random((tokens, top_k)) + 0.1, jnp.float32)
    order, held = _sorted_order(rng, tokens, top_k, routing, share, weight)
    extent = slots if held is None else int(held.extent)
    inverse = jnp.argsort(order)
    out = jnp.asarray(rng.standard_normal((slots, hidden)), jnp.bfloat16)
    cot = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)

    def plain(out, weight):
        out = jnp.where(jnp.arange(slots)[:, None] < extent, out, 0)  # an absent expert's rows add nothing
        per_slot = jnp.take(out, inverse, axis=0).reshape(tokens, top_k, hidden)
        return jnp.einsum("nk,nkh->nh", weight, per_slot.astype(jnp.float32))

    combine = lambda o, w: trunk._combine(_poisoned(o, held), w, order, held)
    got = jax.jit(combine)(out, weight)
    assert got.dtype == jnp.float32 and np.allclose(got, plain(out, weight), rtol=1e-6, atol=1e-6)
    if top_k == 1:  # one slot a token: the move alone, bit for bit
        assert np.array_equal(np.asarray(got), np.asarray(out[inverse].astype(jnp.float32) * weight))
    loss = lambda f: lambda o, w: jnp.sum(f(o.astype(jnp.bfloat16), w) * cot)
    got_do, got_dw = jax.jit(jax.grad(loss(combine), (0, 1)))(out.astype(jnp.float32), weight)
    want_do, want_dw = jax.grad(loss(plain), (0, 1))(out.astype(jnp.float32), weight)
    assert np.array_equal(np.asarray(got_do)[:extent], np.asarray(want_do)[:extent])  # a permutation: one term a row, rounded once after the scale
    assert np.all(np.isfinite(got_dw)) and np.allclose(got_dw, want_dw, rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(want_dw))))  # float32 sums in another order
    if held is not None:
        assert not np.any(np.asarray(got_dw)[~np.asarray(held.mask)])  # an absent slot's weight has no gradient


@pytest.mark.parametrize("tokens,top_k,hidden,routing,share", [case for case in MOVE_CASES if case[4] in ((0, 1), (0, 513), (5, 700), (0, 90))],
                         ids=lambda value: str(value).replace(" ", ""))
def test_the_sums_loop_bodies_of_eight_rows_select_their_padding_away(monkeypatch, tokens, top_k, hidden, routing, share):
    """On the TPU ``rows_sum`` runs its loops eight rows to a body, and
    the rows that fill a tile's last body are its first held row again,
    selected away; the interpreter runs one row to a body and never meets
    them. Here it is made to: both sums of a share (the combine's under
    the weights, the dispatch's gradient without) at eight rows a body,
    at counts a tile of 1, 7 and under, and no multiple of eight, against
    the masked sums; NaN everywhere a share may not read."""
    from fishnet_tpu.ops import row_move

    monkeypatch.setattr(row_move, "_unroll", lambda interpret, most=1: most)
    rng = np.random.default_rng(tokens + hidden)
    slots = tokens * top_k
    weight = jnp.asarray(rng.random((tokens, top_k)) + 0.1, jnp.float32)
    order, held = _sorted_order(rng, tokens, top_k, routing, share, weight)
    assert np.any(np.asarray(held.counts) % 8)
    rows = _poisoned(jnp.asarray(rng.integers(-8, 9, (slots, hidden)), jnp.bfloat16), held)
    per_slot = jnp.where(held.mask[:, :, None], jnp.take(rows, jnp.argsort(order), axis=0).reshape(tokens, top_k, hidden), 0).astype(jnp.float32)
    mixed = trunk._held_slots_sum(rows, order, held, weight, jnp.float32)
    assert np.allclose(mixed, jnp.einsum("nk,nkh->nh", weight, per_slot), rtol=1e-6, atol=1e-6)
    summed = trunk._held_slots_sum(rows, order, held, None, jnp.bfloat16)
    assert summed.dtype == jnp.bfloat16 and np.array_equal(np.asarray(summed, np.float32), np.asarray(per_slot.sum(axis=1)))  # small integers: exact


@pytest.mark.parametrize("top_k,score,norm,scale", [(8, "sigmoid", True, 2.826), (6, "sigmoid", True, 2.448), (2, "softmax", False, 1.0)],
                         ids=["afmoe", "deepseek_v3", "softmax"])
def test_the_routers_chosen_scores_are_take_along_axis_bit_for_bit(top_k, score, norm, scale):
    """With an ``expert_bias`` the choice is on ``score + bias`` and the
    weights are the chosen experts' scores: a one-hot select and a sum
    over the experts, where it was ``take_along_axis`` and its scatter
    gradient. A token's experts are distinct, so every sum is one score
    and zeros: weights and both gradients equal the gather's bit for bit,
    also where ``score + bias`` ties (expert 9 is expert 3 again, and the
    inputs are coarse: whole tokens repeat). Operation by operation: a
    compiler that fuses the sigmoid into the select may round it another
    way than into the gather, and that is its own business."""
    rng = np.random.default_rng(top_k)
    tokens, hidden, experts = 96, 32, 16
    cfg = trunk.TrunkConfig(hidden=hidden, heads=2, head_dim=16, experts=experts, experts_per_token=top_k, router_score=score,
                            route_norm=norm, route_scale=scale)
    n2 = jnp.asarray(rng.integers(-2, 3, (tokens, hidden)), jnp.float32)
    n2 = n2.at[48:].set(n2[:48])
    router_w = jnp.asarray(rng.integers(-4, 5, (hidden, experts)) / 16, jnp.float32)
    router_w = router_w.at[:, 9].set(router_w[:, 3])
    bias = jnp.asarray(rng.integers(-1, 2, experts) / 8, jnp.float32).at[9].set(0.0).at[3].set(0.0)
    cot = jnp.asarray(rng.standard_normal((tokens, top_k)), jnp.float32)

    def gathered(n2, router_w):
        logits = jnp.dot(n2, router_w, precision=jax.lax.Precision.HIGHEST)
        chosen_from = jax.nn.softmax(logits, axis=-1) if score == "softmax" else jax.nn.sigmoid(logits)
        _, expert = jax.lax.top_k(chosen_from + bias, top_k)
        weight = jnp.take_along_axis(chosen_from, expert, axis=-1)
        if norm:
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
        return expert, weight * scale if scale != 1.0 else weight

    route = lambda n2, router_w: trunk._route(n2, {"router_w": router_w, "expert_bias": bias}, cfg)[:2]
    (expert, weight), (want_expert, want_weight) = route(n2, router_w), gathered(n2, router_w)
    assert np.array_equal(expert, want_expert) and np.array_equal(np.asarray(weight), np.asarray(want_weight))
    tied = np.asarray(expert == 3).any(axis=1) & np.asarray(expert == 9).any(axis=1)
    assert tied.any() and np.array_equal(np.asarray(expert[:48]), np.asarray(expert[48:]))  # the ties are met
    got = jax.grad(lambda *a: jnp.sum(route(*a)[1] * cot), (0, 1))(n2, router_w)
    want = jax.grad(lambda *a: jnp.sum(gathered(*a)[1] * cot), (0, 1))(n2, router_w)
    for g, w in zip(got, want):
        assert np.any(np.asarray(w)) and np.array_equal(np.asarray(g), np.asarray(w))


def test_trainer_overfits_a_small_batch():
    trainer = AzTrainer(TINY, learning_rate=3e-3)
    state, batch = trainer.init(0), batch_of(5)
    losses = []
    for _ in range(30):
        state, metrics = trainer.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < 0.5 * losses[0], (losses[0], losses[-1])
    assert {"expert_load_max", "expert_load_min", "router_entropy"} <= set(metrics)
    assert float(metrics["expert_load_max"]) >= BATCH * 64 * 2 / 8 >= float(metrics["expert_load_min"])


def test_config_round_trips_a_trunk_checkpoint(tmp_path):
    cfg = TrunkConfig(hidden=32, heads=2, head_dim=16, layers=1, experts=4, experts_per_token=3, expert_width=16,
                      rope_theta=1234.5, rms_eps=1e-6, value_hidden=8)
    trainer = AzTrainer(cfg)
    trainer.export(trainer.init(0), str(tmp_path / "trunk.npz"))
    loaded = dict(np.load(tmp_path / "trunk.npz"))
    assert az_config_from_params(loaded) == cfg
    assert az_config_from_params(az_checkpoint(init_az_params(jax.random.PRNGKey(0), TINY), TINY)) == TINY
    # the loaded dict, hyperparameters and all, is what the served forward takes
    logits, value = jax.jit(lambda p, x: az_forward(p, x, cfg))(loaded, jnp.zeros((1, 8, 8, 19)))
    assert logits.shape == (1, 4672) and value.shape == (1,)
    with pytest.raises(ValueError, match="trunk_hparams"):  # a trunk's tensors without its hyperparameters
        az_config_from_params({k: v for k, v in loaded.items() if k != "trunk_hparams"})
    with pytest.raises(ValueError, match="mismatched"):
        az_config_from_params({**loaded, "wq": loaded["wq"][:, :, :16]})
    with pytest.raises(ValueError, match="not an AZ checkpoint"):
        az_config_from_params({"ft_w": np.zeros((4, 4))})


def test_the_rpc_hosts_az_backend_serves_a_trunk():
    """rpc/host.py builds its forward from ``az_forward(p, x, cfg.az)``: a
    ``TrunkConfig`` there serves the trunk, uint8 planes in, float16 logits out."""
    from fishnet_tpu.rpc.host import _HostAzBackend
    from fishnet_tpu.search.mcts import MctsConfig

    params = init_az_params(jax.random.PRNGKey(0), TINY)
    backend = _HostAzBackend(params, MctsConfig(batch_capacity=16, az=TINY))
    planes = (np.random.default_rng(0).random((5, 8, 8, 19)) < 0.2).astype(np.uint8)
    logits16, values = backend._run([planes[:2], planes[2:]])
    assert logits16.dtype == np.float16 and logits16.shape[1] == 4672 and values.dtype == np.float32
    decoded = jnp.asarray(planes, jnp.float32).at[..., 17].multiply(1.0 / 100.0)  # the wire's halfmove plane rides x100
    want_logits, want_values = jax.jit(lambda p, x: az_forward(p, x, TINY))(params, decoded)
    # rows are padded to the host's bucket; a position's own rows do not depend on the others
    assert np.allclose(logits16[:5], np.asarray(want_logits, np.float16), atol=2e-3)
    assert np.allclose(values[:5], want_values, atol=2e-3)

"""The fifth block of the square-token trunk (models/trunk.py with a
``TrunkConfig.cca``: ZAYA1-8B's zaya block) at a tiny size on the CPU that
keeps the published ratios (queries in hidden / 2 columns, keys and values
in hidden / 8, 4 query heads a key-value head, half a head rotated, 16
experts, one a token, 8 of them held): the program against the
benchmark's own plain reference (loss and every gradient), wrong layers
against the tolerance, the 2 shares of 8 experts against the uncut
reference's layer, the sum over a token's ONE slot, what ``TrunkConfig``
refuses, the checkpoint and the three counters. The four accepted blocks'
lowered step programs are held to their parent's, op for op, by
``test_hybrid_trunk.py::test_an_accepted_blocks_lowered_step_is_the_parents_op_for_op``
(all four pass on this tree unedited: nothing they lower changed)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import cca_trunk as cca_family
from benchmark.reference import cca_trunk as cca_reference
from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_checkpoint, az_config_from_params, az_forward, init_az_buffers, init_az_params
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer
from tools.step_text import HOW_TO_SEE_WHAT_MOVED, lowered_step_text
from trunk_tiny import AFMOE, BATCH, CANCELLING, CCA, GRAD_CANCELLING_TOL, MLA, TINY, _all, board_batch, rel  # noqa: E402

CCA_MODEL = {"hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8, "cca_time0": 2,
             "cca_time1": 2, "rotary_dim": 4, "rope_theta": 5000000, "moe_intermediate_size": 32, "num_experts": 8, "num_routed_experts": 16,
             "first_held_expert": 4, "num_experts_per_tok": 1, "router_hidden_size": 32, "load_balance_coeff": 0.001, "rms_norm_eps": 1e-05,
             "input_planes": 19, "value_hidden": 32, "policy_planes": 73}
CCA_CONFIG = {"model": CCA_MODEL, "train": {"value_weight": 1.0}}


def cca_params(seed: int, model=CCA_MODEL):
    return {k: jnp.asarray(v) for k, v in cca_reference.init_params(seed, model).items()}


@pytest.fixture(scope="module")
def cca_program():
    return cca_family.loss_and_grads(AzTrainer(CCA))


# Readings over seeds 1-3 (CPU): the loss within 0.0001 of the reference's; all gradients as one vector 0.004-0.006; single
# tensors 0.003-0.011 but the cancelling heads' (policy_b 0.15, value_b 0.09). The wrong layers below read 0.08 and more on
# the tensors they name. ``temp`` is two numbers a layer, each a sum of signed terms over a head's scores: 0.003-0.05.
CCA_GRAD_ALL_TOL, CCA_TENSOR_TOL = 0.02, 0.04


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_cca_loss_and_every_gradient_match_the_benchmarks_reference(cca_program, seed):
    params, batch = cca_params(seed), board_batch(seed)
    loss, got = cca_program(params, batch)
    want_loss, want = jax.value_and_grad(cca_reference.loss)(params, batch, CCA_CONFIG)
    assert not np.any(np.asarray(want.pop("expert_bias"))) and not np.any(np.asarray(got.pop("expert_bias")))
    assert set(got) == set(want) == set(trunk.trunk_param_shapes(CCA))
    assert {"wv1", "wv2", "conv0_w", "conv1_w", "temp", "router_down", "router_w3"} < set(want) and not {"wv", "q_norm", "k_norm", "router_w"} & set(want)
    print("cca", seed, abs(float(loss) - float(want_loss)) / float(want_loss), _all(got, want), {k: round(rel(got[k], want[k]), 4) for k in want})
    assert abs(float(loss) - float(want_loss)) < 0.001 * float(want_loss)
    assert _all(got, want) < CCA_GRAD_ALL_TOL
    for name in want:
        assert got[name].shape == want[name].shape and float(jnp.linalg.norm(want[name])) > 0, name
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in (*CANCELLING, "temp") else CCA_TENSOR_TOL), name  # temp: 4 numbers


def wrong_cca(setattr, wrong: str) -> None:
    """Break the program's fifth block through ``setattr(object, name,
    value)``: the mutations ISSUE 43 names (the chip's sweep applies the
    same at width)."""
    mix, core = trunk.cca_mix, trunk.board_attention
    if wrong == "conv1_taps_exchanged":
        setattr(trunk, "cca_mix", lambda x, w0, b0, w1, b1, *rest: mix(x, w0, b0, w1[:, ::-1], b1, *rest))
    elif wrong == "no_value_shift":  # v2 from the token itself

        def unshifted(v12, kv_heads):
            boards, _, width = v12.shape
            halves = [y.reshape(boards, trunk.SQUARES, kv_heads, width // (2 * kv_heads)) for y in (v12[..., :width // 2], v12[..., width // 2:])]
            return jnp.concatenate(halves, axis=-1).reshape(boards, trunk.SQUARES, width).astype(jnp.bfloat16)

        setattr(trunk, "_shifted_values", unshifted)
    elif wrong == "no_qk_mean":

        def without_mean(x, w0, b0, w1, b1, heads, kv_heads, *rest):
            q, k, sums = mix(x, w0, b0, w1, b1, heads, kv_heads, *rest)
            by_head = x.reshape(*x.shape[:2], heads + kv_heads, -1)
            xq, xk = by_head[:, :, :heads], by_head[:, :, heads:]
            m_q = (xq + jnp.repeat(xk, heads // kv_heads, axis=2)) / 2
            m_k = (xq.reshape(*x.shape[:2], kv_heads, heads // kv_heads, -1).mean(axis=3) + xk) / 2
            return q - m_q.reshape(q.shape), k - m_k.reshape(k.shape), sums

        setattr(trunk, "cca_mix", without_mean)
    elif wrong == "rope_on_every_column":
        setattr(trunk, "board_attention", lambda *args, rotary_dim=None, **kw: core(*args, **kw))
    elif wrong == "temp_x1.5":
        setattr(trunk, "board_attention", lambda q, k, v, g_q, g_k, *rest, **kw: core(q, k, v, g_q, 1.5 * g_k, *rest, **kw))
    elif wrong == "router_gelu_as_relu":
        setattr(jax.nn, "gelu", lambda x, approximate=True: jax.nn.relu(x))
    else:
        raise ValueError(wrong)


#: The tensors of which each wrong layer has to move one's gradient past twice a single tensor's tolerance (read: 0.19-1.5).
#: All gradients as one vector show NONE of them (0.0034-0.0047 against a sound 0.0033-0.0071: the experts' and the heads'
#: tensors carry the vector), which is why the cell's ``correct`` names these tensors each with a limit of its own.
WRONG_CCA_SHOWS = {"conv1_taps_exchanged": ("conv1_w", "conv0_w"), "no_value_shift": ("wv2",), "no_qk_mean": ("wq", "wk", "conv1_w"),
                   "rope_on_every_column": ("wq", "wk"), "temp_x1.5": ("temp", "wk"), "router_gelu_as_relu": ("router_w3", "router_w2", "router_down")}
#: The value shift alone shows less: a square's normed stream is mostly what its board's squares share (the plane of ones, the
#: castling planes, the embedding's bias; over half the squares are empty), so ``n_{t-1}`` is close to ``n_t`` and ``wv2``'s
#: gradient reads 0.036 without the shift, 7x its sound 0.004-0.005 and under the others' threshold.
WRONG_CCA_THRESHOLD = {"no_value_shift": 0.02}


@pytest.mark.parametrize("wrong", WRONG_CCA_SHOWS)
def test_the_tolerance_catches_a_wrong_mix_core_or_router(monkeypatch, wrong):
    params, batch = cca_params(2), board_batch(2)
    want = jax.grad(cca_reference.loss)(params, batch, CCA_CONFIG)  # before the program is broken: the reference calls jax.nn.gelu too
    wrong_cca(monkeypatch.setattr, wrong)
    _, got = cca_family.loss_and_grads(AzTrainer(CCA))(params, batch)
    named = {name: round(rel(got[name], want[name]), 3) for name in WRONG_CCA_SHOWS[wrong]}
    print(wrong, _all(got, want), named)
    assert max(named.values()) > WRONG_CCA_THRESHOLD.get(wrong, 2 * CCA_TENSOR_TOL)


def test_the_two_shares_of_eight_experts_add_up_to_the_uncut_references_layer():
    """Published layer 0 with all 16 experts, as the benchmark's reference
    computes it uncut, against the program's pieces put together as the
    deployment's 2 chips would: attention (its mix and its value shift)
    ONCE, and the routed parts of 2 shares of 8 experts, each routing
    over all 16 through the MLP with top-1. A token's one expert lives
    on exactly one chip: a share alone leaves about half the tokens
    without a feed-forward."""
    model = {**CCA_MODEL, "num_hidden_layers": 1, "num_experts": 16, "first_held_expert": 0}
    whole = dataclasses.replace(CCA, layers=1, held_experts=None)
    params = cca_params(5, model)
    planes = board_batch(5, 4)["planes"]
    same = lambda x: x
    want = cca_reference.features(params, planes, model, same, same).reshape(256, 128)

    x = trunk._matmul(planes.reshape(256, 19), params["embed_w"]) + params["embed_b"]
    attention, routed = trunk.trunk_plan(whole)
    x = x + trunk._cca_attention(x, trunk.sublayer_params(params, attention), whole, attention)[0]  # every chip computes it alike: once
    layer = trunk.sublayer_params(params, routed)
    n2 = trunk._rms_norm(x, layer.pop("moe_norm"), whole.rms_eps)

    def share(first):
        cfg = dataclasses.replace(whole, held_experts=(first, 8))
        held = {k: (v[first:first + 8] if k.startswith("experts_") else v) for k, v in layer.items()}
        mixed, counters = jax.jit(lambda n, l: trunk._experts(n, l, cfg, "layer00"))(n2, held)
        return mixed, float(jnp.sum(counters["expert_slots"][first:first + 8])), float(counters["route_top1_weight"])

    parts = [share(first) for first in (0, 8)]
    final = lambda y: trunk._rms_norm(y, params["final_norm"], whole.rms_eps)
    total = final(x + sum(mixed for mixed, _, _ in parts))
    assert rel(total, want) < 0.02, rel(total, want)
    for mixed, slots, _ in parts:  # one share is not the layer: its absent half adds nothing
        assert rel(final(x + mixed), want) > 3 * rel(total, want) and 0 < slots < 256
        assert int(jnp.sum(jnp.any(mixed != 0, axis=-1))) == int(slots)  # a token gets its feed-forward from the chip that holds its expert, or nothing
    assert sum(slots for _, slots, _ in parts) == 256  # every token's one slot falls in exactly one share
    assert parts[0][2] == parts[1][2] and 1 / 16 < parts[0][2] < 1.0  # both chips route alike: the same mean chosen score
    uncut, _ = jax.jit(lambda n, l: trunk._experts(n, l, whole, "layer00"))(n2, layer)  # all 16 held, no offset: the same sum
    assert rel(uncut, sum(mixed for mixed, _, _ in parts)) < 0.01


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recomputed"])
def test_a_shares_experts_at_one_slot_a_token_against_a_loop_over_the_held_experts(recompute):
    """``_routed`` at top-1 on a share against plain indexing: the sum
    over a token's ONE slot is a select on the slot's mask (no
    ``moe_rows_sum``, no lists of places), what the view holds at an
    absent slot's place (NaN under the interpreter) is selected away, and
    the weight's gradient is the held rows' alone."""
    rng = np.random.default_rng(7)
    tokens, experts, count, hidden, width = 192, 8, 3, 64, 32
    n2 = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((count, hidden, width)) / np.sqrt(hidden), jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((count, width, hidden)) / np.sqrt(width), jnp.float32)
    expert = jnp.asarray(rng.integers(0, experts, (tokens, 1)), jnp.int32)
    weight = jnp.asarray(rng.uniform(0.2, 1.0, (tokens, 1)), jnp.float32)

    def program(n2, weight, gate, up, down):
        group = expert.reshape(-1)
        _, order, scale = jax.lax.sort((group, jnp.arange(tokens, dtype=jnp.int32), weight.reshape(-1)), num_keys=1, is_stable=True)
        sizes = jnp.sum(group[:, None] == jnp.arange(experts)[None, :], axis=0, dtype=jnp.int32)
        held = trunk._held(jnp.sum(sizes[:count]), expert < count, scale)
        assert held.places is None and held.counts is None  # no lists at one slot a token
        routed = trunk._routed_recomputed if recompute else trunk._routed
        return routed(n2, weight, order, sizes[:count], held, gate, up, down, "layer00")

    def plain(n2, weight, gate, up, down):
        bf = lambda y: y.astype(jnp.bfloat16).astype(jnp.float32)
        out = 0.0
        for e in range(count):
            own = jnp.where(expert == e, weight, 0.0)
            out = out + own * bf(bf(jax.nn.silu(bf(n2) @ bf(gate[e])) * (bf(n2) @ bf(up[e]))) @ bf(down[e]))
        return out

    got = jax.jit(program)(n2, weight, gate, up, down)
    assert got.shape == (tokens, hidden) and bool(jnp.all(jnp.isfinite(got)))
    assert not np.any(np.asarray(got)[np.asarray(expert[:, 0]) >= count])  # an absent expert's token gets exactly nothing
    value, grads = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(program(*a) ** 2), (0, 1, 2, 3, 4)))(n2, weight, gate, up, down)
    want, want_grads = jax.value_and_grad(lambda *a: jnp.sum(plain(*a) ** 2), (0, 1, 2, 3, 4))(n2, weight, gate, up, down)
    assert abs(float(value) - float(want)) < 0.02 * float(want)
    for name, g, w in zip(("n2", "weight", "gate", "up", "down"), grads, want_grads):
        assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g))) and rel(g, w) < 0.03, (name, rel(g, w))


#: what is asked for beside ``cca`` (or of the two fields that stand alone), and a part of the sentence that refuses it
REFUSED = {
    "no_kv_heads": (dict(kv_heads=None), "wants kv_heads"), "odd_head": (dict(head_dim=7, rotary_dim=None), "even head_dim"),
    "no_qk_norm": (dict(qk_norm=False), "without a gain under a key temperature"),
    "latent": (dict(kv_heads=None, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16, rotary_dim=None), "no latent"),
    "pattern": (dict(pattern="E*"), "no pattern"), "output_gate": (dict(gated_attention=True), "no output gate"),
    "post_norms": (dict(post_norms=True), "no post-norms"), "nope_layers": (dict(nope_layers=(1,)), "no nope_layers"),
    "no_taps": (dict(cca=(2, 0)), "not two kernel sizes"), "taps_past_a_board": (dict(cca=(2, 65)), "not two kernel sizes"),
    "one_size": (dict(cca=(2,)), "not two kernel sizes"), "odd_part": (dict(rotary_dim=5), "rotary_dim 5 is not an even part"),
    "part_past_a_head": (dict(rotary_dim=10), "rotary_dim 10 is not an even part"), "no_part": (dict(rotary_dim=0), "rotary_dim 0 is not an even part"),
    "negative_router": (dict(router_hidden=-1), "router_hidden -1 is under 0"),
}


@pytest.mark.parametrize("what", REFUSED)
def test_the_fifth_block_refuses_what_the_code_does_not_compute(what):
    asked, sentence = REFUSED[what]
    with pytest.raises(ValueError, match=sentence):
        dataclasses.replace(CCA, **asked)


def test_the_new_fields_leave_the_other_blocks_as_they_were_and_stand_alone():
    assert (TrunkConfig().cca, TrunkConfig().rotary_dim, TrunkConfig().router_hidden) == (None, None, 0)
    with pytest.raises(ValueError, match="beside a latent"):
        dataclasses.replace(MLA, rotary_dim=8)
    # each is told on its own: a partial rotation and an MLP router on the first block's attention and experts
    assert dataclasses.replace(TINY, rotary_dim=8, router_hidden=16).rotary_dim == 8
    shapes = trunk.trunk_param_shapes(dataclasses.replace(TINY, router_hidden=16))
    assert "router_w" not in shapes and shapes["router_down"] == (2, 64, 16) and shapes["router_w3"] == (2, 16, 8)
    shapes = trunk.trunk_param_shapes(CCA)
    assert (shapes["wq"], shapes["wk"], shapes["wv1"], shapes["wv2"], shapes["wo"]) == ((2, 128, 64), (2, 128, 16), (2, 128, 8), (2, 128, 8), (2, 64, 128))
    assert (shapes["conv0_w"], shapes["conv0_b"], shapes["conv1_w"], shapes["conv1_b"], shapes["temp"]) == (
        (2, 80, 2), (2, 80), (2, 10, 2, 8, 8), (2, 80), (2, 2))


def test_cca_checkpoint_round_trips_fresh_tensors_start_as_a_pass_and_the_counters_say_so(tmp_path):
    """A fresh state's mix passes its input (``cca_conv_share`` reads
    the rounding of conv1's bfloat16 operand), its temperature is 1, no tensor is mistaken for a bias or a
    matrix by its name; the ``.npz`` gives the configuration back from
    its shapes and ``rotary_dim``; the older blocks' files still load."""
    params = init_az_params(jax.random.PRNGKey(3), CCA)
    taps0, taps1 = np.asarray(params["conv0_w"]), np.asarray(params["conv1_w"])
    assert np.all(taps0[..., -1] == 1.0) and not np.any(taps0[..., :-1])
    assert np.all(taps1[:, :, -1] == np.eye(8)) and not np.any(taps1[:, :, :-1])
    assert np.all(np.asarray(params["temp"]) == 1.0) and np.all(np.asarray(params["attn_norm"]) == 1.0)
    for name in trunk._STACKED_BIASES:
        assert not np.any(np.asarray(params[name])), name
    for name in ("wq", "wk", "wv1", "wv2", "wo", "router_down", "experts_down"):
        assert 0.015 < float(np.asarray(params[name]).std()) < 0.025, name  # matrices, not biases: N(0, 0.02)
    for name in trunk._ROUTER_HIDDEN:  # by their fan-in, so that a fresh MLP's logits are spread as a one-product router's are
        assert 0.85 < float(np.asarray(params[name]).std()) * np.sqrt(32) < 1.15, name
    state = {**params, **init_az_buffers(CCA)}
    path = tmp_path / "cca.npz"
    np.savez(path, **az_checkpoint(state, CCA))
    with np.load(path) as data:
        loaded = dict(data)
    assert az_config_from_params(loaded) == CCA
    for older in (TINY, AFMOE, MLA):  # their files carry a rotary_dim of 0, and files written before it default to it
        older_file = az_checkpoint({**init_az_params(jax.random.PRNGKey(0), older), **init_az_buffers(older)}, older)
        assert az_config_from_params(older_file) == older
        assert az_config_from_params({**older_file, trunk.HPARAMS: older_file[trunk.HPARAMS][:-1]}) == older
    with pytest.raises(ValueError, match="without"):
        az_config_from_params({k: v for k, v in loaded.items() if k != "temp"})
    logits, value = az_forward(state, board_batch(1)["planes"], az_config_from_params(loaded))
    assert logits.shape == (BATCH, 4672) and np.all(np.isfinite(np.asarray(logits))) and np.all(np.abs(np.asarray(value)) <= 1)
    trainer = AzTrainer(dataclasses.replace(CCA, recompute_experts=True))
    state, metrics = trainer.step(trainer.init(1), board_batch(1))
    # the step's forward read the fresh tensors: the mix passes its input but for conv1's bfloat16 operand (2^-9 a number: 0.0017)
    fresh = float(metrics["cca_conv_share"])
    assert 0.001 < fresh < 0.003 and float(metrics["cca_temp_max"]) == 1.0
    assert 1 / 16 < float(metrics["route_top1_weight"]) < 0.5 and "held_slots" in metrics  # a fresh router's scores differ, and none leads far
    _, metrics = trainer.step(state, board_batch(2))
    print("fresh", fresh, "one update later", float(metrics["cca_conv_share"]), float(metrics["cca_temp_max"]))
    assert fresh < float(metrics["cca_conv_share"]) < 0.1 and 1.0 != float(metrics["cca_temp_max"]) < 1.01  # one update later both have moved


def test_the_counters_read_the_references_mix_temperature_and_chosen_score():
    params, batch = cca_params(4), board_batch(4)
    counters = jax.jit(lambda p, x: trunk.trunk_forward_counted(p, x, CCA)[2])(params, batch["planes"])
    assert abs(float(counters["cca_temp_max"]) - float(np.max(np.asarray(params["temp"])))) < 1e-6
    # the reference's conv taps are its own tap's 1 + 0.1 normal, the earlier tap's 0.3 normal and conv1's identity + 0.022 normal: a share of ~0.4
    assert 0.2 < float(counters["cca_conv_share"]) < 0.8
    # its router is peaked to a margin of a logit over a spread of 3: a mean chosen probability of a half and more, never 1
    assert 0.4 < float(counters["route_top1_weight"]) < 0.99
    slots = np.asarray(cca_reference.expert_slots(params, batch["planes"], CCA_MODEL))
    assert abs(float(counters["held_slots"]) - slots[:, 4:12].sum()) <= 2 and float(np.sum(counters["expert_slots"])) == 2 * BATCH * 64


#: sha256 of the fifth block's tiny lowered step program, as ``tests/test_hybrid_trunk.py PARENT_STEP_SHA256`` holds the four
#: older blocks': read on PR 44's parent (3160177) and on PR 44's tree with this jax, and the same on both (PR 44 changed the
#: fourth block's mixer, which no ``cca`` layer runs). A PR that means to change it reads its own parent the same way. PR 61 MEANT
#: to move it (the attention core's query heads two a product; this net runs a group of 4; PR 60 brought the same change, was measured by the driver and refused on one pair of runs of ``train_pos_per_s``, its tree thrown away; PR 61 asked again): read anew on
#: PR 61's tree, its parent 8be8117 read a835ede2...b41a; the ``tools/step_text.py --block cca --no-ids`` dumps differ inside the two kernels' calls
#: alone, 236 -> 208 ``stablehlo.dot_general``, 38 loops both: with the parent's two bodies (``tests/test_board_attention.py PARENT_BODIES``) and its 16 (board, head)s a step patched over the module, the text hashes to the parent's pin.
CCA_STEP_SHA256 = "001950aaae35b7f24da16745185cd5d6a642a6e93d943c6bb62009fc6ce4d585"


def test_the_fifth_blocks_lowered_step_is_the_parents_op_for_op():
    import hashlib

    text = lowered_step_text(CCA, board_batch(1))
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == CCA_STEP_SHA256, HOW_TO_SEE_WHAT_MOVED.format(block="cca")

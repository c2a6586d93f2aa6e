"""The seventh block of the square-token trunk (models/trunk.py with
``TrunkConfig.mixers`` of gdn and attention: Qwen3-Next's qwen3_next block,
three Gated DeltaNet layers to one gated attention layer with part of a
head rotated, a gated shared expert, zero-centred norms) at a tiny size on
the CPU, on a worker of its own: the program against the benchmark's plain
reference (the literal recurrence, the published column orders), three
optimizer steps under a weight decay that tells ``w`` from ``1 + w``, the
share tied to the model (all expert shares and the gated shared expert once
add up to the uncut reference's layer), its step pin, and the checkpoint
round trip."""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_config_from_params
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer
from trunk_tiny import BATCH, BLOCKS, CANCELLING, GDN, GDN_CONFIG, GDN_MODEL, GRAD_CANCELLING_TOL, GRAD_TENSOR_TOL, KDA, _all, batch_of, rel  # noqa: E402

# The plain reference is the benchmark's own (benchmark/reference/gdn_trunk.py: the published equations and column orders, the
# recurrence square by square, importing nothing of the program), at a tiny size; the program reads its parameters through
# benchmark/families/gdn_trunk.py (the permutations of ``W_qkvz`` and ``W_ba``, the gate's columns out of ``W_q``).

from benchmark.families import gdn_trunk as gdn_family  # noqa: E402
from benchmark.reference import gdn_trunk as gdn_reference  # noqa: E402
from tools.step_text import HOW_TO_SEE_WHAT_MOVED, lowered_step_text, without_ids  # noqa: E402


def gdn_params(seed: int, model=GDN_MODEL):
    return {k: jnp.asarray(v) for k, v in gdn_reference.init_params(seed, model).items()}


@pytest.fixture(scope="module")
def gdn_program():
    return gdn_family.loss_and_grads(AzTrainer(GDN))


# Readings over seeds 1-3 (CPU, a head of 32): all gradients as one vector 0.028-0.053, the worst single tensor 0.105
# (shared_token_gate, seed 3) but for the cancelling ones; the wrong layers below read 0.2 and more on the tensors that see them.
GDN_GRAD_ALL_TOL = 0.1
NEW_MATHEMATICS = ("gdn_A_log", "gdn_dt_bias", "gdn_ba", "gdn_conv", "gdn_o_norm")


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_gdn_loss_and_every_gradient_match_the_benchmarks_reference(gdn_program, seed):
    params, batch = gdn_params(seed), batch_of(seed)
    loss, got = gdn_program(params, batch)
    want_loss, want = jax.value_and_grad(gdn_reference.loss)(params, batch, GDN_CONFIG)
    assert not np.any(np.asarray(want.pop("expert_bias"))) and not np.any(np.asarray(got.pop("expert_bias")))
    # the published order has the attention's gate among ``wq``'s columns: one tensor there, two in the program
    assert set(got) == set(want) == set(trunk.trunk_param_shapes(GDN)) - {"wgate"} and got["wq"].shape == (1, 64, 2 * 4 * 16)
    assert set(trunk._OWNS["gdn"]) < set(want) and "shared_token_gate" in want
    print("gdn", seed, abs(float(loss) - float(want_loss)) / float(want_loss), _all(got, want), {k: round(rel(got[k], want[k]), 4) for k in want})
    assert abs(float(loss) - float(want_loss)) < 0.01 * float(want_loss)
    assert _all(got, want) < GDN_GRAD_ALL_TOL
    for name in want:
        assert got[name].shape == want[name].shape and float(jnp.linalg.norm(want[name])) > 0, name
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL), name
    assert max(rel(got[name], want[name]) for name in NEW_MATHEMATICS) < 0.15  # the tensors that alone see the new mathematics


#: A misreading of the block -> the tensors whose gradient has to read over 0.2 (all gradients as one vector need not: one attention layer of four).
MISREADINGS = {"rate_times_1.5": ("gdn_A_log", "gdn_dt_bias"), "key_head_mod": NEW_MATHEMATICS, "gate_sigmoid": NEW_MATHEMATICS,
               "gate_before_norm": NEW_MATHEMATICS, "no_token_gate": ("shared_token_gate", "shared_up"), "rope_all": ("wk",), "plain_gain": ("q_norm", "k_norm")}


@pytest.mark.parametrize("wrong", MISREADINGS)
def test_the_tolerance_catches_a_wrong_seventh_block(gdn_program, wrong):
    """A decay rate 1.5 times the published, value head h on key head ``h %
    K``, the head norm's gate as a sigmoid, the gate before the norm, the
    shared expert's token gate dropped, RoPE on all of a head, a q- and
    k-norm's gain read ``w`` for ``1 + w``: each reads over 0.2 on a tensor
    that sees it, where the sound program reads under 0.15 on all of them."""
    params, batch = gdn_params(1), batch_of(1)
    _, got = gdn_program(params, batch)
    want = jax.grad(gdn_reference.loss)(params, batch, {**GDN_CONFIG, "model": {**GDN_MODEL, "misread": wrong}})
    seen_by = MISREADINGS[wrong]
    print("gdn wrong", wrong, _all(got, want), {k: round(rel(got[k], want[k]), 3) for k in seen_by})
    assert max(rel(got[name], want[name]) for name in seen_by) > 0.2


def test_three_steps_where_decaying_w_and_decaying_the_gain_differ():
    """Three AdamW steps of the program from the reference's parameters
    against the reference's own, at a rate and a weight decay large enough
    that a zero-centred norm's ``w`` decayed toward 0 (the optimizer sees
    ``w``: both sides) and its gain ``1 + w`` decayed toward 0 (an optimizer
    that saw the gain) part: the program's losses follow the first and leave
    the second."""
    rate, decay = 3e-3, 10.0  # 3% of a tensor a step: over four steps a gain held as 1 + w would shrink by 11%, w by 11% of its 0.1
    config = {**GDN_CONFIG, "train": {"value_weight": 1.0, "learning_rate": rate, "weight_decay": decay}}
    params, batch = gdn_params(2), batch_of(2)
    trainer = AzTrainer(GDN, optimizer=optax.adamw(rate, weight_decay=decay))
    state, got = gdn_family.state_from_params(trainer, params), []
    for _ in range(4):
        state, metrics = trainer.step(state, batch)
        got.append(float(metrics["loss"]))
    grad = jax.jit(jax.value_and_grad(lambda p, b: gdn_reference.loss(p, b, config)))
    want = [float(x) for x in gdn_reference.train_losses(grad, params, batch, config, 4)]

    def gain_decayed(params):  # the same steps by an optimizer that holds the GAINS of the zero-centred norms: 1 + w decays toward 0
        gains = {k: (1.0 + v if k in trunk._ZERO_CENTERED else v) for k, v in params.items()}
        as_w = lambda g: {k: (v - 1.0 if k in trunk._ZERO_CENTERED else v) for k, v in g.items()}
        held = jax.jit(jax.value_and_grad(lambda g, b: gdn_reference.loss(as_w(g), b, config)))
        return [float(x) for x in gdn_reference.train_losses(held, gains, batch, config, 4)]

    other = gain_decayed(params)
    print("steps", got, want, other)
    drop = want[0] - want[-1]
    # read on the CPU: the program's fall 2.242, the reference's 2.232, the gain-holding optimizer's 1.958
    assert drop > 0.05 and abs((got[0] - got[-1]) - drop) < 0.02 * drop
    assert abs((other[0] - other[-1]) - drop) > 0.08 * drop  # the two optimizers part by four times the tolerance
    w = np.asarray(state.params["final_norm"])
    assert np.all(np.abs(w) < np.abs(np.asarray(params["final_norm"])) + 4 * rate)  # w stays near 0: nothing pulled it toward -1 or held it at 1


# -- the share tied to the model (guide section 4): two chips share a layer's experts ---------------------------------------------

UNCUT = {**GDN_MODEL, "mixers": ["gdn", "attention"], "num_hidden_layers": 2, "num_experts": 16, "first_held_expert": 0}


@pytest.mark.parametrize("left_out", [None, "an_expert_share", "shared_counted_twice", "token_gate_dropped"])
def test_the_expert_shares_and_the_gated_shared_expert_once_add_up_to_the_uncut_layers(left_out):
    """Two layers of the block UNCUT, as the benchmark's reference computes
    them (all 16 experts, every expert on every token): a GDN layer and a
    gated attention layer, each with its routed feed-forward. Against the
    program's pieces put together as two chips would: the mixers whole, the
    routed part as the sum of TWO expert shares (8 of 16 each, routing over
    all 16, weights renormalised over all three chosen, held or not), the
    shared expert under its token gate ONCE. Leaving a share out, counting
    the gated shared expert on both chips, or dropping its gate is seen."""
    params = gdn_params(5, UNCUT)
    planes = batch_of(5)["planes"]
    want = gdn_reference.features(params, planes, UNCUT, lambda y: y, lambda y: y).reshape(-1, GDN.hidden)

    share = dataclasses.replace(GDN, mixers=("gdn", "attention"), layers=2, held_experts=(0, 8))
    program = trunk.centred_gains(gdn_family.to_program(share, params), share)
    embedded = jnp.dot(planes.reshape(-1, 19), params["embed_w"], precision="highest") + params["embed_b"]  # float32: the embedding is no share's
    x = embedded
    for sublayer in trunk.trunk_plan(share):
        own = trunk.sublayer_params(program, sublayer)
        if sublayer.kind != "routed":
            x = x + trunk._KINDS[sublayer.kind][0](x, own, share, sublayer)[0]
            continue
        n2 = trunk._rms_norm(x, own["moe_norm"], share.rms_eps)
        gate = 1.0 if left_out == "token_gate_dropped" else jax.nn.sigmoid(jnp.sum(n2 * own["shared_token_gate"][:, 0], axis=-1, keepdims=True))
        out = gate * trunk._ffn(n2, own, "shared", True) * (2.0 if left_out == "shared_counted_twice" else 1.0)
        full = {name: params[name][int(sublayer.layer[-2:])] for name in ("experts_gate", "experts_up", "experts_down")}
        for first in (0, 8)[:1 if left_out == "an_expert_share" else 2]:
            held = {**own, **{name: full[name][first:first + 8] for name in full}}
            out = out + trunk._experts(n2, held, dataclasses.replace(share, held_experts=(first, 8)), sublayer.layer)[0]
        x = x + out
    got = trunk._rms_norm(x, program["final_norm"], share.rms_eps)
    # what the two layers ADDED to the stream, so that the embedding (sqrt(hidden) times the branches' scale) does not hide a share
    start = trunk._rms_norm(embedded, program["final_norm"], share.rms_eps)
    error = rel(got - start, want - start)
    print("shares", left_out, error)
    assert error < 0.05 if left_out is None else error > 0.15, (left_out, error)


# -- the step pins: the seventh block's own, and the sixth's, whose kernel pair shares ``ops/board_delta.py`` with it -------------------

#: sha256 of the tiny lowered step programs (``tools/step_text.py --block gdn|kda``), as ``tests/test_hybrid_trunk.py
#: PARENT_STEP_SHA256`` holds the four older blocks'. Both read on PR 58's tree (PR 57's, which was thrown away unmeasured: the same bodies), which MEANT to move both: the gradient kernels of
#: ``ops/board_delta.py`` work two chains a product, and what of a single chain's products shares an operand is one product too. Its
#: parent (be9aa8a) read ``kda`` 2e221aae... and ``gdn`` 323fe50e..., the pins as PR 55 left them (that PR MEANT to move both: the head
#: norm under its gate as one kernel pair; PR 53 before it: two chains a product in the forward solve). The ``--no-ids`` dumps of parent and
#: change differ inside ``board_delta_grad``'s loops alone, in every delta layer, and in the numbering of what follows: the tiny batch is ONE
#: board a block, so the first form's body runs its single chain, and its products are 25 where they were 36 a board and head in ``kda``'s dump
#: (the six levels' ``r`` one product, a level's ``dQL`` and ``dKL`` one: 550 -> 506 ``stablehlo.dot_general``, four layers); the second form's
#: packs the tiny net's two value heads a key head, 9 products where they were 18 a board and key head in ``gdn``'s (the spans, ``Mq^T dO``, ``T^T
#: dU``, ``dMq``, ``dA`` and ``dD``'s sums of the pair one each, the key head's six three: 231 -> 204, three layers); the loops are as many (134 and 114 ``stablehlo.while``) and every forward body reads what it
#: read; ``hybrid``, whose mixer runs ``ops/mamba_mix.py``'s kernels, reads what it read (``tests/test_hybrid_trunk.py``). A PR that means to
#: change either reads its own parent the same way. PR 61 MEANT to move ``gdn`` (its one attention layer's query heads go two a
#: product in ``ops/board_attention.py``'s plain pair; PR 60 brought the same change, was measured by the driver and refused on one pair of runs of ``train_pos_per_s``, its tree thrown away; PR 61 asked again): read anew on PR 61's tree, its parent 8be8117 read 473e6939...e1f6; the
#: ``tools/step_text.py --block gdn --no-ids`` dumps differ inside that layer's two kernel calls alone, 204 -> 197 ``stablehlo.dot_general``, 114 loops
#: both: with the parent's two bodies (``tests/test_board_attention.py PARENT_BODIES``) and its 16 (board, head)s a step patched over the module, the text hashes to the parent's pin; ``kda``, whose attention layer is the latent pair, passed UNEDITED.
GDN_STEP_SHA256 = {"kda": "9e78ff9cc17bc116b82bf620c05b68429ea8c71c5ee77b045dbec8e3ab674f17", "gdn": "a753d2e34b4ec08949cbdb9ba29a7eaa7995eb90464020c6dad60d36f8bb5110"}


@pytest.mark.parametrize("block", GDN_STEP_SHA256)
def test_the_sixth_and_seventh_blocks_lowered_steps_are_the_parents_op_for_op(block):
    cfg, batch = BLOCKS[block]
    assert cfg is (GDN if block == "gdn" else KDA)
    text = lowered_step_text(cfg, batch(1))
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == GDN_STEP_SHA256[block], HOW_TO_SEE_WHAT_MOVED.format(block=block)


def test_no_ids_takes_the_ssa_numbers_and_the_counters_on_function_names_and_nothing_else():
    """``tools/step_text.py --no-ids``: an operation more renumbers every ``%123`` after it and a helper more every ``@_where_203``
    after it; both numbers go, so that the ``diff`` of two dumps is the operations that moved. A name's own digits stay."""
    text = ("func.func private @_where_203(%arg0: tensor<64x128xi1>) -> tensor<64x128xf32> {\n"
            "  %12 = call @closed_call_280(%11, %c_5) : (tensor<f32>) -> tensor<f32>\n"
            "  %13 = stablehlo.custom_call @tpu_custom_call(%12) {kernel_name = \"board_delta\"}\n"
            "  %14 = call @layer03.attention(%13) : (tensor<8x64xf32>) -> tensor<8x64xf32>\n")
    assert without_ids(text) == ("func.func private @_where(%arg0: tensor<64x128xi1>) -> tensor<64x128xf32> {\n"
                                 "  % = call @closed_call(%, %c_5) : (tensor<f32>) -> tensor<f32>\n"
                                 "  % = stablehlo.custom_call @tpu_custom_call(%) {kernel_name = \"board_delta\"}\n"
                                 "  % = call @layer03.attention(%) : (tensor<8x64xf32>) -> tensor<8x64xf32>\n")
    assert without_ids(without_ids(text)) == without_ids(text)


def test_the_seventh_blocks_counters_checkpoint_and_refusals(tmp_path):
    trainer = AzTrainer(GDN)
    state, metrics = trainer.step(trainer.init(0), batch_of(0))
    # a fresh mixer at the public reset: rates uniform in (0, 16) on steps of softplus(1 + a) ~ 1.3 keep little of a state; beta and the
    # shared expert's gate are sigmoids of small logits
    assert 0.0 < float(metrics["gdn_state_kept"]) < 0.35 and 0.45 < float(metrics["gdn_beta"]) < 0.55 and 0.45 < float(metrics["shared_gate_mean"]) < 0.55
    assert "held_slots" in metrics and "kda_state_kept" not in metrics and "latent_rms" not in metrics
    fresh = trainer.init(0).params
    assert all(not np.any(np.asarray(fresh[name])) for name in trunk._ZERO_CENTERED) and np.all(np.asarray(fresh["gdn_o_norm"]) == 1.0)
    assert np.all(np.asarray(fresh["gdn_dt_bias"]) == 1.0) and np.all(np.exp(np.asarray(fresh["gdn_A_log"])) < 16.0)
    trainer.export(state, str(tmp_path / "gdn.npz"))
    loaded = dict(np.load(tmp_path / "gdn.npz"))
    assert az_config_from_params(loaded) == GDN  # the mixers from trunk_mixers, the GDN sizes from gdn_A_log, gdn_o_norm and gdn_conv, the norms' kind from trunk_hparams
    assert list(loaded[trunk.MIXERS]) == [5, 5, 5, 1] and loaded["gdn_qkvz"].shape == (3, 64, 2 * 64 + 2 * 128) and loaded["wq"].shape == (1, 64, 64)
    assert loaded["shared_token_gate"].shape == (4, 64, 1) and loaded[trunk.HPARAMS].shape == (len(trunk._HPARAMS),) and loaded[trunk.HPARAMS][-1] == 1.0
    # the forward through the loaded file is the trainer's own
    planes = batch_of(0)["planes"]
    restored = {k: jnp.asarray(v) for k, v in loaded.items() if k not in (trunk.HPARAMS, trunk.MIXERS)}
    want = trunk.trunk_forward({**state.params, **state.buffers}, planes, GDN)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(trunk.trunk_forward(restored, planes, az_config_from_params(loaded)), want))
    with pytest.raises(ValueError, match="missing|without"):
        az_config_from_params({k: v for k, v in loaded.items() if k != "gdn_A_log"})
    plain = {**loaded, trunk.HPARAMS: np.concatenate([loaded[trunk.HPARAMS][:-1], [0.0]])}  # a file of plain gains is another net, and is read as one
    assert az_config_from_params(plain) == dataclasses.replace(GDN, zero_centered_norms=False)
    older = AzTrainer(KDA)
    older.export(older.init(0), str(tmp_path / "kda.npz"))
    assert az_config_from_params(dict(np.load(tmp_path / "kda.npz"))) == KDA  # a file of the sixth block reads as it did: no gdn field, plain gains
    fields = {f.name: getattr(GDN, f.name) for f in dataclasses.fields(GDN)}
    for wrong in (dict(pattern="MEM*"), dict(cca=(2, 2)), dict(mixers=("gdn", "latent", "gdn", "attention")), dict(mixers=("gdn", "kda", "gdn", "attention")),
                  dict(mixers=("gdn",) * 3), dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16), dict(linear_num_key_heads=0),
                  dict(linear_num_value_heads=3), dict(linear_value_head_dim=16), dict(post_norms=True), dict(conv_kernel=0), dict(shared_width=0)):
        with pytest.raises(ValueError):
            TrunkConfig(**{**fields, **wrong})
    for beside_another_block in (dict(shared_token_gate=True), dict(zero_centered_norms=True), dict(linear_num_key_heads=2)):  # a knob of the seventh block beside the sixth
        with pytest.raises(ValueError):
            dataclasses.replace(KDA, **beside_another_block)
    assert dataclasses.replace(GDN, mixers=("gdn",) * 4).attention_layers == 0 and (GDN.attention_layers, GDN.routed_layers) == (1, 4)

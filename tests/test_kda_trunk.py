"""The sixth block of the square-token trunk (models/trunk.py with
``TrunkConfig.mixers``: Kimi-Linear's kimi_linear block, three Kimi Delta
Attention layers to one latent layer without RoPE) at a tiny size on the
CPU, on a worker of its own: the program against the benchmark's plain
reference (the literal recurrence), the unrotated latent against it, and
the share tied to the model: head shares and expert shares add up to the
uncut reference's layers."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_config_from_params
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer
from trunk_tiny import BATCH, CANCELLING, GRAD_CANCELLING_TOL, GRAD_TENSOR_TOL, KDA, KDA_CONFIG, KDA_MODEL, MLA, _all, batch_of, rel  # noqa: E402

# The plain reference is the benchmark's own (benchmark/reference/kda_trunk.py: the published equations, the recurrence square
# by square, the latent in the published column order, importing nothing of the program), at a tiny size; the program reads
# its parameters through benchmark/families/kda_trunk.py (the third trunk's permutation of the latent's columns).

from benchmark.families import kda_trunk as kda_family  # noqa: E402
from benchmark.reference import kda_trunk as kda_reference  # noqa: E402


def kda_params(seed: int, model=KDA_MODEL):
    return {k: jnp.asarray(v) for k, v in kda_reference.init_params(seed, model).items()}


@pytest.fixture(scope="module")
def kda_program():
    return kda_family.loss_and_grads(AzTrainer(KDA))


# Readings over seeds 1-3 (CPU): all gradients as one vector 0.006-0.011; the wrong layers below read 0.08 and more.
KDA_GRAD_ALL_TOL = 0.04
NEW_MATHEMATICS = ("kda_A_log", "kda_dt_bias", "kda_fb", "kda_beta", "kda_conv", "kda_o_norm", "kda_gb")


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_kda_loss_and_every_gradient_match_the_benchmarks_reference(kda_program, seed):
    params, batch = kda_params(seed), batch_of(seed)
    loss, got = kda_program(params, batch)
    want_loss, want = jax.value_and_grad(kda_reference.loss)(params, batch, KDA_CONFIG)
    assert not np.any(np.asarray(want.pop("expert_bias"))) and not np.any(np.asarray(got.pop("expert_bias")))
    assert set(got) == set(want) == set(trunk.trunk_param_shapes(KDA)) and set(trunk._OWNS["kda"]) | set(trunk._OWNS["latent"]) < set(want)
    print("kda", seed, abs(float(loss) - float(want_loss)) / float(want_loss), _all(got, want), {k: round(rel(got[k], want[k]), 4) for k in want})
    assert abs(float(loss) - float(want_loss)) < 0.01 * float(want_loss)
    assert _all(got, want) < KDA_GRAD_ALL_TOL
    for name in want:
        assert got[name].shape == want[name].shape and float(jnp.linalg.norm(want[name])) > 0, name
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL), name
    assert max(rel(got[name], want[name]) for name in NEW_MATHEMATICS) < 0.15  # the tensors that alone see the new mathematics


@pytest.mark.parametrize("wrong", ["decay_after", "unit_beta", "latent_rotated", "gate_before_norm"])
def test_the_tolerance_catches_a_wrong_sixth_block(kda_program, wrong):
    """The two misreadings of the recurrence (the decay after the rank-one
    correction; beta fixed at 1), a latent layer that rotates after all,
    and the fourth block's order of gate and norm (the norm over the gated
    head): each is further from the reference than the tolerance on all
    gradients as one vector, and a tensor that alone sees the new
    mathematics reads over its own. (A q without its d^-1/2 is NOT seen:
    the head norm takes a head's scale out again, 0.09 as one vector.)"""
    params, batch = kda_params(1), batch_of(1)
    config = KDA_CONFIG
    if wrong == "latent_rotated":
        config = {**KDA_CONFIG, "model": {**KDA_MODEL, "mla_use_nope": False}}
    else:
        config = {**KDA_CONFIG, "model": {**KDA_MODEL, "misread": wrong}}
    _, got = kda_program(params, batch)
    want = jax.grad(kda_reference.loss)(params, batch, config)
    print("kda wrong", wrong, _all(got, want), {k: round(rel(got[k], want[k]), 3) for k in NEW_MATHEMATICS + ("wq", "wkv_a")})
    assert _all(got, want) > 1.5 * KDA_GRAD_ALL_TOL, (wrong, _all(got, want))
    seen_by = ("wq", "wkv_a") if wrong == "latent_rotated" else NEW_MATHEMATICS
    assert max(rel(got[name], want[name]) for name in seen_by) > 0.2


def test_a_latent_layer_told_not_to_rotate_against_the_reference():
    """ONE latent sublayer of the program (its kernel pair under the tables
    that turn nothing) against the reference's latent with ``mla_use_nope``,
    and with it off against the rotating one: the branch alone."""
    params = kda_params(4)
    plan = trunk.trunk_plan(KDA)
    sublayer = next(s for s in plan if s.kind == "latent")
    assert not sublayer.rope and sublayer.index == 0 and sublayer.layer == "layer03"
    x = jnp.asarray(np.random.default_rng(4).standard_normal((BATCH * 64, KDA.hidden)), jnp.float32)
    own = trunk.sublayer_params(kda_family.to_program(KDA, params), sublayer)
    n1 = kda_reference._rms_norm(x, params["attn_norm"][3], KDA.rms_eps).reshape(BATCH, 64, -1)
    p = {name: params[name][0] for name in kda_reference._LATENT}
    product = kda_reference._product(lambda y: y, lambda y: y)
    for nope in (True, False):
        got, counters = trunk._latent_attention(x, own, KDA, sublayer._replace(rope=not nope))
        want = kda_reference._latent(n1, p, {**KDA_MODEL, "mla_use_nope": nope}, product).reshape(x.shape)
        other = kda_reference._latent(n1, p, {**KDA_MODEL, "mla_use_nope": not nope}, product).reshape(x.shape)
        print("latent nope", nope, rel(got, want), rel(got, other))
        assert rel(got, want) < 0.03 < 0.3 < rel(got, other) and 0.0 < float(counters["latent_rms"])


# -- the share tied to the model (guide section 4): two chips share a layer's heads, two its experts ----------------------------

UNCUT = {**KDA_MODEL, "mixers": ["kda", "latent"], "num_hidden_layers": 2, "num_attention_heads": 4, "kda_num_heads": 4,
         "num_experts": 16, "first_held_expert": 0}


def _head_share(cfg: TrunkConfig, kind: str, full, first: int, count: int):
    """The tensors of ``count`` heads from ``first`` of one uncut mixer (the reference's names and column order): the held
    heads' columns of what is made a head, their rows of the out-projection, everything else whole."""
    if kind == "kda":
        d, heads = cfg.kda_head_dim, UNCUT["kda_num_heads"]
        cols, per_head = slice(first * d, (first + count) * d), slice(first, first + count)
        conv = full["kda_conv"].reshape(3, heads * d, -1)[:, cols].reshape(3 * count * d, -1)
        return {**full, **{name: full[name][:, cols] for name in ("kda_q", "kda_k", "kda_v", "kda_fb", "kda_gb")}, "kda_conv": conv,
                "kda_dt_bias": full["kda_dt_bias"][cols], "kda_A_log": full["kda_A_log"][per_head], "kda_beta": full["kda_beta"][:, per_head],
                "kda_out": full["kda_out"][cols]}
    score, key_value, value = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.qk_nope_head_dim + cfg.v_head_dim, cfg.v_head_dim
    return {**full, "wq": full["wq"][:, first * score:(first + count) * score], "wkv_b": full["wkv_b"][:, first * key_value:(first + count) * key_value],
            "wo": full["wo"][first * value:(first + count) * value]}


@pytest.mark.parametrize("left_out", [None, "a_head_share", "an_expert_share", "shared_counted_twice"])
def test_the_head_shares_and_the_expert_shares_add_up_to_the_uncut_layers(left_out):
    """Two layers of the block UNCUT, as the benchmark's reference computes
    them (4 heads a mixer, all 16 experts, every expert on every token):
    a KDA layer with the dense feed-forward and a latent NoPE layer with a
    routed one. Against the program's pieces put together as four chips
    would: each mixer as the sum of TWO head shares' branches (2 of 4
    heads each: the held heads' columns and their rows of the
    out-projection; ``W_fa``, ``W_ga``, ``o_norm``, ``wkv_a`` and its norm
    whole on both), the dense layer and the shared expert ONCE, the routed
    part as the sum of TWO expert shares (8 of 16 each, routing over all
    16, weights renormalised over all three chosen, held or not). Leaving
    a share out, or counting the shared expert on both chips, is seen."""
    params = kda_params(5, UNCUT)
    planes = batch_of(5)["planes"]
    want = kda_reference.features(params, planes, UNCUT, lambda y: y, lambda y: y).reshape(-1, KDA.hidden)

    share = dataclasses.replace(KDA, mixers=("kda", "latent"), layers=2, nope_layers=(1,), held_experts=(0, 8))
    plan = trunk.trunk_plan(share)
    tokens = planes.reshape(-1, 19)
    embedded = jnp.dot(tokens, params["embed_w"], precision="highest") + params["embed_b"]  # float32: the embedding is no share's
    x = embedded
    for sublayer in plan:
        i = int(sublayer.layer[-2:])
        norms = {"attn_norm": params["attn_norm"][i], "moe_norm": params["moe_norm"][i]}
        if sublayer.kind in ("kda", "latent"):
            full = {name: params[name][0] for name in (kda_reference._KDA if sublayer.kind == "kda" else kda_reference._LATENT)}
            branches = []  # both chips read the same stream; the sum of their branches is the all-reduce
            for first in (0, 2)[:1 if left_out == "a_head_share" else 2]:
                held = _head_share(share, sublayer.kind, full, first, 2)
                if sublayer.kind == "latent":  # a share's own columns into the program's order
                    held = {k: v[0] for k, v in kda_family.to_program(share, {k: v[None] for k, v in held.items()}).items()}
                branches.append(trunk._KINDS[sublayer.kind][0](x, {**norms, **held}, share, sublayer._replace(index=0))[0])
            x = x + sum(branches)
        elif sublayer.kind == "dense":
            x = x + trunk._dense_layer(x, {**norms, **{name: params[name][0] for name in trunk._OWNS["dense"]}}, share, sublayer)[0]
        else:
            n2 = trunk._rms_norm(x, norms["moe_norm"], share.rms_eps)
            routed = {name: params[name][0] for name in trunk._OWNS["routed"] if name in params}
            out = trunk._ffn(n2, routed, "shared", True) * (2.0 if left_out == "shared_counted_twice" else 1.0)
            for first in (0, 8)[:1 if left_out == "an_expert_share" else 2]:
                held = {**routed, **{name: routed[name][first:first + 8] for name in ("experts_gate", "experts_up", "experts_down")}}
                out = out + trunk._experts(n2, held, dataclasses.replace(share, held_experts=(first, 8)), sublayer.layer)[0]
            x = x + out
    got = trunk._rms_norm(x, params["final_norm"], share.rms_eps)
    # what the two layers ADDED to the stream, so that the embedding (sqrt(hidden) times the branches' scale) does not hide a share
    start = trunk._rms_norm(embedded, params["final_norm"], share.rms_eps)
    error = rel(got - start, want - start)
    print("shares", left_out, error)
    assert error < 0.05 if left_out is None else error > 0.15, (left_out, error)


def test_the_sixth_blocks_counters_checkpoint_and_refusals(tmp_path):
    trainer = AzTrainer(KDA)
    state, metrics = trainer.step(trainer.init(0), batch_of(0))
    # a fresh mixer: rates uniform in [1, 16] on steps log-uniform in [0.001, 0.1] keep ~0.83 of a state a square; beta = sigmoid of a small logit
    assert 0.7 < float(metrics["kda_state_kept"]) < 0.95 and 0.45 < float(metrics["kda_beta"]) < 0.55
    assert 0.0 < float(metrics["latent_rms"]) < 10.0 and "held_slots" in metrics and "ssm_dt_mean" not in metrics
    trainer.export(state, str(tmp_path / "kda.npz"))
    loaded = dict(np.load(tmp_path / "kda.npz"))
    assert az_config_from_params(loaded) == KDA  # the mixers from trunk_mixers, the KDA sizes from kda_A_log, kda_o_norm and kda_conv
    assert list(loaded[trunk.MIXERS]) == [4, 4, 4, 2, 4] and loaded["kda_q"].shape == (4, 64, 32) and loaded["wq"].shape == (1, 64, 160)
    with pytest.raises(ValueError, match="missing|without"):
        az_config_from_params({k: v for k, v in loaded.items() if k != "kda_A_log"})
    for places in ([4, 4, 4, 2, 9], [4, 4, 4, 1, 4]):  # no such kind; a kind a mixed plan does not take
        with pytest.raises(ValueError, match="places|without"):
            az_config_from_params({**loaded, trunk.MIXERS: np.asarray(places, np.uint8)})
    older = AzTrainer(MLA)
    older.export(older.init(0), str(tmp_path / "mla.npz"))
    assert az_config_from_params(dict(np.load(tmp_path / "mla.npz"))) == MLA and trunk.MIXERS not in dict(np.load(tmp_path / "mla.npz"))
    fields = {f.name: getattr(KDA, f.name) for f in dataclasses.fields(KDA)}
    for wrong in (dict(pattern="MEM*E"), dict(cca=(2, 2), kv_heads=2), dict(mixers=("kda", "latent")), dict(mixers=("kda",) * 4 + ("attention",)),
                  dict(kv_lora_rank=None), dict(kda_heads=0), dict(post_norms=True), dict(conv_kernel=0)):
        with pytest.raises(ValueError):
            TrunkConfig(**{**fields, **wrong})
    assert TrunkConfig(**{**fields, "mixers": ("kda",) * 5, "kv_lora_rank": None, "nope_layers": ()}).attention_layers == 0

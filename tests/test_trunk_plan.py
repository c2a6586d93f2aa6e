"""What a trunk's lowered step text does not hold (``tools/step_text.py``
prints what the step pins hash: a step takes its parameters as arguments,
so neither their order nor their first values are in it), and the plan
the program reads a ``TrunkConfig`` as, for the six blocks' tiny nets
(``trunk_tiny.py BLOCKS``)."""

from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.trunk import Sublayer
from trunk_tiny import BLOCKS

_HEADS = "final_norm policy_w policy_b value_w value_b value_fc1_w value_fc1_b value_fc2_w value_fc2_b"
_FIRST = "attn_norm wq wk wv q_norm k_norm wo moe_norm router_w experts_gate experts_up experts_down"
#: ``list(trunk_param_shapes(cfg))`` and the sha256 of ``init_trunk_params(PRNGKey(0), cfg)``'s bytes in that order, read on
#: PR 46's PARENT (3036d35), before the shapes' maker was rewritten by kind. ``init_trunk_params`` deals the split of its rng
#: out in the order of the keys: reorder them and every tensor of every seed starts elsewhere, a share cell's rate follows
#: its seed's held rows, and no step pin would notice. A PR that means to move either reads its own parent the same way.
KEYS_AND_INIT_SHA256 = {
    "llada": (f"embed_w embed_b {_FIRST} {_HEADS}", "fabaae8f6bd6f0e472ef85b73eca49a2fd6d7dfb5675bb93b25646dc47f8036a"),
    "afmoe": (f"embed_w embed_b {_FIRST} {_HEADS} wgate post_attn_norm post_mlp_norm dense_gate dense_up dense_down shared_gate shared_up shared_down",
              "9e9d9defb3eec212fe622fbf639933a553dd4aa6d173a48867e413bee1161a56"),
    "mla": (f"embed_w embed_b attn_norm wq wkv_a kv_norm wkv_b wo moe_norm router_w experts_gate experts_up experts_down {_HEADS} "
            "dense_gate dense_up dense_down shared_gate shared_up shared_down", "6a1f37e25c06fc7b657eb970aff645cd6325b9056fa9931b0780860f244d92a2"),
    "hybrid": ("embed_w embed_b layer_norm mamba_in conv_w conv_b dt_bias A_log D_skip mamba_norm mamba_out wq wk wv wo router_w experts_up "
               f"experts_down {_HEADS} shared_up shared_down", "1f3777e015a1ca8428d85d740210e08593b56c941a2ea555f3b8a075da38342e"),
    "cca": ("embed_w embed_b attn_norm wq wk wv1 wv2 conv0_w conv0_b conv1_w conv1_b temp wo moe_norm router_down router_down_b router_w1 "
            f"router_w1_b router_w2 router_w2_b router_w3 experts_gate experts_up experts_down {_HEADS}",
            "02d03546c382512b911e8fa0998f8970e7fc73249ccd4031c3ff805d34c088a3"),
    # the sixth block, read on the tree of the PR that brought it (PR 47): the latent's tensors, then the KDA mixer's, under one ``attn_norm``
    "kda": ("embed_w embed_b attn_norm wq wkv_a kv_norm wkv_b wo kda_q kda_k kda_v kda_conv kda_fa kda_fb kda_dt_bias kda_A_log kda_beta kda_ga "
            f"kda_gb kda_o_norm kda_out moe_norm router_w experts_gate experts_up experts_down {_HEADS} dense_gate dense_up dense_down shared_gate "
            "shared_up shared_down", "f59c743787cb0a103838cbb9dc8b656acf5089f5ccdc243f012bd6ec07bdad88"),
    # the seventh block, read on the tree of the PR that brought it (PR 51): the attention's tensors, then the GDN mixer's, the token gate
    # with the routed layer's own; its five zero-centred norms start at zero
    "gdn": ("embed_w embed_b attn_norm wq wk wv q_norm k_norm wo gdn_qkvz gdn_ba gdn_conv gdn_dt_bias gdn_A_log gdn_o_norm gdn_out moe_norm "
            f"router_w experts_gate experts_up experts_down shared_token_gate {_HEADS} wgate shared_gate shared_up shared_down", "4c22383c99139dcb3ef79e42f7e0a973317762d3bccd1a971f233811cda7e22b"),
    # the eighth block, read on the tree of the PR that brought it (PR 56): the first kind's tensors and no other (no gate: no ``wgate``), the key-value
    # heads' ``wk`` and ``wv`` narrower than ``wq``; the two layer kinds' tables are no tensor
    "mellum": (f"embed_w embed_b attn_norm wq wk wv q_norm k_norm wo moe_norm router_w experts_gate experts_up experts_down {_HEADS}",
               "3db25edba706c732ef6cf70b43ee6225d3dfa1848b9d94fb503af150dc914347"),
    # the ninth block, read on the tree of the PR that brought it (PR 59): the eighth's tensors, the mask embedding after the embedding and the denoiser after
    # the value head (no tensor of a layer is its own: the block mask and the two streams are no parameter)
    "sdar": (f"embed_w embed_b mask_embed {_FIRST} {_HEADS} denoise_w denoise_b", "ee1d1dbcebdc3c0407685b5c7dd0bfda5aa25b89e3a314608d7076a7283b8979"),
    # the tenth block, read on the tree of the PR that brought it (PR 64): the first kind's tensors without the qk-norm's gains, NO tensor of a routed layer, the
    # exit gate after the value head, and what the second block brought (the two post-norms, the dense feed-forward) after the heads as it always comes
    "ouro": (f"embed_w embed_b attn_norm wq wk wv wo moe_norm {_HEADS} exit_gate_w exit_gate_b post_attn_norm post_mlp_norm dense_gate dense_up dense_down",
             "def521065847d86957b71567ba2dd4ff7db87b1db5b7c9a867add0ec471124d2"),
}


@pytest.mark.parametrize("block", KEYS_AND_INIT_SHA256)
def test_a_blocks_tensors_come_in_the_parents_order_and_start_where_the_parents_do(block):
    keys, digest = KEYS_AND_INIT_SHA256[block]
    cfg = BLOCKS[block][0]
    assert list(trunk.trunk_param_shapes(cfg)) == keys.split()
    params = trunk.init_trunk_params(jax.random.PRNGKey(0), cfg)
    assert list(params) == keys.split() and {k: v.shape for k, v in params.items()} == trunk.trunk_param_shapes(cfg)
    sha = hashlib.sha256()
    for value in params.values():
        sha.update(np.asarray(value).tobytes())
    assert sha.hexdigest() == digest
    # the table and the shapes agree: a kind's tensors are its row's, and nothing but norms, the embedding and the heads is no kind's
    owned = {name for names in trunk._OWNS.values() for name in names}
    plain = {"embed_w", "embed_b", *_HEADS.split(), *(("mask_embed", "denoise_w", "denoise_b") if cfg.block_length else ()),
             *(("exit_gate_w", "exit_gate_b") if cfg.loop_steps > 1 else ())} | {norm for s in trunk.trunk_plan(cfg) for norm in (s.norm, s.post_norm) if norm}
    assert set(keys.split()) <= owned | plain and not owned & plain


def _block_plan(mixer, layers, dense=0, nope=(), post=False):
    """Two sublayers a layer, written out: the mixer under ``attn_norm[i]``, the feed-forward under ``moe_norm[i]``."""
    plan = []
    for i in range(layers):
        plan.append(Sublayer(f"layer{i:02d}", mixer, i, "attn_norm", i, i not in nope, "post_attn_norm" if post else None))
        kind, index = ("dense", i) if i < dense else ("routed", i - dense)
        plan.append(Sublayer(f"layer{i:02d}", kind, index, "moe_norm", i, False, "post_mlp_norm" if post else None))
    return tuple(plan)


#: The plan of each tiny block, by hand: (scope prefix, kind, row of its kind's tensors, norm, norm's row, RoPE, post-norm).
PLANS = {
    "llada": _block_plan("attention", 2),
    "afmoe": (  # three layers, the first dense, the last without RoPE, a post-norm a sublayer
        Sublayer("layer00", "attention", 0, "attn_norm", 0, True, "post_attn_norm"), Sublayer("layer00", "dense", 0, "moe_norm", 0, False, "post_mlp_norm"),
        Sublayer("layer01", "attention", 1, "attn_norm", 1, True, "post_attn_norm"), Sublayer("layer01", "routed", 0, "moe_norm", 1, False, "post_mlp_norm"),
        Sublayer("layer02", "attention", 2, "attn_norm", 2, False, "post_attn_norm"), Sublayer("layer02", "routed", 1, "moe_norm", 2, False, "post_mlp_norm")),
    "mla": _block_plan("latent", 3, dense=1),
    "hybrid": (  # MEMEM*E: one sublayer a character, each under layer_norm[i], indexed from the first of its kind
        Sublayer("layer00", "mamba", 0, "layer_norm", 0), Sublayer("layer01", "routed", 0, "layer_norm", 1), Sublayer("layer02", "mamba", 1, "layer_norm", 2),
        Sublayer("layer03", "routed", 1, "layer_norm", 3), Sublayer("layer04", "mamba", 2, "layer_norm", 4),
        Sublayer("layer05", "attention", 0, "layer_norm", 5, True), Sublayer("layer06", "routed", 2, "layer_norm", 6)),
    "cca": _block_plan("cca", 2),
    "kda": (  # the mixer told by layer: KDA KDA KDA MLA KDA, each indexed from the first of its kind; the latent layer without RoPE; layer 0 dense
        Sublayer("layer00", "kda", 0, "attn_norm", 0, True), Sublayer("layer00", "dense", 0, "moe_norm", 0),
        Sublayer("layer01", "kda", 1, "attn_norm", 1, True), Sublayer("layer01", "routed", 0, "moe_norm", 1),
        Sublayer("layer02", "kda", 2, "attn_norm", 2, True), Sublayer("layer02", "routed", 1, "moe_norm", 2),
        Sublayer("layer03", "latent", 0, "attn_norm", 3, False), Sublayer("layer03", "routed", 2, "moe_norm", 3),
        Sublayer("layer04", "kda", 3, "attn_norm", 4, True), Sublayer("layer04", "routed", 3, "moe_norm", 4)),
    "gdn": (  # the mixer told by layer: GDN GDN GDN attention, each indexed from the first of its kind; every layer routed; RoPE where the attention reads it
        Sublayer("layer00", "gdn", 0, "attn_norm", 0, True), Sublayer("layer00", "routed", 0, "moe_norm", 0),
        Sublayer("layer01", "gdn", 1, "attn_norm", 1, True), Sublayer("layer01", "routed", 1, "moe_norm", 1),
        Sublayer("layer02", "gdn", 2, "attn_norm", 2, True), Sublayer("layer02", "routed", 2, "moe_norm", 2),
        Sublayer("layer03", "attention", 0, "attn_norm", 3, True), Sublayer("layer03", "routed", 3, "moe_norm", 3)),
    "mellum": (  # attention then routed, four times; every attention turns, the full layer (the last) by the YaRN table and the sliding ones by the plain one
        Sublayer("layer00", "attention", 0, "attn_norm", 0, True), Sublayer("layer00", "routed", 0, "moe_norm", 0),
        Sublayer("layer01", "attention", 1, "attn_norm", 1, True), Sublayer("layer01", "routed", 1, "moe_norm", 1),
        Sublayer("layer02", "attention", 2, "attn_norm", 2, True), Sublayer("layer02", "routed", 2, "moe_norm", 2),
        Sublayer("layer03", "attention", 3, "attn_norm", 3, True, rope_type="yarn"), Sublayer("layer03", "routed", 3, "moe_norm", 3)),
    "sdar": _block_plan("attention", 2),  # what is served: the first block's plan, one copy of a board (``streams`` 1); the training forward's is below
    "ouro": _block_plan("attention", 2, dense=2, post=True),  # ONE pass of the loop: every feed-forward dense, a post-norm a sublayer; ``loop_steps`` is no part of the plan
}


@pytest.mark.parametrize("block", PLANS)
def test_the_plan_of_a_tiny_block_is_what_it_should_be(block):
    cfg = BLOCKS[block][0]
    plan = trunk.trunk_plan(cfg)
    assert plan == PLANS[block]
    assert plan is trunk.trunk_plan(cfg)  # made once a configuration
    assert _block_plan("attention", 3, dense=1, nope=(2,), post=True) == PLANS["afmoe"]  # the helper against the one written out whole
    # the plan and the configuration's own counts agree, and every kind has its function, its row and, a mixer, its reader
    kinds = [s.kind for s in plan]
    assert kinds.count("routed") == cfg.routed_layers and sum(k in ("attention", "latent", "cca") for k in kinds) == cfg.attention_layers
    assert set(trunk._KINDS) == set(trunk._OWNS) == {*trunk._MIXERS, *trunk._FEED_FORWARDS} and set(trunk._SIZES) == set(trunk._MIXERS)
    # a sublayer's slice is its norms and its row of the table, out of the stacked tensors
    params = {name: np.zeros(shape, np.float32) for name, shape in {**trunk.trunk_param_shapes(cfg), **trunk.trunk_buffer_shapes(cfg)}.items()}
    for sublayer in plan:
        own = trunk.sublayer_params(params, sublayer)
        assert set(own) == ({sublayer.norm, sublayer.post_norm, *trunk._OWNS[sublayer.kind]} - {None}) & set(params)
        assert all(value.shape == params[name].shape[1:] for name, value in own.items())
    sliced = list(trunk._sliced(params, plan))
    assert [s for s, _ in sliced] == list(plan) and all(set(p) == set(trunk.sublayer_params(params, s)) for s, p in sliced)


def test_the_ninth_blocks_training_plan_tells_its_attention_sublayers_two_streams_and_nothing_else():
    """``trunk_plan(cfg, 2)`` (``trunk_forward_counted`` told a batch's noise) is the served plan but for ``streams`` on the attention sublayers: the routed
    path is not told a board's length; the older blocks' plans have one stream whatever they are asked."""
    cfg = BLOCKS["sdar"][0]
    training = trunk.trunk_plan(cfg, 2)
    assert [s._replace(streams=1) for s in training] == list(PLANS["sdar"]) and [s.streams for s in training] == [2, 1, 2, 1]
    assert training is trunk.trunk_plan(cfg, 2) and trunk.trunk_plan(cfg, 1) == trunk.trunk_plan(cfg)
    assert all(s.streams == 1 for block, (other, _) in BLOCKS.items() if block != "sdar" for s in trunk.trunk_plan(other))


def test_a_mixed_plan_is_the_one_mixers_plan_where_every_layer_names_the_same():
    """``mixers`` that name the latent on every layer give the third block's plan, tensors and key order (the field adds
    nothing where it changes nothing), but for RoPE, which a latent takes off only as a layer's own of ``mixers``."""
    import dataclasses

    mla = BLOCKS["mla"][0]
    told = dataclasses.replace(mla, mixers=("latent",) * mla.layers)
    assert trunk.trunk_plan(told) == trunk.trunk_plan(mla) and trunk.trunk_param_shapes(told) == trunk.trunk_param_shapes(mla)
    assert list(trunk.trunk_param_shapes(told)) == list(trunk.trunk_param_shapes(mla))
    unrotated = dataclasses.replace(told, nope_layers=(1,))
    assert [s.rope for s in trunk.trunk_plan(unrotated) if s.kind == "latent"] == [True, False, True]
    with pytest.raises(ValueError, match="nope_layers"):
        dataclasses.replace(mla, nope_layers=(1,))
    # the rows of a kind's stacked tensors count that kind's layers alone
    kda = BLOCKS["kda"][0]
    shapes = trunk.trunk_param_shapes(kda)
    assert shapes["kda_q"][0] == 4 and shapes["wq"][0] == 1 and shapes["attn_norm"][0] == shapes["moe_norm"][0] == 5
    assert (kda.attention_layers, kda.routed_layers) == (1, 4)


# -- the counter PR 61 brings: how much of a plan's attention the kernel pair takes two query heads a product -------------------------------

#: by the shapes alone: the first block a group of 1, the third's layers and the sixth's one attention layer latent; the ninth's block-masked
#: layers pair by their group as the plain ones do (since PR 63: the tiny plan 4 over 1)
PAIRED = {"llada": 0.0, "afmoe": 1.0, "mla": 0.0, "hybrid": 1.0, "cca": 1.0, "kda": 0.0, "gdn": 1.0, "mellum": 1.0, "sdar": 1.0, "ouro": 0.0}


@pytest.mark.parametrize("block", PAIRED)
def test_the_share_of_a_plans_query_heads_that_go_two_a_product_follows_the_shapes(block):
    assert set(PAIRED) == set(BLOCKS)
    assert trunk.attention_heads_paired(BLOCKS[block][0]) == PAIRED[block]


@pytest.mark.parametrize("heads,kv_heads,share", [(32, 4, 1.0), (6, 2, 4 / 6), (16, 16, 0.0)], ids=["published_32_over_4", "odd_group", "group_of_1"])
def test_a_block_masked_plan_pairs_by_its_group_as_a_plain_one_does(heads, kv_heads, share):
    """``block_length`` moves nothing in the count: the blocks pair's bodies take a group's heads by the same ``_pairs``."""
    import dataclasses

    masked = dataclasses.replace(BLOCKS["sdar"][0], heads=heads, kv_heads=kv_heads)
    assert masked.block_length == 4 and trunk.attention_heads_paired(masked) == share
    assert trunk.attention_heads_paired(dataclasses.replace(masked, block_length=0)) == share


def test_an_odd_group_pairs_all_but_its_last_head_and_the_init_span_says_so():
    import dataclasses
    import time

    from fishnet_tpu.ops.board_attention import paired_heads
    from fishnet_tpu.telemetry.spans import RECORDER
    from fishnet_tpu.train.az_trainer import AzTrainer

    assert [paired_heads(heads, kv) for heads, kv in ((16, 16), (32, 4), (8, 2), (6, 2), (3, 1), (2, 1))] == [0, 32, 8, 4, 2, 2]
    cfg = dataclasses.replace(BLOCKS["afmoe"][0], heads=6)  # 6 over 2: a pair and a lone head a key-value head
    assert trunk.attention_heads_paired(cfg) == 4 / 6
    started = time.monotonic()
    AzTrainer(cfg).init(0)
    span = [s for s in RECORDER.spans() if s["t"] >= started and s["stage"] == "train_init"][-1]
    assert span["trainer"] == "az" and span["attention_heads_paired"] == 4 / 6 and "layout_held_leaves" in span

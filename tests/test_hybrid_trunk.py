"""The fourth block of the square-token trunk (models/trunk.py with a
``TrunkConfig.pattern``: Nemotron-Labs-TwoTower's nemotron_h block) at a
tiny size on the CPU (batches, tolerances and the blocks' tiny
configurations are ``trunk_tiny.py``'s; a file a block, so that each runs on
a worker of its own): the program against the benchmark's own plain
reference, the shares against the uncut layer, the rule for widths no
tile divides, what a pattern refuses, its checkpoint, and the three
accepted blocks' step programs against their parent's."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_checkpoint, az_config_from_params, az_forward, init_az_buffers, init_az_params
from fishnet_tpu.train.az_trainer import AzTrainer
from tools.step_text import HOW_TO_SEE_WHAT_MOVED, lowered_step_text
from trunk_tiny import BATCH, BLOCKS, CANCELLING, GRAD_CANCELLING_TOL, GRAD_TENSOR_TOL, HYBRID, _all, batch_of, rel  # noqa: E402

# The plain reference is again the benchmark's own (benchmark/reference/hybrid_trunk.py: the sequential recurrence,
# importing nothing of the program), at a tiny size with the published factors: hidden 21 x 4 (one short row for the
# moves, as every tiny net here), expert width 29 x 8 = 232 = 1.8125 lane tiles, which the odd-lane rule pads to 256.

from benchmark.families import hybrid_trunk as hybrid_family  # noqa: E402
from benchmark.reference import hybrid_trunk as hybrid_reference  # noqa: E402

HYBRID_MODEL = {"hidden_size": 84, "pattern": "MEMEM*E", "num_hidden_layers": 7, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
                "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
                "moe_intermediate_size": 232, "moe_shared_expert_intermediate_size": 58, "num_experts": 8, "num_routed_experts": 16,
                "first_held_expert": 4, "num_experts_per_tok": 3, "route_scale": 2.5, "load_balance_coeff": 0.001, "rope_theta": 10000,
                "rms_norm_eps": 1e-05, "input_planes": 19, "value_hidden": 32, "policy_planes": 73}
HYBRID_CONFIG = {"model": HYBRID_MODEL, "train": {"value_weight": 1.0}}


def hybrid_params(seed: int, model=HYBRID_MODEL):
    return {k: jnp.asarray(v) for k, v in hybrid_reference.init_params(seed, model).items()}


@pytest.fixture(scope="module")
def hybrid_program():
    return hybrid_family.loss_and_grads(AzTrainer(HYBRID))


# Readings over seeds 1-3 (CPU): the loss within 0.0004 of the reference's; all gradients as one vector 0.035-0.045 (a hidden of
# 84 averages less rounding away than 2,688 does: with the XLA products in float32 the same reads 0.012, the kernels' own bfloat16);
# single tensors 0.002-0.115. The wrong mixers and experts below read 0.17 and more.
HYBRID_GRAD_ALL_TOL = 0.08


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_hybrid_loss_and_every_gradient_match_the_benchmarks_reference(hybrid_program, seed):
    params, batch = hybrid_params(seed), batch_of(seed)
    loss, got = hybrid_program(params, batch)
    want_loss, want = jax.value_and_grad(hybrid_reference.loss)(params, batch, HYBRID_CONFIG)
    assert not np.any(np.asarray(want.pop("expert_bias"))) and not np.any(np.asarray(got.pop("expert_bias")))
    assert set(got) == set(want) == set(trunk.trunk_param_shapes(HYBRID))
    assert {"mamba_in", "conv_w", "A_log", "dt_bias", "D_skip", "layer_norm"} < set(want) and not {"experts_gate", "q_norm", "attn_norm"} & set(want)
    print("hybrid", seed, abs(float(loss) - float(want_loss)) / float(want_loss), _all(got, want), {k: round(rel(got[k], want[k]), 4) for k in want})
    assert abs(float(loss) - float(want_loss)) < 0.01 * float(want_loss)
    assert _all(got, want) < HYBRID_GRAD_ALL_TOL
    for name in want:
        assert got[name].shape == want[name].shape and float(jnp.linalg.norm(want[name])) > 0, name
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL), name


def _wrong_hybrid(monkeypatch, wrong):
    if wrong == "decay_rate_x1.5":
        scan = trunk.board_scan
        monkeypatch.setattr(trunk, "board_scan", lambda x, b, c, step, a, *rest: scan(x, b, c, step, 1.5 * a, *rest))
    elif wrong == "taps_reversed":
        conv = trunk.mamba_conv  # what ``_mamba`` calls since PR 44 (``ops/mamba_mix.py``)
        monkeypatch.setattr(trunk, "mamba_conv", lambda u, w, b, *rest: conv(u, w[:, ::-1], b, *rest))
    elif wrong == "no_direct_term":
        scan = trunk.board_scan
        monkeypatch.setattr(trunk, "board_scan", lambda x, b, c, step, a, skip, *rest: scan(x, b, c, step, a, 0.0 * skip, *rest))
    elif wrong == "plain_relu":
        monkeypatch.setattr(trunk, "squared_relu", lambda u, extent, interpret: jax.nn.relu(u))
        monkeypatch.setattr(trunk, "_ffn", lambda n, p, kind, gated: trunk._matmul(jax.nn.relu(trunk._matmul(n, p[f"{kind}_up"])), p[f"{kind}_down"]))
    elif wrong == "scan_backwards":
        scan = trunk.board_scan
        flip = lambda y: y[:, ::-1]
        monkeypatch.setattr(trunk, "board_scan", lambda x, b, c, step, *rest: flip(scan(flip(x), flip(b), flip(c), flip(step), *rest)))


#: The tensors of which each wrong layer has to move one's gradient past 1.5x a single tensor's tolerance (sound readings: at most 0.115).
#: All gradients as one vector do not show a wrong decay (0.06 against a sound 0.045): a fresh mixer's steps are 0.001-0.1, so
#: what passes through its state is small beside its direct term; the named limits of the cell's ``correct`` exist for the same reason.
WRONG_HYBRID_SHOWS = {"decay_rate_x1.5": ("A_log", "dt_bias"), "taps_reversed": ("conv_w",), "no_direct_term": ("D_skip", "mamba_out"),
                      "plain_relu": ("experts_up", "shared_up"), "scan_backwards": ("A_log", "conv_w")}


@pytest.mark.parametrize("wrong", WRONG_HYBRID_SHOWS)
def test_the_tolerance_catches_a_wrong_mixer_or_expert(monkeypatch, wrong):
    params, batch = hybrid_params(2), batch_of(2)
    _wrong_hybrid(monkeypatch, wrong)
    _, got = hybrid_family.loss_and_grads(AzTrainer(HYBRID))(params, batch)
    want = jax.grad(hybrid_reference.loss)(params, batch, HYBRID_CONFIG)
    named = {name: round(rel(got[name], want[name]), 3) for name in WRONG_HYBRID_SHOWS[wrong]}
    print(wrong, _all(got, want), named)
    assert max(named.values()) > 1.5 * GRAD_TENSOR_TOL


def test_the_sixteen_shares_of_an_ungated_layer_add_up_to_the_uncut_reference():
    """Published layers 0-1 (``ME``) with all 128 experts, as the
    benchmark's reference computes them uncut, against the program's
    pieces put together as 16 chips would: the mixer and the shared
    expert ONCE, and the routed parts of 16 shares of 8 ungated experts,
    each routing over all 128 with top-6."""
    import dataclasses

    model = {**HYBRID_MODEL, "pattern": "ME", "num_hidden_layers": 2, "num_experts": 128, "num_routed_experts": 128, "first_held_expert": 0,
             "num_experts_per_tok": 6, "moe_intermediate_size": 24}
    whole = dataclasses.replace(HYBRID, pattern="ME", layers=1, experts=128, experts_per_token=6, expert_width=24, held_experts=None)
    params = hybrid_params(5, model)
    planes = batch_of(5, 2)["planes"]
    same = lambda x: x
    want = hybrid_reference.features(params, planes, model, same, same).reshape(128, 84)

    x = trunk._matmul(planes.reshape(128, 19), params["embed_w"]) + params["embed_b"]
    mixer, routed = trunk.trunk_plan(whole)
    x = x + trunk._mamba(x, trunk.sublayer_params(params, mixer), whole, mixer)[0]  # every chip computes it alike: once
    layer = trunk.sublayer_params(params, routed)
    n2 = trunk._rms_norm(x, layer.pop("layer_norm"), whole.rms_eps)

    def share(first):
        cfg = dataclasses.replace(whole, held_experts=(first, 8))
        held = {k: (v[first:first + 8] if k.startswith("experts_") else v) for k, v in layer.items()}
        mixed, counters = jax.jit(lambda n, l: trunk._experts(n, l, cfg, "layer01"))(n2, held)
        return mixed, counters["held_slots"] if "held_slots" in counters else counters["expert_slots"][first:first + 8].sum()

    parts = [share(first) for first in range(0, 128, 8)]
    final = lambda y: trunk._rms_norm(y, params["final_norm"], whole.rms_eps)
    shared = trunk._ffn(n2, layer, "shared", False)
    total = final(x + shared + sum(mixed for mixed, _ in parts))
    assert rel(total, want) < 0.03, rel(total, want)
    assert rel(final(x + shared + parts[0][0]), want) > 2 * rel(total, want)  # one share is not the layer
    assert rel(final(x + 16 * shared + sum(mixed for mixed, _ in parts)), want) > 0.1  # the shared expert counts once
    assert sum(float(slots) for _, slots in parts) == 128 * 6  # every slot falls in exactly one share


@pytest.mark.parametrize("pattern,match", [("", "not a string of M"), ("MAM", "not a string of M"), ("MM*", "with an E in it"), ("me", "not a string of M")])
def test_a_malformed_pattern_is_refused(pattern, match):
    import dataclasses

    with pytest.raises(ValueError, match=match):
        dataclasses.replace(HYBRID, pattern=pattern, layers=1)


def test_a_pattern_refuses_what_its_layers_do_not_have():
    import dataclasses

    assert HYBRID.layers == 7 and HYBRID.routed_layers == 3 and HYBRID.attention_layers == 1
    with pytest.raises(ValueError, match="names 7 layers, not 5"):
        dataclasses.replace(HYBRID, layers=5)
    for field, value in (("dense_layers", 1), ("nope_layers", (5,)), ("post_norms", True), ("gated_attention", True)):
        with pytest.raises(ValueError, match="one sublayer and one norm"):
            dataclasses.replace(HYBRID, **{field: value, **({"dense_width": 8} if field == "dense_layers" else {})})
    with pytest.raises(ValueError, match="whole groups"):
        dataclasses.replace(HYBRID, mamba_groups=3)
    with pytest.raises(ValueError, match="an M layer wants"):
        dataclasses.replace(HYBRID, state_size=0)
    assert dataclasses.replace(HYBRID, pattern="E*", layers=1, mamba_heads=0).layers == 2  # no M: the mixer's sizes are not read


def test_hybrid_checkpoint_round_trips_and_fresh_tensors_start_where_mamba2s_do(tmp_path):
    """A pattern's ``.npz`` names its pattern and the two sizes no shape
    gives; a fresh state's mixer tensors are Mamba-2's, none mistaken for
    a bias or a matrix by its name."""
    import dataclasses

    params = init_az_params(jax.random.PRNGKey(3), HYBRID)
    assert not np.any(np.asarray(params["conv_b"])) and np.all(np.asarray(params["D_skip"]) == 1.0)
    rates, steps = np.exp(np.asarray(params["A_log"])), np.log1p(np.exp(np.asarray(params["dt_bias"], np.float64)))
    assert rates.min() >= 1.0 and rates.max() <= 16.0 and rates.std() > 1.0 and steps.min() >= 0.00099 and steps.max() <= 0.1001
    assert np.abs(np.asarray(params["conv_w"])).max() <= 0.5 and np.asarray(params["conv_w"]).std() > 0.2
    assert np.all(np.asarray(params["layer_norm"]) == 1.0) and np.all(np.asarray(params["mamba_norm"]) == 1.0)
    state = {**params, **init_az_buffers(HYBRID)}
    path = tmp_path / "hybrid.npz"
    np.savez(path, **az_checkpoint(state, HYBRID))
    with np.load(path) as data:
        loaded = dict(data)
    assert bytes(loaded["trunk_pattern"]).decode() == "MEMEM*E"
    assert az_config_from_params(loaded) == HYBRID
    with pytest.raises(ValueError, match="mismatched"):
        az_config_from_params({**loaded, "trunk_pattern": loaded["trunk_pattern"][:5]})
    with pytest.raises(ValueError, match="without"):
        az_config_from_params({k: v for k, v in loaded.items() if k != "dt_bias"})
    logits, value = az_forward(state, batch_of(1)["planes"], az_config_from_params(loaded))
    assert logits.shape == (BATCH, 4672) and np.all(np.isfinite(np.asarray(logits))) and np.all(np.abs(np.asarray(value)) <= 1)
    trainer = AzTrainer(dataclasses.replace(HYBRID, recompute_experts=True))
    _, metrics = trainer.step(trainer.init(1), batch_of(1))
    assert 0.001 < float(metrics["ssm_dt_mean"]) < 0.2 and 0.0 <= float(metrics["ssm_decay_min"]) <= 1.0 and "held_slots" in metrics


@pytest.mark.parametrize("width,lanes,hidden,rows", [(1856, 1920, 2688, 3072), (1024, 1024, 2048, 2048), (768, 768, 1024, 1024), (232, 256, 84, 84),
                                                      (116, 116, 336, 336), (1536, 1536, 4096, 4096)])
def test_the_one_rule_for_dimensions_no_tile_divides(width, lanes, hidden, rows):
    assert trunk._whole_lanes(width) == lanes and trunk._whole_rows(hidden) == rows


def test_mosaic_is_never_handed_a_tile_that_is_not_whole_lanes(monkeypatch):
    assert trunk._tile(232, 1024) == 232  # the interpreter's tiny nets
    monkeypatch.setattr(trunk, "_interpret", lambda: False)
    assert trunk._tile(1920, 1024) == 640 and trunk._tile(2688, 1024) == 896 and trunk._tile(3072, 1024) == 1024
    with pytest.raises(ValueError, match="1856"):
        trunk._tile(1856, 1024)


@pytest.mark.parametrize("hidden,width", [(84, 232), (2688, 136)], ids=["odd_lanes", "odd_rows_and_lanes"])
def test_a_shares_ungated_experts_against_a_loop_over_the_held_experts(hidden, width):
    """``_routed`` on a share with the rule's padding in force (232 ->
    256 lanes; 2,688 -> a moved row of 3,072) against plain indexing:
    the padding changes no number and takes no gradient."""
    rng = np.random.default_rng(3)
    tokens, k, experts, count = 64, 2, 8, 3
    n2 = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    up = jnp.asarray(rng.standard_normal((count, hidden, width)) / np.sqrt(hidden), jnp.float32)
    down = jnp.asarray(rng.standard_normal((count, width, hidden)) / np.sqrt(width), jnp.float32)
    expert = jnp.asarray(np.stack([rng.permutation(experts)[:k] for _ in range(tokens)]), jnp.int32)
    weight = jnp.asarray(rng.uniform(0.2, 1.0, (tokens, k)), jnp.float32)

    def program(n2, up, down, recompute):
        group = expert.reshape(-1)
        _, order, scale = jax.lax.sort((group, jnp.arange(tokens * k, dtype=jnp.int32), weight.reshape(-1)), num_keys=1, is_stable=True)
        sizes = jnp.sum(group[:, None] == jnp.arange(experts)[None, :], axis=0, dtype=jnp.int32)
        held = trunk._held(jnp.sum(sizes[:count]), expert < count, scale)
        routed = trunk._routed_recomputed if recompute else trunk._routed
        return routed(n2, weight, order, sizes[:count], held, None, up, down, "layer00")

    def plain(n2, up, down):
        bf = lambda y: y.astype(jnp.bfloat16).astype(jnp.float32)
        out = 0.0
        for e in range(count):
            own = jnp.sum(jnp.where(expert == e, weight, 0.0), axis=1)[:, None]
            out = out + own * bf(bf(jnp.square(jax.nn.relu(bf(bf(n2) @ bf(up[e])))) ) @ bf(down[e]))
        return out

    want, want_grads = jax.value_and_grad(lambda *a: jnp.sum(plain(*a) ** 2), (0, 1, 2))(n2, up, down)
    for recompute in (False, True):
        got, grads = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(program(*a, recompute) ** 2), (0, 1, 2)))(n2, up, down)
        assert program(n2, up, down, recompute).shape == (tokens, hidden)
        assert abs(float(got) - float(want)) < 0.02 * float(want), (recompute, float(got), float(want))
        for g, w in zip(grads, want_grads):
            assert g.shape == w.shape and rel(g, w) < 0.03, (recompute, rel(g, w))


#: sha256 of ``jax.jit(AzTrainer(cfg)._step).lower(state, batch).as_text()`` (no debug locations) at this file's tiny sizes, read
#: on PR 41's parent (792ac6d) with this jax: the three accepted blocks' step programs. A PR that means to change one of them
#: reads its own parent the same way and says so; one that does not has changed a program it did not mean to. PR 42 read
#: all four on ITS parent (3c5f3ae): the three are what they were, and the fourth block's is pinned beside them. PR 42's
#: kernel form of the gated feed-forward is taken by size (``trunk._FUSED_GATE_BYTES``), and every tiny net here is under
#: the rule, so ``afmoe`` and ``mla`` hold too: the kernel form is held by ``test_moe_trunk.py`` on both sides of the rule
#: and by ``test_trunk_tpu_compile.py`` at the dense layer's published size. PR 44 MEANT to change ``hybrid`` (the mixer's
#: convolution with its silu and its gate with the grouped norm became two kernel pairs, ``ops/mamba_mix.py``): its pin was
#: read anew on PR 44's tree (the parent 3160177 read b47f7635...763a); the three others passed unedited. PR 50 MEANT to change
#: ``afmoe`` (the gated out-projection became one ``custom_vjp``, ``trunk._gated_out``, taken by the layer's own
#: ``cfg.gated_attention`` at every size): its pin was read anew on PR 50's tree (the parent 1a857f0 read 3bb78678...6f35); the
#: three others and ``cca``'s passed unedited: no ungated block's program moved. PR 61 MEANT to move ``afmoe`` and ``hybrid`` (and
#: ``cca``'s, ``gdn``'s and ``mellum``'s in their files): inside ``board_attention`` / ``board_attention_grad`` a key-value head's query
#: heads go two a product (the tiny nets run groups of 2 and 4), and a grid step of a paired group takes ``_PAIRED_BOARDS // group``
#: boards (PR 60 brought the same change, was measured by the driver and refused on one pair of runs of ``train_pos_per_s``, its tree thrown away; PR 61 asked again). Both read anew on PR 61's tree (its parent 8be8117 read 52990bc0...5d49 and 3fa4c14a...4f71); the ``tools/step_text.py
#: --block <name> --no-ids`` dumps of parent and change differ inside the two kernels' calls alone, in every attention layer (the grid loop's
#: block of boards and the body: ``afmoe`` 141 -> 120 ``stablehlo.dot_general``, ``hybrid`` 158 -> 151, the loops as many, 46 and 100
#: ``stablehlo.while``): with the parent's two bodies (``tests/test_board_attention.py PARENT_BODIES``) and its 16 (board, head)s a step patched over the module, the text hashes to the parent's pin.
#: ``llada`` (a group of 1: the one-head body, PR 32's program) and ``mla`` (the latent pair, another body) passed UNEDITED.
PARENT_STEP_SHA256 = {
    "llada": "60f5865d293d8516a7b2474ae17766489aca839166a5b0790d0a185cee8b0c77",
    "afmoe": "504621b28996cfe07348a59c69cd7f729153834fa739d196e7cc30e9e31468a3",
    "mla": "0fedb499d5d0ceb1b7924dbb2f29537fddb8a67cbebdbcd6d1a68efeda7719e2",
    "hybrid": "e8c8c0db1e151e16e86b918c0737e9f9a86cfc6ab916ae60a66277720c62e822",
}


@pytest.mark.parametrize("block", PARENT_STEP_SHA256)
def test_an_accepted_blocks_lowered_step_is_the_parents_op_for_op(block):
    import hashlib

    cfg, batch = BLOCKS[block]
    text = lowered_step_text(cfg, batch(1))
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP_SHA256[block], HOW_TO_SEE_WHAT_MOVED.format(block=block)

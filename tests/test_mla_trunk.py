"""The third block of the square-token trunk (models/trunk.py with a
``TrunkConfig.kv_lora_rank``: Kanana-2's deepseek_v3 block, latent
attention) at a tiny size on the CPU, split by PR 46 from
``test_moe_trunk.py`` so that it runs on a worker of its own."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_checkpoint, az_config_from_params, az_forward, init_az_buffers, init_az_params
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer
from trunk_tiny import (  # noqa: E402
    AFMOE,
    BATCH,
    CANCELLING,
    GRAD_CANCELLING_TOL,
    GRAD_TENSOR_TOL,
    MLA,
    MLA_CONFIG,
    MLA_MODEL,
    TINY,
    _all,
    batch_of,
    rel,
)

# -- the third block: latent attention (Kanana-2's deepseek_v3 block) ---------------------------------------------
#
# The plain reference here is the benchmark's own (benchmark/reference/mla_trunk.py: the published equations,
# literally, in the published column order, importing nothing of the program), at a tiny size; the program reads
# its parameters through benchmark/families/mla_trunk.py's permutation and hands its gradients back through it.

from benchmark.families import mla_trunk as mla_family  # noqa: E402
from benchmark.reference import mla_trunk as mla_reference  # noqa: E402



def mla_params(seed: int):
    return {k: jnp.asarray(v) for k, v in mla_reference.init_params(seed, MLA_MODEL).items()}


@pytest.fixture(scope="module")
def mla_program():
    return mla_family.loss_and_grads(AzTrainer(MLA))


# Readings over seeds 1-3 (CPU): all gradients as one vector 0.005-0.013 (the embedding at sqrt(hidden) and the peaked, centred
# router of the reference's conditioning make this block's sums add); the wrong layers below read 0.115-0.54.
MLA_GRAD_ALL_TOL = 0.04



@pytest.mark.parametrize("seed", [1, 2, 3])
def test_mla_loss_and_every_gradient_match_the_benchmarks_reference(mla_program, seed):
    params, batch = mla_params(seed), batch_of(seed)
    loss, got = mla_program(params, batch)
    want_loss, want = jax.value_and_grad(mla_reference.loss)(params, batch, MLA_CONFIG)
    assert not np.any(np.asarray(want.pop("expert_bias"))) and not np.any(np.asarray(got.pop("expert_bias")))
    assert set(got) == set(want) == set(trunk.trunk_param_shapes(MLA)) and {"wkv_a", "kv_norm", "wkv_b"} < set(want) and "q_norm" not in want
    print("mla", seed, abs(float(loss) - float(want_loss)) / float(want_loss), _all(got, want), {k: round(rel(got[k], want[k]), 4) for k in want})
    assert abs(float(loss) - float(want_loss)) < 0.01 * float(want_loss)
    assert _all(got, want) < MLA_GRAD_ALL_TOL
    for name in want:
        assert got[name].shape == want[name].shape and float(jnp.linalg.norm(want[name])) > 0, name
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL), name


@pytest.mark.parametrize("wrong", ["no_latent_norm", "rotate_half_unpermuted", "keys_before_values_unpermuted", "scale_of_nope"])
def test_the_tolerance_catches_a_wrong_latent_layer(mla_program, monkeypatch, wrong):
    """A latent that is not normed; the program's rotate-half on columns
    left in the published interleaved order; ``wkv_b`` left in the
    published per-head order; a scale of 1 / sqrt(nope): each is further
    from the reference than the tolerance on all gradients as one vector."""
    params, batch = mla_params(1), batch_of(1)
    if wrong == "no_latent_norm":
        norm = mla_reference._rms_norm
        monkeypatch.setattr(mla_reference, "_rms_norm", lambda x, g, eps: x * g if x.shape[-1] == MLA_MODEL["kv_lora_rank"] else norm(x, g, eps))
    elif wrong == "scale_of_nope":
        monkeypatch.setattr(mla_reference.np, "sqrt", lambda x: np.float64(x - 64) ** 0.5 if x == 80 else np.float64(x) ** 0.5)
    else:
        orders = mla_family.column_orders(MLA)
        key = "wq" if wrong == "rotate_half_unpermuted" else "wkv_b"
        monkeypatch.setattr(mla_family, "column_orders", lambda cfg: {**orders, key: np.arange(len(orders[key]))})
    _, got = mla_family.loss_and_grads(AzTrainer(MLA))(params, batch)
    want = jax.grad(mla_reference.loss)(params, batch, MLA_CONFIG)
    print("mla wrong", wrong, _all(got, want))
    assert _all(got, want) > 1.5 * MLA_GRAD_ALL_TOL, (wrong, _all(got, want))


def test_the_programs_column_order_against_a_hand_count():
    """2 heads, NoPE 2, RoPE 4, value 3, latent 5: ``program = published[..., order]``."""
    cfg = TrunkConfig(heads=2, kv_lora_rank=5, qk_nope_head_dim=2, qk_rope_head_dim=4, v_head_dim=3)
    orders = mla_family.column_orders(cfg)
    # published wq: head 0 = [n0 n1 | r0 r1 r2 r3] at 0..5, head 1 at 6..11; the pairs (r0, r1), (r2, r3) taken apart: r0 r2 | r1 r3
    assert list(orders["wq"]) == [0, 1, 6, 7, 2, 4, 3, 5, 8, 10, 9, 11]
    assert list(orders["wkv_a"]) == [0, 1, 2, 3, 4, 5, 7, 6, 8]  # the latent as it is, then the RoPE key's pairs taken apart
    assert list(orders["wkv_b"]) == [0, 1, 5, 6, 2, 3, 4, 7, 8, 9]  # published: head 0 = [k0 k1 | v0 v1 v2], head 1 the same at 5..9
    params = {"wq": jnp.arange(12.0)[None], "wkv_a": jnp.arange(9.0)[None], "wkv_b": jnp.arange(10.0)[None], "wo": jnp.arange(6.0)[None]}
    there = mla_family.to_program(cfg, params)
    assert list(np.asarray(there["wq"][0])) == list(orders["wq"]) and np.array_equal(there["wo"], params["wo"])
    back = mla_family.from_program(cfg, there)
    assert all(np.array_equal(back[k], params[k]) for k in params)


def test_mla_checkpoint_round_trips_and_the_older_blocks_files_still_load(tmp_path):
    trainer = AzTrainer(MLA)
    state, metrics = trainer.step(trainer.init(0), batch_of(0))
    assert 0.0 < float(metrics["latent_rms"]) < 10.0 and "held_slots" in metrics
    trainer.export(state, str(tmp_path / "mla.npz"))
    loaded = dict(np.load(tmp_path / "mla.npz"))
    assert az_config_from_params(loaded) == MLA  # heads and the latent's four widths from the shapes of wq, wkv_a, kv_norm, wkv_b, wo
    assert {"wkv_a", "kv_norm", "wkv_b"} < set(loaded) and not {"q_norm", "k_norm", "wk", "wv"} & set(loaded)
    assert bool(jnp.any(state.params["wkv_b"] != 0))  # a matrix, not a bias: initialised as one
    logits, value = jax.jit(lambda p, x: az_forward(p, x, MLA))(loaded, batch_of(0)["planes"])
    assert logits.shape == (BATCH, 4672) and bool(jnp.all(jnp.isfinite(value)))
    for older in (TINY, AFMOE):
        older_params = {**init_az_params(jax.random.PRNGKey(0), older), **init_az_buffers(older)}
        assert az_config_from_params(az_checkpoint(older_params, older)) == older
    with pytest.raises(ValueError, match="mismatched"):
        az_config_from_params({**loaded, "wkv_a": loaded["wkv_a"][..., :32]})  # no RoPE key beside the latent
    with pytest.raises(ValueError, match="missing"):
        az_config_from_params({k: v for k, v in loaded.items() if k != "kv_norm"})  # neither a latent nor q_norm and wk


def test_a_latent_refuses_what_the_code_does_not_compute():
    latent = dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16)
    assert TrunkConfig(heads=4, **latent).kv_lora_rank == 32
    for wrong in (dict(kv_heads=2), dict(gated_attention=True), dict(layers=2, nope_layers=(1,)), dict(qk_rope_head_dim=63),
                  dict(v_head_dim=0), dict(qk_nope_head_dim=0)):
        with pytest.raises(ValueError):
            TrunkConfig(heads=4, **{**latent, **wrong})


def _the_sixteen_shares_of_a_latent_layer_add_up():
    """One layer of the third block with all 128 experts, as the benchmark's
    reference computes it uncut (every expert on every token, the published
    column order), against the program's pieces put together as 16 chips
    would: the latent attention and the two shared experts (one feed-forward
    of twice the width) ONCE, and the routed parts of 16 shares of 8 experts,
    each routing over all 128 with top-6 and weights renormalised over all
    six chosen, held or not."""
    import dataclasses

    model = {**MLA_MODEL, "num_hidden_layers": 1, "num_dense_layers": 0, "num_experts": 128, "num_routed_experts": 128,
             "first_held_expert": 0, "num_experts_per_tok": 6, "moe_intermediate_size": 16}
    whole = dataclasses.replace(MLA, layers=1, dense_layers=0, dense_width=0, experts=128, experts_per_token=6, expert_width=16,
                                shared_width=32, held_experts=None)
    published = {k: jnp.asarray(v) for k, v in mla_reference.init_params(5, model).items()}
    planes = batch_of(5, 2)["planes"]
    same = lambda x: x
    want = mla_reference.features(published, planes, model, same, same).reshape(128, 64)

    params = mla_family.to_program(whole, published)
    latent, routed = trunk.trunk_plan(whole)
    layer = trunk.sublayer_params(params, routed)
    x = trunk._matmul(planes.reshape(128, 19), params["embed_w"]) + params["embed_b"]
    a = x + trunk._latent_attention(x, trunk.sublayer_params(params, latent), whole, latent)[0]  # every chip computes it alike: counted once
    n2 = trunk._rms_norm(a, layer.pop("moe_norm"), whole.rms_eps)

    def share(first):
        cfg = dataclasses.replace(whole, held_experts=(first, 8))
        held = {k: (v[first:first + 8] if k.startswith("experts_") else v) for k, v in layer.items()}
        mixed, counters = jax.jit(lambda n, l: trunk._experts(n, l, cfg, "layer00"))(n2, held)
        return mixed, counters["expert_slots"]

    parts = [share(first) for first in range(0, 128, 8)]
    final = lambda y: trunk._rms_norm(y, params["final_norm"], whole.rms_eps)
    shared = trunk._gated_ffn(n2, layer, "shared")
    total = final(a + shared + sum(mixed for mixed, _ in parts))
    print("mla shares", rel(total, want), rel(final(a + shared + parts[0][0]), want), rel(final(a + sum(mixed for mixed, _ in parts)), want))
    assert rel(total, want) < 0.02, rel(total, want)
    assert rel(final(a + shared + parts[0][0]), want) > 5 * rel(total, want)  # one share is not the layer
    assert rel(final(a + 2 * shared + sum(mixed for mixed, _ in parts)), want) > 5 * rel(total, want)  # nor the shared experts twice
    slots = parts[0][1]
    assert all(np.array_equal(s, slots) for _, s in parts)  # every share counts all 128 experts' slots alike
    assert float(slots.sum()) == 128 * 6 and sum(float(s[first:first + 8].sum()) for first, (_, s) in zip(range(0, 128, 8), parts)) == 128 * 6

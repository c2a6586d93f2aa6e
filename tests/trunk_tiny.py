"""What the trunk's test files share (``test_moe_trunk.py``, ``test_afmoe_trunk.py``,
``test_mla_trunk.py``, ``test_hybrid_trunk.py``, ``test_cca_trunk.py``, ``test_kda_trunk.py`` and
``tools/step_text.py``): the six blocks' tiny configurations, their
batches, the conditioned parameters, the plain formulas' norm and RoPE,
and the tolerances with the readings they were set from. No test lives
here: until PR 46 the first three blocks' 130 tests were one file, one
worker's 817 s of a 842 s run."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fishnet_tpu.models import trunk
from fishnet_tpu.models.trunk import TrunkConfig

TINY = TrunkConfig(hidden=64, heads=4, head_dim=16, layers=2, experts=8, experts_per_token=2,
                   expert_width=32, value_hidden=32)
BATCH = 8


def conditioned_params(seed: int, cfg: TrunkConfig = TINY):
    """Matrices normal(0, 0.9^2 / fan_in), gains and biases off their
    special points, a peaked router (logits spread ~3), so that a
    bfloat16 rounding that swaps a token's second and third expert swaps
    two small weights (benchmark/reference/moe_trunk.py says the same)."""
    rng = np.random.default_rng(seed)
    params = {}
    for name, shape in trunk.trunk_param_shapes(cfg).items():
        if name.endswith("_norm"):
            value = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("_b") and len(shape) == 1:
            value = 0.05 * rng.standard_normal(shape) + (0.5 if name == "value_fc2_b" else 0.0)
        else:
            fan_in = shape[-2]
            value = rng.standard_normal(shape) * (3.0 if name == "router_w" else 0.9) / np.sqrt(fan_in)
        params[name] = jnp.asarray(value, jnp.float32)
    return params


def batch_of(seed: int, n: int = BATCH):
    rng = np.random.default_rng(seed)
    planes = (rng.random((n, 8, 8, 19)) < 0.15).astype(np.float32)
    planes[..., 17] = rng.random((n, 1, 1)) * 0.5  # the halfmove plane is a fraction
    target = rng.gamma(0.3, size=(n, 4672)) * (rng.random((n, 4672)) < 0.01)
    target[:, 0] += 1e-3
    return {"planes": jnp.asarray(planes), "policy_target": jnp.asarray(target / target.sum(1, keepdims=True), jnp.float32),
            "value_target": jnp.asarray(rng.uniform(-1, 1, n), jnp.float32)}


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    half = x.shape[-1] // 2
    angle = np.arange(64)[:, None] / theta ** (np.arange(half) / half)[None, :]
    cos, sin = (jnp.asarray(np.concatenate([f(angle)] * 2, -1), jnp.float32)[None, :, None, :] for f in (np.cos, np.sin))
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def rel(got, want):
    return float(jnp.linalg.norm(jnp.asarray(got, jnp.float32) - want) / jnp.linalg.norm(want))



# Readings over seeds 1-5 (CPU): logits 0.008-0.018 of their norm, value 0.004-0.009 absolute; the wrong
# references read logits >= 0.088 (renormalised), >= 0.55 (unweighted), >= 0.45 (causal).
LOGITS_TOL, VALUE_TOL = 0.03, 0.03


# Readings over seeds 1-5: all tensors as one vector 0.032-0.077; the worst single tensor 0.14 (experts_up,
# seed 1) but for the policy head's bias and the value head's first layers, whose gradients are cancelling
# sums (<= 0.20). The wrong references read >= 0.22 (renormalised), >= 0.68 (unweighted), >= 0.64 (causal)
# as one vector.
GRAD_ALL_TOL, GRAD_TENSOR_TOL, GRAD_CANCELLING_TOL = 0.1, 0.25, 0.5
CANCELLING = ("policy_b", "value_w", "value_b", "value_fc1_w", "value_fc1_b")



def _all(got, want):
    return np.sqrt(sum(float(jnp.sum((got[k] - want[k]) ** 2)) for k in want) / sum(float(jnp.sum(want[k] ** 2)) for k in want))


# -- the second block (afmoe): a leading dense layer, grouped-query gated attention with RoPE and NoPE layers,
# -- a shared expert beside sigmoid-routed experts of which a share is held, four norms a layer -------------------

AFMOE = TrunkConfig(hidden=64, heads=4, head_dim=16, layers=3, experts=16, experts_per_token=4, expert_width=32,
                    rope_theta=10000.0, value_hidden=32, kv_heads=2, nope_layers=(2,), sliding_window=2048,
                    gated_attention=True, post_norms=True, embed_scale=8.0, dense_layers=1, dense_width=96,
                    shared_width=32, router_score="sigmoid", route_norm=True, route_scale=2.826,
                    held_experts=(4, 8), balance_rate=0.001)


# -- the third block: latent attention (Kanana-2's deepseek_v3 block); ``MLA_MODEL`` is the same net as the benchmark's
# -- reference reads it (benchmark/reference/mla_trunk.py) -------------------------------------------------------


MLA_MODEL = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 64, "v_head_dim": 16,
             "num_hidden_layers": 3, "num_dense_layers": 1, "intermediate_size": 96, "moe_intermediate_size": 32, "num_shared_experts": 2,
             "num_experts": 8, "num_routed_experts": 16, "first_held_expert": 4, "num_experts_per_tok": 3, "route_scale": 2.448,
             "load_balance_coeff": 0.001, "rope_theta": 1000000, "rms_norm_eps": 1e-06, "input_planes": 19, "value_hidden": 32, "policy_planes": 73}
MLA_CONFIG = {"model": MLA_MODEL, "train": {"value_weight": 1.0}}
MLA = TrunkConfig(hidden=64, heads=4, layers=3, experts=16, experts_per_token=3, expert_width=32, rope_theta=1e6, rms_eps=1e-6,
                  value_hidden=32, dense_layers=1, dense_width=96, shared_width=64, router_score="sigmoid", route_norm=True,
                  route_scale=2.448, held_experts=(4, 8), balance_rate=0.001, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=64, v_head_dim=16)

# -- the fourth block (nemotron_h; ``test_hybrid_trunk.py HYBRID_MODEL`` is the same net as its reference reads it) and the
# -- fifth (zaya; ``test_cca_trunk.py CCA_MODEL``) ----------------------------------------------------------------

HYBRID = TrunkConfig(hidden=84, heads=4, kv_heads=2, head_dim=16, qk_norm=False, pattern="MEMEM*E", experts=16, experts_per_token=3,
                     expert_width=232, gated_ffn=False, shared_width=58, rope_theta=1e4, rms_eps=1e-5, value_hidden=32,
                     mamba_heads=4, mamba_head_dim=8, mamba_groups=2, state_size=16, router_score="sigmoid", route_norm=True,
                     route_scale=2.5, held_experts=(4, 8), balance_rate=0.001)
CCA = TrunkConfig(hidden=128, heads=8, kv_heads=2, head_dim=8, layers=2, cca=(2, 2), rotary_dim=4, router_hidden=32, experts=16, experts_per_token=1,
                  expert_width=32, rope_theta=5e6, rms_eps=1e-5, value_hidden=32, held_experts=(4, 8), balance_rate=0.001)


# -- the sixth block (kimi_linear): the mixer told by layer, three Kimi Delta Attention layers to one latent layer without RoPE;
# -- ``KDA_MODEL`` is the same net as the benchmark's reference reads it (benchmark/reference/kda_trunk.py) -----------------------

KDA_MODEL = {"hidden_size": 64, "num_attention_heads": 2, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 64, "v_head_dim": 16,
             "mla_use_nope": True, "kda_num_heads": 2, "kda_head_dim": 16, "short_conv_kernel_size": 4, "mixers": ["kda", "kda", "kda", "latent", "kda"],
             "num_hidden_layers": 5, "num_dense_layers": 1, "intermediate_size": 96, "moe_intermediate_size": 32, "num_shared_experts": 1,
             "num_experts": 8, "num_routed_experts": 16, "first_held_expert": 4, "num_experts_per_tok": 3, "route_scale": 2.446,
             "load_balance_coeff": 0.001, "rope_theta": 10000, "rms_norm_eps": 1e-05, "input_planes": 19, "value_hidden": 32, "policy_planes": 73}
KDA_CONFIG = {"model": KDA_MODEL, "train": {"value_weight": 1.0}}
KDA = TrunkConfig(hidden=64, heads=2, experts=16, experts_per_token=3, expert_width=32, rope_theta=1e4, rms_eps=1e-5, value_hidden=32,
                  dense_layers=1, dense_width=96, shared_width=32, router_score="sigmoid", route_norm=True, route_scale=2.446,
                  held_experts=(4, 8), balance_rate=0.001, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16,
                  mixers=("kda", "kda", "kda", "latent", "kda"), nope_layers=(3,), kda_heads=2, kda_head_dim=16)


def board_batch(seed: int, n: int = BATCH):
    """Boards as the encoder writes them: at most ONE piece plane a square (the reference's router chooses on it, with a
    margin no rounding flips), castling planes a board, the halfmove fraction, the plane of ones."""
    rng = np.random.default_rng(seed)
    planes = np.zeros((n, 8, 8, 19), np.float32)
    kind = rng.integers(-14, 12, (n, 8, 8))  # over half the squares empty
    for piece in range(12):
        planes[..., piece] = kind == piece
    planes[..., 12:16] = rng.random((n, 1, 1, 4)) < 0.5
    planes[..., 17] = rng.random((n, 1, 1)) * 0.5
    planes[..., 18] = 1.0
    policy = rng.random((n, 4672)).astype(np.float32) ** 8
    return {"planes": jnp.asarray(planes), "policy_target": jnp.asarray(policy / policy.sum(-1, keepdims=True)),
            "value_target": jnp.asarray(rng.uniform(-1, 1, n).astype(np.float32))}


#: The six blocks by the names of their step pins (``test_hybrid_trunk.py PARENT_STEP_SHA256``, ``test_cca_trunk.py
#: CCA_STEP_SHA256``): the tiny configuration and the batch its pin lowers (``tools/step_text.py``).
BLOCKS = {"llada": (TINY, batch_of), "afmoe": (AFMOE, batch_of), "mla": (MLA, batch_of), "hybrid": (HYBRID, batch_of), "cca": (CCA, board_batch),
          "kda": (KDA, batch_of)}


# -- the seventh block (qwen3_next): three Gated DeltaNet layers (two value heads a key head) to one gated attention layer with part of a
# -- head rotated, a gated shared expert, zero-centred norms; ``GDN_MODEL`` is the same net as the benchmark's reference reads it
# -- (benchmark/reference/gdn_trunk.py) ---------------------------------------------------------------------------------------------

GDN_MODEL = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "rotary_dim": 4,
             "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 32, "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
             "mixers": ["gdn", "gdn", "gdn", "attention"], "num_hidden_layers": 4, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
             "num_experts": 8, "num_routed_experts": 16, "first_held_expert": 4, "num_experts_per_tok": 3, "load_balance_coeff": 0.001,
             "rope_theta": 10000000, "rms_norm_eps": 1e-06, "input_planes": 19, "value_hidden": 32, "policy_planes": 73}
GDN_CONFIG = {"model": GDN_MODEL, "train": {"value_weight": 1.0}}
GDN = TrunkConfig(hidden=64, heads=4, kv_heads=2, head_dim=16, experts=16, experts_per_token=3, expert_width=32, rope_theta=1e7, rms_eps=1e-6,
                  value_hidden=32, gated_attention=True, rotary_dim=4, shared_width=32, router_score="softmax", route_norm=True, held_experts=(4, 8),
                  balance_rate=0.001, mixers=("gdn", "gdn", "gdn", "attention"), linear_num_key_heads=2, linear_num_value_heads=4,
                  linear_key_head_dim=32, linear_value_head_dim=32, shared_token_gate=True, zero_centered_norms=True)
BLOCKS["gdn"] = (GDN, batch_of)


# -- the eighth block (mellum): grouped-query attention without a gate under TWO RoPE tables by layer kind (three sliding layers plain, the full
# -- layer YaRN with its attention factor), a renormalised softmax router over experts of which a share is held, no shared expert, no dense
# -- layer; ``MELLUM_MODEL`` is the same net as the benchmark's reference reads it (benchmark/reference/mellum_trunk.py). At a head of 16 and
# -- theta 1e4 an original context of 2,048 puts YaRN's ramp on pairs 2 to 6 of 8 (the published numbers put it on 18 to 35 of 64)

MELLUM_ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 16, "original_max_position_embeddings": 2048, "beta_fast": 32,
                                  "beta_slow": 1, "attention_factor": 1.2772588722239782},
               "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
MELLUM_MODEL = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4, "num_dense_layers": 0,
                "kept_layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"], "rope_parameters": MELLUM_ROPE,
                "sliding_window": 1024, "moe_intermediate_size": 32, "num_experts": 8, "num_routed_experts": 16, "first_held_expert": 4,
                "num_experts_per_tok": 3, "load_balance_coeff": 0.001, "rms_norm_eps": 1e-06, "input_planes": 19, "value_hidden": 32, "policy_planes": 73}
MELLUM_CONFIG = {"model": MELLUM_MODEL, "train": {"value_weight": 1.0}}
MELLUM = TrunkConfig(hidden=64, heads=8, kv_heads=2, head_dim=16, layers=4, experts=16, experts_per_token=3, expert_width=32, rope_theta=1e4, rms_eps=1e-6,
                     value_hidden=32, sliding_window=1024, router_score="softmax", route_norm=True, held_experts=(4, 8), balance_rate=0.001,
                     full_attention_layers=(3,), rope_type="yarn", rope_factor=16.0, original_max_position_embeddings=2048, beta_fast=32.0, beta_slow=1.0,
                     attention_factor=1.2772588722239782)
BLOCKS["mellum"] = (MELLUM, batch_of)


# -- the ninth block (sdar_moe): the eighth block's layer under ONE plain table, trained by block diffusion: a clean and a noised copy of every
# -- board under the three-part block mask, a mask embedding and a denoiser; ``SDAR_MODEL`` is the same net as the benchmark's reference reads it
# -- (benchmark/reference/sdar_trunk.py). Its batches carry their noise (``noised_batch``: the program's own maker, ``train/data.py block_noise``)

SDAR_MODEL = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2, "num_dense_layers": 0,
              "rope_theta": 1000000, "moe_intermediate_size": 32, "num_experts": 8, "num_routed_experts": 16, "first_held_expert": 4,
              "num_experts_per_tok": 3, "load_balance_coeff": 0.001, "rms_norm_eps": 1e-06, "input_planes": 19, "value_hidden": 32, "policy_planes": 73,
              "block_length": 4, "t_min": 0.001}
SDAR_CONFIG = {"model": SDAR_MODEL, "train": {"value_weight": 1.0, "denoise_weight": 1.0}}
SDAR = TrunkConfig(hidden=64, heads=8, kv_heads=2, head_dim=16, layers=2, experts=16, experts_per_token=3, expert_width=32, rope_theta=1e6, rms_eps=1e-6,
                   value_hidden=32, router_score="softmax", route_norm=True, held_experts=(4, 8), balance_rate=0.001, block_length=4)


def noised_batch(seed: int, n: int = BATCH, block_length: int = 4, t_min: float = 0.05):
    """``board_batch`` (one piece plane a square: a square has a class) with its noise. ``t_min`` 0.05, not the cell's 0.001: eight boards'
    512 squares are too few for a weight of 1,000 on one of them to be a term among many."""
    from fishnet_tpu.train.data import block_noise

    block_level, square_masked = block_noise(np.random.default_rng([seed, 0x6E]), n, block_length, t_min)
    return {**board_batch(seed, n), "block_level": jnp.asarray(block_level), "square_masked": jnp.asarray(square_masked)}


BLOCKS["sdar"] = (SDAR, noised_batch)


# -- the tenth block (ouro): two layers walked ``total_ut_steps`` = 3 times over the same weights, an exit (final norm, the two heads, a gate a board)
# -- after every pass; sandwich norms, attention at a group of one without qk-norm, EVERY feed-forward dense: the first trunk without a routed layer;
# -- ``OURO_MODEL`` is the same net as the benchmark's reference reads it (benchmark/reference/ouro_trunk.py)

OURO_MODEL = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2, "intermediate_size": 96,
              "rope_theta": 1000000, "rms_norm_eps": 1e-06, "total_ut_steps": 3, "early_exit_threshold": 1, "input_planes": 19, "value_hidden": 32,
              "policy_planes": 73}
OURO_CONFIG = {"model": OURO_MODEL, "train": {"value_weight": 1.0, "exit_entropy_weight": 0.1}}
OURO = TrunkConfig(hidden=64, heads=4, head_dim=16, layers=2, rope_theta=1e6, rms_eps=1e-6, value_hidden=32, qk_norm=False, post_norms=True,
                   dense_layers=2, dense_width=96, loop_steps=3)
BLOCKS["ouro"] = (OURO, batch_of)

"""Sparse-expert transformer trunk over the 64 squares of a board.

The second network family behind ``az_forward``: where ``models/az.py``
runs a convolution tower over the 8x8x19 planes, this runs a
bidirectional transformer over 64 tokens (one a square, 19 features
each) whose feed-forward is a routed mixture of experts, and ends in the
tower's own policy and value heads. ``TrunkConfig`` describes ten
published blocks as one code path at different values; no attention but
the ninth block's (block diffusion: its mask is what it trains under) has a
causal mask here, and a board is far shorter than any's window, so running
them over a board removes nothing.

The program reads a trunk as a LIST OF SUBLAYERS (``trunk_plan``, made once a configuration), each ``x <- x +
[post-norm](kind(norm(x)))``, and runs ONE loop over it (``trunk_forward_counted``). A kind is one function ``(x, p, cfg,
sublayer) -> (branch, counters by name)`` (a row of ``_KINDS``) and owns one row of ``_OWNS``, the table of stacked tensors
by kind that ``trunk_param_shapes``, the loop's slices (``sublayer_params``) and the checkpoint reader go by. Six kinds mix
tokens: ``attention`` (``_attention``), ``latent`` (``_latent_attention``: deepseek_v3's), ``cca`` (``_cca_attention``:
zaya's), ``mamba`` (``_mamba``), ``kda`` (``_kda``: kimi_linear's), ``gdn`` (``_gdn``: qwen3_next's); two are feed-forwards: ``dense`` (``_dense_layer``),
``routed`` (``_routed_layer``). A
layer of the first block is attention then routed, under ``attn_norm[i]`` and ``moe_norm[i]``; of the second, attention then
dense or routed, a post-norm each; of the third, latent then dense or routed; of the fifth, cca then routed; of the sixth, kda
or latent, as ``mixers[i]`` says, then dense or routed; of the seventh, gdn or attention, as ``mixers[i]`` says, then routed; of the eighth,
attention (turned by its layer kind's table, ``Sublayer.rope_type``) then routed; a layer of the
fourth is ONE of mamba, routed and attention, as ``pattern`` says, under ``layer_norm[i]``. A new token mixer is one
function, one row in each of the two tables, its shapes (``_kind_shapes``), its fields of ``TrunkConfig`` with their
refusal, and one helper of the checkpoint reader (``_SIZES``): nothing inside another kind's function.

The first block is LLaDA-MoE-7B-A1B's (inclusionAI, config.json: hidden
2048, 16 heads x 128, qk-norm, RoPE theta 50000, 64 experts top-8,
softmax router, expert width 1024, SiLU, RMSNorm eps 1e-5): every layer
the same. Layer equations (``n = RMSNorm(x; g, eps)``, statistics in
float32)::

    tokens   t = planes.reshape(B, 64, 19);  x = t @ W_in + b_in
    attention q, k, v = n1 @ W_q, n1 @ W_k, n1 @ W_v   (heads x head_dim)
             q, k <- RMSNorm over head_dim (one gain each), then RoPE
             (theta, rotate-half, all of head_dim) on the square index
             h = x + concat(softmax(q k^T / sqrt(head_dim)) v) @ W_o   (no mask)
    router   p = softmax(n2 @ W_r) over the experts, float32; the
             experts_per_token largest p are the weights w_j, NOT renormalised
    experts  E_e(u) = (silu(u @ W_g[e]) * (u @ W_u[e])) @ W_d[e]
             x' = h + sum_j w_j E_{e_j}(n2)        (dropless: no capacity)
    out      RMSNorm(x'; g_f) -> [B, 8, 8, hidden] -> the heads of models/az.py

The second block is Trinity-Mini's (arcee-ai, config.json, ``model_type``
afmoe: hidden 2048, 32 query heads over 4 key-value heads of 128, leading
dense layers of width 6144, then 128 routed experts of width 1024, top-8,
sigmoid scores, ``route_norm``, ``route_scale`` 2.826, one shared expert,
three sliding-window layers to one full-attention layer); what its
config.json does not say is the public afmoe modelling code's, listed
under ``assumed`` in ``benchmark/configs/trinity-mini-trunk-train.json``.
``N`` is RMSNorm, ``n`` the normed input, T tokens, 64 a board::

    embed     x = (t W_in + b_in) * embed_scale                 (sqrt(hidden): mup_enabled)
    layer     a = x + N_post_attn( Attn( N_in(x) ) )
              y = a + N_post_mlp( FFN( N_pre_mlp(a) ) )         (four norms a layer)
    Attn      q = n W_q [heads x head_dim];  k = n W_k, v = n W_v [kv_heads x head_dim];  g = n W_gate [heads x head_dim]
              q, k <- RMSNorm over head_dim, one gain each
              sliding layers: RoPE on the square index; full layers (``nope_layers``): none
              query head h attends key-value head h // (heads // kv_heads), within a board, scores / sqrt(head_dim)
              sliding layers mask |i - j| < sliding_window: all true at 64 tokens, so no mask is applied
              and a window under 64 is refused; softmax in float32
              out = ( (P v) * sigmoid(g) ) W_o
    FFN dense (silu(n W_g) * (n W_u)) W_d, width dense_width     (the leading ``dense_layers``)
    FFN MoE   s = sigmoid(n W_r) over all the experts, float32
              chosen = top-k of (s + b), b = expert_bias: a buffer, no gradient through b or the choice
              w_j = route_scale * s[e_j] / (sum_j s[e_j] + 1e-20)        (over all k chosen, held or not)
              out = Shared(n) + sum over chosen e_j HELD HERE of w_j E_{e_j}(n);  Shared, E_e: SiLU-gated
    balance   after a step, a routed layer's c_e = slots routed to expert e (all of them, held or not):
              d = balance_rate * sign(mean(c) - c);  b <- b + d - mean(d)      (``train/az_trainer.py``)
    out       N_final(y) -> the heads

The third block is Kanana-2-30B-A3B's (kakaocorp, config.json,
``model_type`` deepseek_v3 with ``q_lora_rank`` null: hidden 2048, 32
heads, keys and values through a 512-wide latent, a score of 128 NoPE +
64 RoPE columns over values of 128, a leading dense layer of 6144, then
128 routed experts of width 768, top-6, sigmoid scores, two shared
experts, ``routed_scaling_factor`` 2.448, RMSNorm eps 1e-6, RoPE theta
1e6 on interleaved pairs); what its config.json does not say is the
public DeepSeek-V3 modelling code's, listed under ``assumed`` in
``benchmark/configs/kanana-2-trunk-train.json``. H heads::

    embed     x = t W_in + b_in                                         (no scale)
    layer     a = x + Attn(N_in(x));   y = a + FFN(N_post_attn(a))      (two norms a layer, no post-norms)
    Attn      q      = n W_q                    [H x (nope + rope)];  q_nope, q_pe a head
              ckv    = n W_kva                  [rank + rope];  c = N_kv(ckv[:rank]; gain kv_norm),  k_pe = ckv[rank:]  (ONE for all heads)
              kv     = c W_kvb                  [H x (nope + value)];  k_nope, v a head
              q_pe, k_pe <- RoPE(theta, position = square index, interleaved pairs (2i, 2i + 1), all rope columns)
              s_h    = (q_nope_h k_nope_h^T + q_pe_h k_pe^T) / sqrt(nope + rope)      within a board, no mask, no qk-norm
              out    = concat_h( softmax(s_h) v_h ) W_o                               [H x value -> hidden]
    FFN       as the second block's: a leading dense layer; sigmoid scores, the choice on score + expert_bias, weights
              renormalised over all the chosen and scaled; Shared: ONE SiLU-gated feed-forward of width
              n_shared_experts x moe_intermediate_size beside the held experts; the same balance rule
    out       N_final(y) -> the heads

The latent is EXPANDED (k_nope and v are made from c for every head),
not absorbed into the query: absorbing trades the ``rank -> H x (nope +
value)`` product for scores and a mix over ``rank + rope`` = 576 columns
a head in place of 192 and 128, which pays where one query meets a long
cache of latents (decoding), and costs 3x-4.5x the core's products and
bytes where 64 queries meet 64 keys and every key is made once and used
once a head (a training step over a board).
The program keeps the columns of this block's projections in its own
order, which is a layout and not a departure: ``wq`` every head's NoPE
columns, then every head's RoPE columns, each head's interleaved pairs
taken apart into the halves that rotate-half turns
(``ops.board_attention.latent_column_order``; ``wkv_a``'s RoPE columns
likewise: a score is unchanged when q and k are permuted alike);
``wkv_b`` every head's key columns, then every head's value columns. A
checkpoint of the published per-head order is brought in by that
permutation (``benchmark/families/mla_trunk.py`` does it for the
reference's parameters and takes the gradients back).

The fourth block is Nemotron-Labs-TwoTower-30B-A3B's (nvidia,
config.json, ``model_type`` nemotron_h; the ONE tower that file declares:
hidden 2688, 52 layers by ``hybrid_override_pattern``, each ONE sublayer
under ONE norm: 23 Mamba-2 mixers of 64 heads x 64 with a state of 128 in
8 groups and a convolution of 4, 6 attention layers of 32 query heads
over 2 key-value heads of 128, 23 routed feed-forwards of 128 ungated
squared-ReLU experts of width 1856, top-6, sigmoid scores, one shared
expert of width 3712, ``routed_scaling_factor`` 2.5, RMSNorm eps 1e-5);
what its config.json does not say is Mamba-2's published mixer, listed
under ``assumed`` in ``benchmark/configs/nemotron-twotower-trunk-train.json``
(a second tower, its conditioning and block diffusion are named there as
LEFT OUT: no key defines them). ``TrunkConfig.pattern`` is the published
string, cut::

    embed     x = t W_in + b_in                                         (no scale)
    layer i   x <- x + Mixer_kind(i)( N_i(x) )                          kind(i) = pattern[i]: M, E or *; one norm, ``layer_norm[i]``
    M         [z | xBC | dt] = n W_inproj                               ``mamba_in`` [hidden, 2 x inner + 2 x groups x state + heads], inner = heads x P
              xBC <- silu( conv(xBC) ),  conv(u)[t] = b + sum_k w[:, k] u[t - (taps - 1) + k]     depthwise along a board's squares,
                                                                        nothing before square 0;  xBC = [x | B | C]
              D_t = softplus(dt_t + dt_bias) [heads];  a = -exp(A_log) [heads]
              head h (P columns of x), group g = h // (heads // groups) (``state`` columns of B and of C):
                S_t = exp(D_t a) S_{t-1} + D_t x_t B_t^T                S [P, state], zero before square 0 of every board
                y_t = S_t C_t + D_skip[h] x_t
              64 tokens are ONE chunk (the published chunk is 128), so the recurrence is exactly its dual form, which is computed:
                y_i = sum_{j <= i} exp(c_i - c_j) (C_i . B_j) D_j x_j + D_skip x_i,   c = cumsum(D a) along the board
              y <- N_grouped( y * silu(z); gain ``mamba_norm`` [inner], ``groups`` groups )
              out = y W_outproj                                         ``mamba_out`` [inner, hidden]
    *         the second block's attention without qk-norm, gate or post-norm: RoPE on every such layer, 16 query heads a key-value head
    E         the third block's router (sigmoid, the choice on score + expert_bias, weights renormalised over all k and scaled);
              E_e(u) = relu(u W_up[e])^2 W_down[e]: TWO products, no gate (``gated_ffn`` False); Shared the same at ``shared_width``
    out       N_final(x) -> the heads

Mechanism, the mixer: the projections and softplus are XLA's under
``layerNN.mamba``; the scan's core is one Pallas kernel pair
(``ops/board_scan.py``: ``board_scan``, ``board_scan_grad``) under
``layerNN.scan`` beside it, one B/C group and its heads of a few boards a
grid step, the decay from a cumulative sum made in the kernel, nothing
``[64, 64]`` or ``[.., heads, P]`` in HBM. The two float32 chains
between the projections and the scan are two more pairs under
``layerNN.mamba`` (``ops/mamba_mix.py``, which says how), each read once
and written once: the convolution with its silu, whose results ARE the
scan's three bfloat16 operands, and ``y * silu(z)`` under the grouped
norm, written once in the bfloat16 the out-projection reads.
``mamba_in`` is split on the weights' side, so the kernels' operands are
the products' results as they are.

Mechanism, widths no tile divides (``_whole_lanes``, ``_whole_rows``:
ONE rule, zeros inside the step, never a parameter): an expert width of
1,856 = 14.5 lane tiles is padded to 1,920 on the weights' side; a moved
row of 2,688 = 21 x 128 goes to dispatch as 3,072 and the combine's sum
is cut back. ``_tile`` hands Mosaic no tile that is not whole lanes.

The fifth block is ZAYA1-8B's (Zyphra, config.json, ``model_type`` zaya:
hidden 2048, 40 layers all ``hybrid``, 8 query heads over 2 key-value
heads of 128, ``cca_time0`` 2 and ``cca_time1`` 2, ``partial_rotary_factor``
0.5 at theta 5e6, 16 experts of width 2048, ONE a token, behind a router
that is an MLP of ``router_hidden_size`` 256; RMSNorm eps 1e-5, no
biases in attention). Compressed convolutional attention: queries live
in ``heads x head_dim`` = 1024 columns (hidden / 2), keys and values in
``kv_heads x head_dim`` = 256 (hidden / 8), and ALL of attention runs
there. What its config.json does not say is the family's two papers'
(arXiv:2510.04476, arXiv:2511.17127), listed under ``assumed`` in
``benchmark/configs/zaya1-trunk-train.json`` each with its basis (the
router state's mixing across layers, learned residual scales and a
sibling's mixture-of-depths are named there as LEFT OUT: no key sizes
them). ``TrunkConfig.cca`` is the two kernel sizes; ``t - 1`` is the
previous square of the same board, zero before square 0::

    embed     x = t W_in + b_in                                         (no scale)
    layer     a = x + Attn(N_a(x));   y = a + MoE(N_m(a))               (two norms a layer; no dense layer, no shared expert)
    Attn      q~ = n W_q [heads x head_dim];  k~ = n W_k [kv_heads x head_dim]
              v  = [ n_t W_v1 | n_{t-1} W_v2 ]                          a key-value head's first half from the token, its second half from
                                                                        the previous square (the value shift; ``wv1``, ``wv2`` [hidden, kv_heads x head_dim / 2])
              c  = conv1( conv0( [q~ | k~] ) )                          over the heads + kv_heads groups of head_dim columns side by side:
                   conv0 depthwise, ``cca[0]`` taps along the squares, causal, a bias (``conv0_w`` [columns, taps]: the last tap the token's own)
                   conv1 a head at a time, ``cca[1]`` taps, each a [head_dim, head_dim] matrix of its head, causal, a bias (``conv1_w``
                   [heads + kv_heads, taps, head_dim in, head_dim out])
              m_q[h] = (q~[h] + k~[h // group]) / 2;   m_k[g] = (mean over the group's heads of q~ + k~[g]) / 2       (the q-k mean)
              q = c_q + m_q;   k = c_k + m_k
              q[h] <- q[h] / rms(q[h]);   k[g] <- temp[g] * k[g] / rms(k[g])     a norm WITHOUT a gain over head_dim, float32, and the key's
                                                                        learned temperature a key-value head (``temp`` [kv_heads], 1 at first)
              RoPE (theta, rotate-half) on the FIRST ``rotary_dim`` columns of every head, the rest pass
              head h attends key-value head h // group within a board, no mask, scores / sqrt(head_dim), softmax float32
              out = concat_h(P v) W_o                                   ``wo`` [heads x head_dim, hidden]: narrower in than out
    MoE       r = n W_rd + b_rd [router_hidden];  h = gelu(r W_r1 + b_r1);  h = gelu(h W_r2 + b_r2)     (gelu by erf; float32 at ``highest``)
              s = softmax(h W_r3) over all the experts;   e = argmax(s + b), b = expert_bias: no gradient through b or the choice
              out = s[e] * E_e(n) if e is HELD HERE, else 0             the chosen expert's probability is the combine weight (a renormalised
                                                                        single weight would be 1 and leave the router no gradient); E_e SiLU-gated
    balance   the second block's rule;   out   N_final(y) -> the heads

Mechanism, the fifth block: the projections are two joined products,
``[W_q | W_k]`` and ``[W_v1 | W_v2]`` (one read of the normed stream
each). The value shift is a move of the second product's ROWS, not of
its input: ``n_{t-1} W`` is row ``t - 1`` of ``n W``, so nothing shifted
is ever multiplied (``_shifted_values``; the benchmark's reference
shifts the input, so the two do not share a derivation). Everything
between ``[q~ | k~]`` and the core is one Pallas kernel pair
(``ops/cca_mix.py``: ``cca_mix``, ``cca_mix_grad``) under ``layerNN.cca``
beside ``layerNN.attention``: ``[q~ | k~]`` is read once, q and k written
once, and nothing crosses a board. The norm, the temperature and RoPE
stay inside ``board_attention``, which is told ``g_q`` None beside a
gain a key-value head and ``rotary_dim``. At one expert a token a "sum
over a token's slots" is a select on the slot's mask (``_held_slots_sum``).

The sixth block is Kimi-Linear-48B-A3B's (moonshotai, config.json,
``model_type`` kimi_linear: hidden 2304, 27 layers, on 20 of them a Kimi
Delta Attention mixer of 32 heads x 128 behind a convolution of 4, on 7
(every fourth) latent attention of 32 heads WITHOUT RoPE
(``mla_use_nope``; ``kv_lora_rank`` 512, 128 + 64 score columns over
128-wide values), a leading dense layer of 9216, then 256 routed experts
of width 1024, top-8, sigmoid scores, one shared expert,
``routed_scaling_factor`` 2.446, RMSNorm eps 1e-5); what its config.json
does not say is the Kimi Linear report's (arXiv:2510.26692) and the
public layer's, listed under ``assumed`` in
``benchmark/configs/kimi-linear-trunk-train.json`` each with its basis.
``TrunkConfig.mixers`` is each layer's mixer by kind; H heads HELD (below),
d = ``kda_head_dim``, P = H d, a board's 64 squares in index order::

    embed     x = t W_in + b_in                                         (no scale)
    layer i   a = x + Mixer_i(N_in(x));   y = a + FFN_i(N_post(a))      Mixer_i = mixers[i]; two norms a layer; FFN as the third block's
    kda       q, k, v = silu(conv(n W_q)), silu(conv(n W_k)), silu(conv(n W_v))     ``kda_q``, ``kda_k``, ``kda_v`` [hidden, P]; conv depthwise
                                                                        along the squares, ``conv_kernel`` taps, causal, no bias (``kda_conv``
                                                                        [3 P, taps]: q's, k's, v's channels in turn, the last tap the token's own)
              q_h <- q_h / sqrt(|q_h|^2 + 1e-6) * d^-1/2;  k_h <- k_h / sqrt(|k_h|^2 + 1e-6)
              g = -exp(A_log[h]) * softplus((n W_fa) W_fb + dt_bias)    [T, H, d] float32: a log-decay a CHANNEL, alpha = exp(g); ``kda_fa``
                                                                        [hidden, d], ``kda_fb`` [d, P], ``kda_dt_bias`` [P], ``kda_A_log`` [H]
              beta = sigmoid(n W_b)                                     [T, H]; ``kda_beta`` [hidden, H]
              head h of board b, S [d, d] zero before square 0:
                S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T;   o_t = S_t^T q_t
              64 squares are ONE chunk from a zero state, so the recurrence is exactly its chunk form, which is computed
              (``ops/board_delta.py`` says how, and how no exponent in it is ever positive):
                c = cumsum(g);  Mk[t, j] = sum_c k_t k_j exp(c_t - c_j) (j < t);  Mq[t, j] = sum_c q_t k_j exp(c_t - c_j) (j <= t)
                U = (I + Diag(beta) Mk)^-1 (beta * V);   O = Mq U
              out = ( N_d(o; gain ``kda_o_norm`` [d]) * sigmoid((n W_ga) W_gb) ) W_o      ``kda_ga`` [hidden, d], ``kda_gb`` [d, P], ``kda_out`` [P, hidden]
    latent    the third block's, its 64 further score columns NOT rotated on the layers of ``nope_layers`` (the kernel pair under
              tables that turn nothing: ``board_attention`` told ``rotary_dim`` 0)
    out       N_final(y) -> the heads

Mechanism, the sixth block: q, k and v are ONE product on the joined
weights (one read of the normed stream) and ONE convolution with its silu,
the fourth block's kernel pair told three widths and a zero bias, whose
three bfloat16 results are the core's operands as they are; the l2 norms,
the cumulative sum, the six-level cut of the decayed products, the
triangular solve and ``Mq U`` are one Pallas kernel pair
(``ops/board_delta.py``: ``board_delta``, ``board_delta_grad``) under
``layerNN.delta``, one head of a few boards a grid step, no state in HBM;
a training step's forward kernel keeps the solve's ``T``, ``U`` and the two
score tables of every board and head (80 KB each, 160 MiB a layer at the
cell's batch) for its gradient kernel, which makes no solve of its own (the
forward without a gradient writes o alone), and each kernel is called under
one ``jax.jit``, so a program traces and lowers the pair once for all its
KDA layers; after the core the head norm under its gate, ``N_d(o) *
sigmoid(gate)``, is one more kernel pair under ``layerNN.kda``
(``ops/mamba_mix.py``: ``head_norm_gate``, ``head_norm_gate_grad``, told
``sigmoid``): it reads o as the core writes it and the gate's logits as
their product writes them, makes the sigmoid itself and writes what the
out-projection reads, so no ``[tokens, heads, d]`` view of o reaches HBM;
the low-rank gates' products, softplus, beta and the out-projection are
XLA's under ``layerNN.kda`` beside it.

The seventh block is Qwen3-Next-80B-A3B's (Qwen, config.json,
``model_type`` qwen3_next: hidden 2048, 48 layers, three Gated DeltaNet
mixers (arXiv:2412.06464) of 16 key heads and 32 value heads of 128 behind
a convolution of 4 to one gated attention layer of 16 query heads over 2
key-value heads of 256, RoPE theta 1e7 on the first 64 columns of a head;
every layer routed: 512 experts of width 512, softmax scores, top-10
renormalised, beside ONE shared expert of width 512 under a sigmoid gate a
token; RMSNorm eps 1e-6 with zero-centred gains); what its config.json
does not say is the public ``qwen3_next`` modelling code's and the Gated
DeltaNet paper's, listed under ``assumed`` in
``benchmark/configs/qwen3-next-trunk-train.json`` each with its basis. ``N``
is RMSNorm with a gain ``1 + w`` (``zero_centered_norms``: the parameter is
``w``, from zeros; ``trunk_forward_counted`` adds the 1 once, outside every
kind's function); K key heads, V value heads, d a head::

    embed     x = t W_in + b_in                                         (no scale)
    layer i   a = x + Mixer_i(N_1(x));   y = a + MoE(N_2(a))            Mixer_i = mixers[i]; two norms a layer; no dense layer
    gdn       [q | k | v | z] = n W_qkvz                                ``gdn_qkvz`` [hidden, 2 K d + 2 V d]: q's, k's, v's, z's columns in turn
              [b | a] = n W_ba                                          ``gdn_ba`` [hidden, 2 V]
              [q | k | v] <- silu(conv([q | k | v]))                    ONE depthwise causal convolution, ``conv_kernel`` taps, no bias (``gdn_conv``)
              beta = sigmoid(b);  g = -exp(A_log[h]) * softplus(a + dt_bias[h])      [T, V] float32: ONE log-decay a VALUE head and token
              q_h <- q_h / sqrt(|q_h|^2 + 1e-6) * d^-1/2;  k_h <- k_h / sqrt(|k_h|^2 + 1e-6);  value head h reads key head h // (V / K)
              S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T;   o_t = S_t^T q_t        S [d, d] a value head, zero before square 0
              out = ( N_d(o; plain gain ``gdn_o_norm`` [d]) * silu(z) ) W_out        ``gdn_out`` [V d, hidden]: the norm BEFORE the gate
    attention the second block's (``_attention`` with ``gated_attention``, ``_gated_out``) at a head of 256 under ``rotary_dim`` 64, no post-norm
    MoE       the first block's softmax router with ``route_norm``;  out = sigmoid(n w_s) * Shared(n) + sum over the chosen HELD of w_j E_j(n)
              ``shared_token_gate`` [hidden, 1]: float32, a sum over the hidden columns, no product
    out       N_final(y) -> the heads

Mechanism, the seventh block: ``gdn_qkvz`` is one tensor whose z columns
are split off on the weights' side (as ``mamba_in``'s: the convolution's
kernel reads its operand whole), the convolution is the fourth block's
kernel pair told three widths and a zero bias, and the core is the second
form of ``ops/board_delta.py``'s pair, told by ``g`` in ``beta``'s shape: q
and k at the key heads and g, beta ``[boards, 64, V]`` as the layer makes
them, nothing repeated a value head or broadcast a channel in HBM. After
the core, ``N_d(o) * silu(z)`` is the sixth block's kernel pair
(``head_norm_gate``, ``head_norm_gate_grad``) told ``silu``, under
``layerNN.gdn``: o as the core writes it and z as its product writes it in,
the out-projection's bfloat16 operand out, one pass each way.

The eighth block is Mellum2-12B-A2.5B's (JetBrains, config.json,
``model_type`` mellum: hidden 2304, 28 layers by ``layer_types``, three
``sliding_attention`` layers (window 1,024) to one ``full_attention``
layer, each 32 query heads over 4 key-value heads of 128 with qk-norm and
RoPE by ``rope_parameters``, a table a layer KIND: plain at theta 500,000 on
the sliding layers; YaRN, factor 16 over an original 8,192, beta 32 / 1,
``attention_factor`` 1.27726, on the full ones; every layer routed: 64
experts of width 896, softmax scores, top-8 renormalised, no shared expert,
no dense layer; RMSNorm eps 1e-6); what its config.json does not say is
the Qwen3-MoE family's block, whose keys it carries, listed under
``assumed`` in ``benchmark/configs/mellum2-trunk-train.json`` each with its
basis (a multi-token-prediction head is named there as LEFT OUT: no key
defines it). ``TrunkConfig.full_attention_layers`` are the layers of the
second kind; the window masks nothing on a board, as the second block's::

    embed     x = t W_in + b_in                                         (no scale)
    layer i   a = x + Attn_kind(i)(N_in(x));   y = a + MoE(N_post(a))   kind(i) = full_attention where i is among ``full_attention_layers``; two norms a layer
    Attn      the first block's at 8 query heads a key-value head: q, k <- RMSNorm over head_dim, one gain each, no gate, no bias, then RoPE,
              rotate-half over all of head_dim, position = square index, pair j of head_dim / 2, by the layer's kind:
                sliding_attention   f_j = theta^(-2j / head_dim);  cos, sin of position x f_j                    (``rope_tables``)
                full_attention      YaRN (``yarn_rope_tables``): c(b) = head_dim ln(original / (2 pi b)) / (2 ln theta);
                                    lo = floor(c(beta_fast)), hi = ceil(c(beta_slow))                            (18 and 35 as published)
                                    r_j = clip((j - lo) / (hi - lo), 0, 1);   f_j = (1 - r_j) theta^(-2j / head_dim) + r_j theta^(-2j / head_dim) / rope_factor
                                    cos, sin <- attention_factor x cos, sin: q AND k carry it, the layer's scores attention_factor^2 = 1.6314 times the plain ones'
              out = concat_h(softmax(q_h k_{h // 8}^T / sqrt(head_dim)) v_{h // 8}) W_o
    MoE       the first block's softmax router with ``route_norm`` (over ALL the chosen, held here or not), the choice on score + expert_bias
              (the seventh block's departure, for its reason); out = sum over the chosen HELD of w_j E_j(n), nothing beside them
    out       N_final(y) -> the heads

Mechanism, the eighth block: no kind, no tensor and no kernel of its own. A
layer kind's table is made on the host (float64, then float32, the sine
signed for rotate-half, the attention factor folded into both tables) and
handed to ``board_attention`` as ``tables``; the kernel pair reads cos and
sin as an operand and assumes nothing of their rows, so a scaled rotation is
turned forward and un-turned in the gradient (its transpose, ``d * cos +
turned(d * sin)``) by the same instructions as a plain one
(``tests/test_mellum_trunk.py`` holds the gradient to ``jax.vjp`` of the
float32 formula). The block's own cost is elsewhere: a moved row of 2,304 =
18 lane tiles goes as 3,072 (``_whole_rows``), and 8 of 64 experts at top-8
hold ONE slot a token on average, eight times the other shares' rows.

The ninth block is SDAR-30B-A3B-Chat's (JetLM, config.json, ``model_type``
sdar_moe: hidden 2048, 48 identical layers, 32 query heads over 4 key-value
heads of 128 with qk-norm, RoPE theta 1e6 without scaling, 128 experts of
width 768, softmax scores, top-8 renormalised, no shared expert, no dense
layer, no window; RMSNorm eps 1e-6): the eighth block's layer under ONE
plain table, and what it adds is no number of a layer but how the net is
TRAINED, generation by diffusion over blocks (SDAR, arXiv:2510.06303, trained
as BD3-LMs are, arXiv:2503.09573); what its config.json does not say is listed
under ``assumed`` in ``benchmark/configs/sdar-30b-a3b-trunk-train.json``.
``TrunkConfig.block_length`` L: a board's 64 squares in the trunk's order are
64 / L blocks, ``blk(s) = s // L``. A training batch carries the noise
(``train/data.py block_noise``): a level ``t_b`` a board and block, a mask
``m_s`` a square, masked with probability ``t_blk(s)``::

    two streams, one set of weights, 128 tokens a board: rows 0-63 the clean copy, 64-127 the noised one
    embed     x^c_s = t_s W_in + b_in;   x^n_s = t~_s W_in + b_in + m_s e_mask      t~_s: t_s with its 12 piece planes zeroed where m_s = 1 (the 7
                                                                        board-wide planes stay); ``mask_embed`` [hidden], learned
    layer     the eighth block's, on both streams alike: a = x + Attn(N_in(x));  y = a + MoE(N_post(a))
    Attn      q, k, v, qk-norm and RoPE as the eighth block's plain layers', position = SQUARE index in both copies; one softmax over the allowed keys:
                a clean query i sees the clean keys j with blk(j) <= blk(i), never a noised key
                a noised query i sees the noised keys j with blk(j) = blk(i) and the clean keys j with blk(j) < blk(i)
    MoE       a token of either stream: the eighth block's router and held experts
    out       N_final on both streams; the policy and value heads read the CLEAN stream; the denoiser reads the NOISED one:
              z_s = N_final(x^n_s) W_d + b_d  [13]: a square's class, empty or one of the 12 piece planes (``denoise_w``, ``denoise_b``)
    loss      policy + value_weight x value + denoise_weight x (1 / (boards x 64)) sum_s m_s (1 / t_blk(s)) CE(z_s, class_s)      (``train/az_trainer.py _loss``)
    served    (``trunk_forward``: no noise) the clean stream alone under its block-causal rule, 64 tokens a board: the training forward's
              clean stream exactly, which no noised token reaches

Mechanism, the ninth block: no kind and no tensor of a layer of its own.
``trunk_forward_counted`` told ``square_masked`` lays the two copies side by
side, ``[boards x 128, hidden]``, and everything a token at a time
(projections, norms, the router, the moves, the grouped products, the
combine) runs on 128 tokens a board as it runs on 64: nothing in the routed
path knows a board's length. The attention core alone has to know which rows
are which copy: ``Sublayer.streams`` tells ``_attention``, which hands
``board_attention`` the block length and the streams, and the core is a
kernel pair of its own (``ops/board_attention.py``: ``board_attention_blocks``,
``board_attention_blocks_grad``): a key-value head's k, both copies, normed,
turned and read once for the clean and the noised queries of its group, the
64 x 64 and 128 x 64 scores in VMEM, what the mask forbids taken out before
the softmax's maximum and sums, dk and dv of the clean copy summed over both
copies' queries before they are written.

The tenth block is Ouro-2.6B's (ByteDance, config.json, ``model_type``
ouro: hidden 2048, 48 identical layers all ``full_attention``, 16 query heads
over 16 key-value heads of 128, RoPE theta 1e6 without scaling, a SiLU-gated
feed-forward of 5632, RMSNorm eps 1e-6, no bias, no window, no router:
EVERY feed-forward is dense), and what it adds is no layer but how the stack
is RUN: ``total_ut_steps`` 4 times over the same weights, an exit at every
pass (arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language
Models"); what its config.json does not say is listed under ``assumed`` in
``benchmark/configs/ouro-2.6b-trunk-train.json``. ``TrunkConfig.loop_steps``
T, ``exit_threshold`` the published ``early_exit_threshold``::

    embed     h_0 = t W_in + b_in                                       (no scale)
    layer l   a = h + N2_l(Attn_l(N1_l(h)));   h' = a + N4_l(FFN_l(N3_l(a)))       four norms a layer (``post_norms``), plain gains; the weights
                                                                        of layer l are the same at every pass
    Attn      the first block's at a group of ONE without qk-norm: q, k, v = n W_q, n W_k, n W_v, rotate-half RoPE over all of head_dim,
              softmax(q k^T / sqrt(head_dim)) v within a board, then W_o
    FFN       (silu(n W_g) * (n W_u)) W_d at ``dense_width``            (``dense_layers == layers``: a trunk WITHOUT a routed layer)
    loop      h_t = (Layer_{L-1} o ... o Layer_0)(h_{t-1}),  t = 1..T
    exits     f_t = N_final(h_t) (one gain for all t);  (policy_t, value_t) = heads(f_t) (one set of head weights)
              g_t = mean over a board's 64 squares of (f_t w_g + b_g);  lambda_t = sigmoid(g_t)      ``exit_gate_w`` [hidden, 1], ``exit_gate_b`` [1]:
                                                                        ONE gate a board (the published gate is a token's; the loss it weighs
                                                                        here is a position's)
    exit distribution   p_1 = lambda_1;  p_t = lambda_t prod_{j<t} (1 - lambda_j), 1 < t < T;  p_T = prod_{j<T} (1 - lambda_j)      (``exit_log_distribution``)
    loss      mean over boards of [ sum_t p_t l_t - exit_entropy_weight H(p) ],  l_t = CE(policy_t) + value_weight (value_t - z)^2      (``train/az_trainer.py _loss``)
    served    (``trunk_forward``) a position's output is that of the first pass t with p_1 + ... + p_t >= exit_threshold, the last pass
              where none is (at the published 1.0: the last); all T passes are computed for a batch and a position's is selected

Mechanism, the tenth block: no kind, no tensor of a layer and no kernel of its
own. ``trunk_forward_counted`` walks the plan ``loop_steps`` times, a Python
loop whose passes read the SAME slices of the stacked tensors, so a weight's
gradient is autodiff's sum over its T uses and a scope ``layerNN.attention``
names a layer's T passes (a ``lax.scan`` over the passes would put ``while``
where the benchmark's scope table keeps a layer's name: PERF.md section 6, PR
64). The T streams are stacked ``[T, tokens, hidden]`` and the final norm, the
two heads and the gate run ONCE on all of them (one product on T x boards, not
T products); the forward returns heads ``[T, B, ..]`` and, after the counters,
the gate's logits ``[T, B]``. The gate is a float32 sum over the hidden columns
(no product), the distribution is made in log space (``log_sigmoid``), so no
``p_t`` is ever a product that underflows before its logarithm is taken.

**Held heads.** A mixer's head count (``heads``, ``kda_heads``) is the
heads HELD here, as ``held_experts`` is the experts': both mixers are sums
over heads (a KDA head's state, norm and gate are its own; the latent is
made once and every head reads it), so a chip that shares a layer's heads
computes its own heads' columns of what is made a head and their partial
sum through their ROWS of ``kda_out`` / ``wo``; the shares of all chips add
up to the branch (``tests/test_kda_trunk.py``). No exchange, no all-reduce,
nothing stands in for the absent heads; ``kda_fa``, ``kda_ga``,
``kda_o_norm`` and ``wkv_a`` with its norm are whole on every chip.

``held_experts = (first, count)`` tells the expert layer which experts
it holds, as one chip of an expert-parallel deployment does: it routes
over all ``experts``, computes the part of the result that its own give
for the tokens routed to them, and leaves the rest out; the shares of
all chips and the shared expert once add up to the whole layer
(``tests/test_afmoe_trunk.py``). Nothing stands in for the absent chips.

Mechanism, attention: the projections are XLA's; everything between
them (qk-norm, RoPE, the 64 x 64 scores, softmax, mix) is one Pallas
kernel pair (``ops/board_attention.py``: ``board_attention`` and its
gradient ``board_attention_grad``; told the norm or its absence, the
extent of RoPE, and whether the rotated key is one a key-value head or
one for all heads) that takes q, k, v as the projections write them and
works on one key-value head and its group of query heads of a few boards
at a time in VMEM: no ``[.., heads, head_dim]`` view and no scores reach
HBM, and the gradient recomputes the softmax from the same inputs. A
gated branch (``cfg.gated_attention``: the second block) ends in
``_gated_out``, one gradient rule round the gate and ``W_o``::

    gl = n1 W_gate;  gated = mixed * sigmoid(gl) (bfloat16);  out = gated W_o
    d_gated = bf16(d_out) W_o^T;  one pass over gl, d_gated, mixed -> d_mixed, d_gl, gated
    d W_o = gated^T d_out;  d W_gate = n1^T d_gl;  d_n1 = d_gl W_gate^T

so that every product reads and writes arrays, the sigmoid and its
gradient are made once in float32 between them, and what is kept is the
bfloat16 normed stream, ``mixed`` and the weights (its docstring has the
barriers and the readings that asked for each). One algorithm, its form
taken from the layer's own ``cfg.gated_attention`` at every size: the
ungated branch is its special case with nothing to hold out, ``mixed W_o``
as it always was.

Mechanism, the feed-forward every token passes (the leading dense layer,
the shared expert; ``_gated_ffn``): three XLA products round ``silu(gate)
* up`` or, past ``_FUSED_GATE_BYTES`` of gate weight (the dense layer of
width 6,144), ONE product on the joined weights, the experts' kernel
pair for the activation and its gradient, and plain bfloat16 operands
(``_gated_products``, which says why). One algorithm, its form taken
from what the function sees in its input: never from the layer's kind
or the family.

Mechanism, experts: the (token, slot) pairs are sorted by expert
(stable), each expert's rows form one group of a grouped matrix product
(megablox ``gmm``, a Pallas kernel: Mosaic on the TPU, the Pallas
interpreter on the CPU), the rows are put back in token order and each
token's slots summed under their weights. An expert is two grouped
products round one elementwise kernel: gate and up are ONE product on
the two weights joined along their columns, ``[slots, hidden] x [held,
hidden, 2 * width]``; ``silu(gate) * up`` is ``ops/expert_gate.py``'s
kernel pair (``expert_gate``, ``expert_gate_grad``); the down product
follows. A share's weights are ``[count, hidden, width]`` and the
products are given the held groups' sizes alone, the first ``count`` of
its sorted order (see below): megablox visits the tiles of the groups it
is given and nothing else, forward and in both gradients, so a slot of
an absent expert costs nothing and adds nothing, and every held expert
is dropless. The rows move through two more Pallas kernels
(``ops/row_move.py``): wherever a row is addressed singly it lives as
``[rows, hidden // 128, 128]``, one contiguous 4 KiB tile at hidden
2048, and one DMA moves it; the sorted side that the grouped products
read stays ``[slots, hidden]``. ``rows_out`` (tokens to sorted slots)
and ``rows_back`` (sorted slots to token order) are each other's
transpose, so ``_dispatch`` and ``_combine`` pair them as forward and
gradient and nothing is ever scatter-added. A share moves the rows of
its own experts alone: it sorts on ``(expert - first) mod experts``, so
they are rows ``[0, held)`` whatever ``first`` is, and hands ``held``
(the layer's own count, on the device) to every move and to the gate
kernels as their extent. The buffers keep their dropless shapes (``[N *
k, hidden]`` sorted, ``[N, k, hidden // 128, 128]`` in token order: any
routing fits, all slots held included); only the work follows the count:
on a share nothing from the router's choice to the sums over a token's k
costs by the slot count. The chosen scores are a one-hot select, not a
gather; the sums over a token's k are a third kernel (``rows_sum``) that
fetches the held places of the token-order view, one DMA a row, and
passes over nothing; the combine weights' gradient is made on the sorted
side, a row product inside the move that gathers the cotangent, and a
sort brings it to token order (``_combine``). Past ``held`` (rounded up
to a block) every sorted buffer and its cotangent, and the places of
absent experts' slots in the token-order view, are uninitialised and may
be NaN: who may read such a tail, and never a product, is
``ops/row_move.py``'s "The extent of a move". Matrix products run in
bfloat16 with float32 accumulation over float32 parameters, as the
tower's; norms, the softmaxes, the router's scores, choice and combine
weights, both sigmoid gates and the experts' gated activation are
float32.

Parameters are one flat dict (the ``.npz`` checkpoint format), the sublayers of a kind stacked on a leading axis:
``wq [attention layers, ..]``, ``dense_gate [dense_layers, ..]``, ``experts_gate [routed layers, held, hidden, width]``,
a pattern's ``layer_norm [layers, hidden]``. ``expert_bias [routed layers, experts]`` is a buffer beside them: the
forward reads it from the same dict when it is there, and no gradient reaches it.
"""

from __future__ import annotations

import functools
import itertools
import math
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as megablox_gmm, tgmm as megablox_tgmm

from fishnet_tpu.models.az_encoding import INPUT_PLANES, PIECE_PLANES
from fishnet_tpu.models.heads import policy_value_heads
from fishnet_tpu.ops.board_attention import SQUARES, board_attention, paired_heads, yarn_rope_tables
from fishnet_tpu.ops.board_delta import board_delta
from fishnet_tpu.ops.board_scan import board_scan
from fishnet_tpu.ops.cca_mix import cca_mix
from fishnet_tpu.ops.expert_gate import expert_gate, expert_gate_grad, gated_activation, squared_relu
from fishnet_tpu.ops.mamba_mix import _called, head_norm_gate, mamba_conv, mamba_gate_norm
from fishnet_tpu.ops.row_move import held_places, row_view, rows_back, rows_covered, rows_out, rows_out_dot, rows_sum

Params = Dict[str, jax.Array]

_INIT_STD = 0.02
#: What the ninth block's denoiser tells apart on a square: empty, or one of the 12 piece planes.
SQUARE_CLASSES = 1 + PIECE_PLANES
#: Mamba-2's ``time_step_min``, ``time_step_max`` and ``time_step_floor``: where a fresh mixer's steps lie (``init_trunk_params``).
_TIME_STEP_MIN, _TIME_STEP_MAX, _TIME_STEP_FLOOR = 0.001, 0.1, 1e-4
#: A fresh GDN head's rate ``exp(gdn_A_log)`` is uniform in (0, 16): this much over 0, so that no draw's logarithm is -inf.
_GDN_RATE_FLOOR = 1e-6


@dataclass(frozen=True)
class TrunkConfig:
    hidden: int = 2048
    heads: int = 16
    head_dim: int = 128
    layers: int = 1
    experts: int = 64
    experts_per_token: int = 8
    expert_width: int = 1024
    rope_theta: float = 50000.0
    rms_eps: float = 1e-5
    value_hidden: int = 256
    policy_planes: int = 73
    # What the second block adds (module docstring); the defaults are the first block's.
    kv_heads: Optional[int] = None  # None: one a query head
    nope_layers: Tuple[int, ...] = ()  # the full-attention layers: no RoPE
    sliding_window: Optional[int] = None  # of the other layers; masks nothing on a board
    gated_attention: bool = False
    post_norms: bool = False
    embed_scale: float = 1.0
    dense_layers: int = 0  # leading layers whose feed-forward is dense
    dense_width: int = 0
    shared_width: int = 0  # 0: no shared expert
    router_score: str = "softmax"  # or "sigmoid"
    route_norm: bool = False
    route_scale: float = 1.0
    held_experts: Optional[Tuple[int, int]] = None  # (first, count); None: all of them
    balance_rate: float = 0.0  # over 0: the routed layers choose on score + expert_bias
    recompute_experts: bool = False  # training keeps nothing of slot size for the backward pass (``_routed_recomputed``)
    # What the third block adds: keys and values through a latent (named after the published keys). None: the two blocks
    # above, and the three widths below are not read. With it ``head_dim`` is not read: a head's score is ``qk_nope_head_dim
    # + qk_rope_head_dim`` wide, RoPE on the second part alone, its value ``v_head_dim``; there is no qk-norm.
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # What the fourth block adds (module docstring). ``pattern``: a layer is ONE sublayer under ONE norm, of the kind its character
    # names (``M`` a Mamba-2 mixer, ``E`` a routed feed-forward, ``*`` attention); ``layers`` is then its length. None: the three
    # blocks above, every layer attention then a feed-forward, and the mixer's four sizes are not read.
    pattern: Optional[str] = None
    qk_norm: bool = True
    gated_ffn: bool = True  # False: relu(u W_up)^2 W_down, two products an expert, routed and shared alike
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_groups: int = 0  # B and C are one a group of mamba_heads // mamba_groups heads
    state_size: int = 0
    conv_kernel: int = 4
    # What the fifth block adds (module docstring). ``cca``: the kernel sizes of the two convolutions over queries and keys, and with
    # them the whole compressed form (the value shift, the q-k mean, ``qk_norm`` as the norm without a gain under a key temperature);
    # None: the four blocks above. ``rotary_dim``: RoPE on the first columns of a head (None: all of it). ``router_hidden``: the
    # width of the router's MLP (0: the one product ``n W_r``).
    cca: Optional[Tuple[int, int]] = None
    rotary_dim: Optional[int] = None
    router_hidden: int = 0
    # What the sixth block adds (module docstring). ``mixers``: each layer's token mixer by its kind, "kda" (Kimi Delta Attention:
    # ``kda_heads`` heads of ``kda_head_dim``, a convolution of ``conv_kernel``) or "latent" (the third block's, at ``heads``;
    # without RoPE where the layer is among ``nope_layers``), before a dense or routed feed-forward; None: ONE mixer for the
    # whole trunk, told by the fields above. A head count is the heads HELD here (the mixer's ``wo`` has their rows).
    mixers: Optional[Tuple[str, ...]] = None
    kda_heads: int = 0
    kda_head_dim: int = 0
    # What the seventh block adds (module docstring): ``mixers`` may instead name "gdn" (Gated DeltaNet: the four sizes below, named
    # after the published keys, and ``conv_kernel``) and "attention" (the first kind, at ``heads``, ``kv_heads``, ``head_dim``);
    # ``shared_token_gate``: the shared expert under ``sigmoid(n w)``, one gate a token; ``zero_centered_norms``: the gain of the two
    # layer norms, the q- and k-norms and the final norm is ``1 + w``, ``w`` the parameter, from zeros.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    shared_token_gate: bool = False
    zero_centered_norms: bool = False
    # What the eighth block adds (module docstring): RoPE by LAYER KIND, named after the published ``layer_types`` and ``rope_parameters``.
    # ``full_attention_layers``: the attention layers of the kind ``full_attention``, which turn by the table of ``rope_type`` ("yarn": the five
    # numbers below, the published ``factor`` as ``rope_factor``; ``ops.board_attention.yarn_rope_tables``) at ``rope_theta``; every other
    # attention layer (``sliding_attention``) turns by the plain table of ``rope_theta``, or by none where it is among ``nope_layers``.
    # (): one table a trunk, the seven blocks above, and the six fields after it are not read.
    full_attention_layers: Tuple[int, ...] = ()
    rope_type: str = "default"
    rope_factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    # What the ninth block adds (module docstring): block diffusion over a board. ``block_length``: the squares of a block; every attention layer is
    # then under the block mask (``ops.board_attention.block_mask``): the clean copy alone where the net is served, a clean and a noised copy where
    # it is trained (``trunk_forward_counted`` told ``square_masked``), and the trunk has a mask embedding and a third head, the denoiser. 0: the
    # eight blocks above, no mask.
    block_length: int = 0
    # What the tenth block adds (module docstring): the stack of layers run ``loop_steps`` times over the same weights (named after the published
    # ``total_ut_steps``), the final norm, the heads and an exit gate (``exit_gate_w``, ``exit_gate_b``) reading the stream after EVERY pass;
    # ``exit_threshold`` (the published ``early_exit_threshold``): what is served is a position's first pass at which the exit distribution's
    # cumulative sum reaches it. 1: the nine blocks above, one pass, no gate, and the threshold is not read. Its feed-forwards are all dense
    # (``dense_layers == layers``: no router, no expert), which any trunk of block layers may be.
    loop_steps: int = 1
    exit_threshold: float = 1.0

    def __post_init__(self) -> None:
        first, count = self.held
        latent = self.kv_lora_rank is not None
        pattern = self.pattern or ""
        cca = self.cca is not None
        mixers = tuple(self.mixers or ())
        gdn = (self.linear_num_key_heads, self.linear_num_value_heads, self.linear_key_head_dim, self.linear_value_head_dim)
        seventh = bool(mixers) and set(mixers) <= {"gdn", "attention"}
        yarn = (self.rope_factor, self.original_max_position_embeddings, self.beta_fast, self.beta_slow, self.attention_factor)
        full = self.full_attention_layers
        if mixers and self.layers in (1, len(mixers)):
            object.__setattr__(self, "layers", len(mixers))
        if pattern and self.layers in (1, len(pattern)):
            object.__setattr__(self, "layers", len(pattern))
        wrong = {
            f"pattern {self.pattern!r} is not a string of M (Mamba-2 mixer), E (routed feed-forward) and * (attention) with an E in it":
                self.pattern is not None and (not pattern or set(pattern) - set("ME*") or "E" not in pattern),
            f"pattern {self.pattern!r} names {len(pattern)} layers, not {self.layers}": bool(pattern) and self.layers != len(pattern),
            "a pattern's layers have one sublayer and one norm: no dense_layers (no E is dense), nope_layers, post_norms, gated "
            "attention or latent": bool(pattern) and bool(self.dense_layers or self.nope_layers or self.post_norms or self.gated_attention
                                                          or latent),
            f"an M layer wants mamba_heads, mamba_head_dim, mamba_groups, state_size and conv_kernel over 0 and whole groups, got "
            f"{(self.mamba_heads, self.mamba_head_dim, self.mamba_groups, self.state_size, self.conv_kernel)}":
                "M" in pattern and (min(self.mamba_heads, self.mamba_head_dim, self.mamba_groups, self.state_size, self.conv_kernel) < 1
                                    or self.mamba_heads % self.mamba_groups != 0),
            f"a latent of {self.kv_lora_rank} wants qk_nope_head_dim, qk_rope_head_dim and v_head_dim, got "
            f"{(self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)}":
                latent and min(self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim) < 1,
            f"qk_rope_head_dim {self.qk_rope_head_dim} is odd: RoPE turns pairs": latent and self.qk_rope_head_dim % 2,
            "latent attention has one key and value a query head from the latent (no kv_heads), no output gate and, but as a layer's own "
            "of mixers, RoPE on every layer (no nope_layers)":
                latent and (self.kv_heads is not None or self.gated_attention or (bool(self.nope_layers) and not mixers)),
            f"sliding_window {self.sliding_window} is under the {SQUARES} tokens of a board and the program applies no mask":
                self.sliding_window is not None and self.sliding_window < SQUARES,
            f"{self.heads} query heads do not divide over {self.kv_heads} key-value heads": self.heads % (self.kv_heads or self.heads),
            f"router_score {self.router_score!r} is neither softmax nor sigmoid": self.router_score not in ("softmax", "sigmoid"),
            f"held_experts {self.held_experts} is not a range of the {self.experts} experts":
                not (0 <= first and 1 <= count and first + count <= self.experts),
            f"{self.dense_layers} dense layers are not 0 to the {self.layers} layers, or have no width":
                not 0 <= self.dense_layers <= self.layers or (self.dense_layers > 0) != (self.dense_width > 0),
            f"nope_layers {self.nope_layers} are not layers": any(not 0 <= i < self.layers for i in self.nope_layers),
            f"cca {self.cca} is not two kernel sizes of 1 to {SQUARES}": cca and not (len(self.cca) == 2 and all(1 <= t <= SQUARES for t in self.cca)),
            "compressed convolutional attention wants kv_heads (its keys and values live in kv_heads x head_dim columns) and an even "
            "head_dim (half of a value head is the previous square's)": cca and (self.kv_heads is None or self.head_dim % 2 != 0),
            "compressed convolutional attention norms queries and keys without a gain under a key temperature (qk_norm), and has no "
            "latent, no pattern, no output gate, no post-norms and RoPE on every layer (no nope_layers)":
                cca and (not self.qk_norm or latent or bool(pattern) or self.gated_attention or self.post_norms or bool(self.nope_layers)),
            f"rotary_dim {self.rotary_dim} is not an even part of a head of {self.head_dim}, or stands beside a latent (whose RoPE "
            "columns are qk_rope_head_dim)": self.rotary_dim is not None and (latent or self.rotary_dim % 2 != 0
                                                                                  or not 0 < self.rotary_dim <= self.head_dim),
            f"router_hidden {self.router_hidden} is under 0": self.router_hidden < 0,
            f"mixers {self.mixers} are not {self.layers} of kda and latent, or of gdn and attention, or stand beside a pattern or cca (which tell "
            "the mixer themselves) or post_norms": bool(mixers) and (len(mixers) != self.layers or not (seventh or set(mixers) <= {"kda", "latent"})
                                                                     or bool(pattern) or cca or self.post_norms),
            "a latent among the mixers wants kv_lora_rank and its three widths": "latent" in mixers and not latent,
            f"a kda mixer wants kda_heads, kda_head_dim and conv_kernel over 0, got {(self.kda_heads, self.kda_head_dim, self.conv_kernel)}":
                "kda" in mixers and min(self.kda_heads, self.kda_head_dim, self.conv_kernel) < 1,
            f"a gdn mixer wants linear_num_key_heads, linear_num_value_heads, linear_key_head_dim, linear_value_head_dim and conv_kernel over 0, "
            f"whole groups of value heads a key head and one width for both (a state is [d, d]), got {(*gdn, self.conv_kernel)}":
                "gdn" in mixers and (min(*gdn, self.conv_kernel) < 1 or gdn[1] % gdn[0] != 0 or gdn[2] != gdn[3]),
            f"Gated DeltaNet's sizes {gdn} stand beside no gdn mixer": any(gdn) and "gdn" not in mixers,
            "gdn and attention mixers have no latent (kv_lora_rank)": seventh and latent,
            "shared_token_gate and zero_centered_norms are the seventh block's (mixers of gdn and attention), and the gate wants a shared "
            "expert (shared_width)": (self.shared_token_gate or self.zero_centered_norms) and not seventh
                                     or self.shared_token_gate and not self.shared_width,
            f"full_attention_layers {full} are not attention layers of the {self.layers} (a pattern's *, a layer of mixers that is attention)":
                any(not 0 <= i < self.layers or (pattern[i:i + 1] or "*") != "*" or (mixers[i:i + 1] or ("attention",)) != ("attention",) for i in full),
            "full_attention_layers turn by a table of their own: all of a head (no rotary_dim), a key a key-value head (no latent, no cca), and "
            f"turned at all (nope_layers {self.nope_layers} name none of them)":
                bool(full) and (self.rotary_dim is not None or latent or cca or bool(set(full) & set(self.nope_layers))),
            f"rope_type {self.rope_type!r} is neither default nor yarn, is told without full_attention_layers to turn by it, or is default "
            f"beside full_attention_layers {full}, which then turn as every other layer does (name none)":
                self.rope_type not in ("default", "yarn") or (self.rope_type != "default") != bool(full),
            f"yarn wants rope_factor of 1 or more, original_max_position_embeddings, beta_fast over beta_slow over 0 and attention_factor over 0, "
            f"got {yarn}": self.rope_type == "yarn" and not (self.rope_factor >= 1.0 and self.original_max_position_embeddings > 0
                                                              and self.beta_fast > self.beta_slow > 0.0 and self.attention_factor > 0.0),
            f"YaRN's numbers {yarn} stand beside rope_type default": self.rope_type == "default" and yarn != (1.0, 0, 32.0, 1.0, 1.0),
            f"block_length {self.block_length} does not divide the {SQUARES} squares of a board, or stands beside what the block-masked core does not "
            "compute: it is the first kind's attention on every layer (no latent, cca, pattern or mixers) with qk-norm and RoPE over all of a head on "
            "every layer under one table (no rotary_dim, nope_layers or full_attention_layers)":
                self.block_length != 0 and (not 0 < self.block_length <= SQUARES or SQUARES % self.block_length != 0 or latent or cca or bool(pattern)
                                            or bool(mixers) or not self.qk_norm or self.rotary_dim is not None or bool(self.nope_layers) or bool(full)),
            f"loop_steps {self.loop_steps} is under 1 (the stack runs at least once), or the loop stands beside what it is not computed under: a pattern "
            "(one sublayer a layer) or block_length (two streams under a mask)": self.loop_steps < 1 or self.loop_steps > 1 and (bool(pattern) or self.block_length != 0),
            f"exit_threshold {self.exit_threshold} is not over 0 and at most 1 (a cumulative probability), or stands beside no loop (loop_steps 1 has no "
            "exit to choose)": not 0.0 < self.exit_threshold <= 1.0 or self.exit_threshold != 1.0 and self.loop_steps == 1,
        }
        if any(wrong.values()):
            raise ValueError("; ".join(k for k, v in wrong.items() if v))

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts whose weights are here."""
        return self.held_experts or (0, self.experts)

    @property
    def routed_layers(self) -> int:
        return self.pattern.count("E") if self.pattern else self.layers - self.dense_layers

    @property
    def attention_layers(self) -> int:
        return self.pattern.count("*") if self.pattern else self.layers - sum(kind in ("kda", "gdn") for kind in self.mixers or ())


class Sublayer(NamedTuple):
    """One entry of a trunk's plan: ``x <- x + [post-norm](kind(norm(x)))``."""
    layer: str  # the prefix of its scopes, ``layerNN``
    kind: str  # a row of ``_OWNS`` and of ``_KINDS``
    index: int  # its row of its kind's stacked tensors: layers of a kind are indexed from the first of that kind
    norm: str  # the norm it reads: the tensor's name ...
    norm_index: int  # ... and its row there, which is its post-norm's too
    rope: bool = False  # an attention's: RoPE on its queries and keys
    post_norm: Optional[str] = None
    rope_type: str = "default"  # where it turns, WHICH table by: "default" the plain one of ``rope_theta``, "yarn" the ``full_attention`` layers' (``_attention``)
    streams: int = 1  # an attention's under ``block_length``: the copies of a board side by side along its rows, 2 where a noised copy follows the clean one


@functools.lru_cache(maxsize=None)
def trunk_plan(cfg: TrunkConfig, streams: int = 1) -> Tuple[Sublayer, ...]:
    """The trunk's sublayers in order: one a character of a pattern, each
    under ``layer_norm[i]``; else two a layer, a token mixer under
    ``attn_norm[i]`` (the layer's own of ``mixers``, or the one mixer of
    the whole trunk) and a feed-forward under ``moe_norm[i]``, dense in
    the leading ``dense_layers``. Everything between ``embed`` and
    ``final_norm`` reads this and not the fields it is made from.
    ``streams``: the copies of a board that ride side by side (2: the
    ninth block's training forward), which its attention sublayers are
    told and nothing else needs to know."""
    table = lambda i: cfg.rope_type if i in cfg.full_attention_layers else "default"  # the layer's kind's: full_attention or sliding_attention
    if cfg.pattern:
        kinds = [{"M": "mamba", "E": "routed", "*": "attention"}[kind] for kind in cfg.pattern]
        return tuple(Sublayer(f"layer{i:02d}", kind, kinds[:i].count(kind), "layer_norm", i, rope=kind == "attention", rope_type=table(i))
                     for i, kind in enumerate(kinds))
    mixer = "cca" if cfg.cca is not None else "latent" if cfg.kv_lora_rank is not None else "attention"
    mixers = cfg.mixers or (mixer,) * cfg.layers  # a layer's mixer is row ``index`` of its kind's tensors: its place among that kind's layers
    # the attention branch's post-norm is the first kind's alone (a latent beside ``post_norms`` holds ``post_attn_norm`` and never reads it)
    after_mixer, after_ffn = ("post_attn_norm" if mixer == "attention" else None, "post_mlp_norm") if cfg.post_norms else (None, None)
    plan = []
    for i in range(cfg.layers):
        ffn = ("dense", i) if i < cfg.dense_layers else ("routed", i - cfg.dense_layers)
        plan += [Sublayer(f"layer{i:02d}", mixers[i], mixers[:i].count(mixers[i]), "attn_norm", i, i not in cfg.nope_layers, after_mixer, table(i), streams),
                 Sublayer(f"layer{i:02d}", *ffn, "moe_norm", i, post_norm=after_ffn)]
    return tuple(plan)


#: THE table of the stacked tensors a kind of sublayer owns (its norms are the plan's): ``trunk_param_shapes`` makes these and
#: no other (checked there), the loop slices by it (``sublayer_params``; a row's order is its slices'), the checkpoint reader
#: requires a mixer's from it (``_SIZES``). ``expert_bias`` is a buffer (``trunk_buffer_shapes``) and is sliced alike.
_OWNS = {
    "mamba": ("mamba_in", "conv_w", "conv_b", "dt_bias", "A_log", "D_skip", "mamba_norm", "mamba_out"),
    "attention": ("wq", "wk", "wv", "q_norm", "k_norm", "wo", "wgate"),
    "latent": ("wq", "wkv_a", "kv_norm", "wkv_b", "wo"),
    "cca": ("wq", "wk", "wv1", "wv2", "conv0_w", "conv0_b", "conv1_w", "conv1_b", "temp", "wo"),
    "kda": ("kda_q", "kda_k", "kda_v", "kda_conv", "kda_fa", "kda_fb", "kda_dt_bias", "kda_A_log", "kda_beta", "kda_ga", "kda_gb", "kda_o_norm",
            "kda_out"),
    "gdn": ("gdn_qkvz", "gdn_ba", "gdn_conv", "gdn_dt_bias", "gdn_A_log", "gdn_o_norm", "gdn_out"),
    "dense": ("dense_gate", "dense_up", "dense_down"),
    "routed": ("router_w", "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up", "shared_down", "expert_bias",
               "router_down", "router_down_b", "router_w1", "router_w1_b", "router_w2", "router_w2_b", "router_w3", "shared_token_gate"),
}
_MIXERS, _FEED_FORWARDS = ("mamba", "attention", "latent", "cca", "kda", "gdn"), ("dense", "routed")
#: The norms whose gain is ``1 + w`` under ``zero_centered_norms`` (``centred_gains``); a GDN head's ``gdn_o_norm`` is a plain gain.
_ZERO_CENTERED = ("attn_norm", "moe_norm", "q_norm", "k_norm", "final_norm")
#: What the second block added to the first's file comes after the heads, in this order: ``init_trunk_params`` deals the
#: split of its rng out in the order of ``trunk_param_shapes``' keys, so the order is every seed's tensors.
_LATE = ("wgate", "post_attn_norm", "post_mlp_norm", "dense_gate", "dense_up", "dense_down", "shared_gate", "shared_up", "shared_down")


def _kind_shapes(cfg: TrunkConfig, kind: str, n: int) -> Dict[str, Tuple[int, ...]]:
    """The tensors that ``n`` stacked sublayers of ``kind`` own, by name."""
    h, inner, kv_inner = cfg.hidden, cfg.heads * cfg.head_dim, (cfg.kv_heads or cfg.heads) * cfg.head_dim
    if kind == "attention":
        norms = {"q_norm": (n, cfg.head_dim), "k_norm": (n, cfg.head_dim)} if cfg.qk_norm else {}
        gate = {"wgate": (n, h, inner)} if cfg.gated_attention else {}
        return {"wq": (n, h, inner), "wk": (n, h, kv_inner), "wv": (n, h, kv_inner), **norms, "wo": (n, inner, h), **gate}
    if kind == "latent":  # columns in the order ``_latent_attention`` reads them
        rank, nope, rope, value = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        return {"wq": (n, h, cfg.heads * (nope + rope)), "wkv_a": (n, h, rank + rope), "kv_norm": (n, rank),
                "wkv_b": (n, rank, cfg.heads * (nope + value)), "wo": (n, cfg.heads * value, h)}
    if kind == "cca":  # queries in ``inner`` columns, keys and values in ``kv_inner``; a value head's halves from two projections
        mixed, groups = inner + kv_inner, cfg.heads + cfg.kv_heads
        return {"wq": (n, h, inner), "wk": (n, h, kv_inner), "wv1": (n, h, kv_inner // 2), "wv2": (n, h, kv_inner // 2),
                "conv0_w": (n, mixed, cfg.cca[0]), "conv0_b": (n, mixed),
                "conv1_w": (n, groups, cfg.cca[1], cfg.head_dim, cfg.head_dim), "conv1_b": (n, mixed),
                "temp": (n, cfg.kv_heads), "wo": (n, inner, h)}
    if kind == "kda":  # q, k, v and the gates in ``heads x d`` columns; the two gates through a rank of one head's width
        d, p = cfg.kda_head_dim, cfg.kda_heads * cfg.kda_head_dim
        return {"kda_q": (n, h, p), "kda_k": (n, h, p), "kda_v": (n, h, p), "kda_conv": (n, 3 * p, cfg.conv_kernel),
                "kda_fa": (n, h, d), "kda_fb": (n, d, p), "kda_dt_bias": (n, p), "kda_A_log": (n, cfg.kda_heads), "kda_beta": (n, h, cfg.kda_heads),
                "kda_ga": (n, h, d), "kda_gb": (n, d, p), "kda_o_norm": (n, d), "kda_out": (n, p, h)}
    if kind == "gdn":  # q and k in ``key heads x d`` columns, v and z in ``value heads x d``; the convolution over q, k and v
        d, key, value = cfg.linear_key_head_dim, cfg.linear_num_key_heads * cfg.linear_key_head_dim, cfg.linear_num_value_heads * cfg.linear_value_head_dim
        return {"gdn_qkvz": (n, h, 2 * key + 2 * value), "gdn_ba": (n, h, 2 * cfg.linear_num_value_heads), "gdn_conv": (n, 2 * key + value, cfg.conv_kernel),
                "gdn_dt_bias": (n, cfg.linear_num_value_heads), "gdn_A_log": (n, cfg.linear_num_value_heads), "gdn_o_norm": (n, d), "gdn_out": (n, value, h)}
    if kind == "mamba":
        mixer, state = cfg.mamba_heads * cfg.mamba_head_dim, cfg.mamba_groups * cfg.state_size
        return {"mamba_in": (n, h, 2 * mixer + 2 * state + cfg.mamba_heads), "conv_w": (n, mixer + 2 * state, cfg.conv_kernel),
                "conv_b": (n, mixer + 2 * state), "dt_bias": (n, cfg.mamba_heads), "A_log": (n, cfg.mamba_heads),
                "D_skip": (n, cfg.mamba_heads), "mamba_norm": (n, mixer), "mamba_out": (n, mixer, h)}
    ffn = lambda name, width, *held: {f"{name}_gate": (n, *held, h, width), f"{name}_up": (n, *held, h, width), f"{name}_down": (n, *held, width, h)}
    if kind == "dense":
        shapes = ffn("dense", cfg.dense_width)
    else:
        rh = cfg.router_hidden
        router = {"router_w": (n, h, cfg.experts)} if not rh else {
            "router_down": (n, h, rh), "router_down_b": (n, rh), "router_w1": (n, rh, rh), "router_w1_b": (n, rh),
            "router_w2": (n, rh, rh), "router_w2_b": (n, rh), "router_w3": (n, rh, cfg.experts)}
        shapes = {**router, **ffn("experts", cfg.expert_width, cfg.held[1]), **(ffn("shared", cfg.shared_width) if cfg.shared_width else {})}
    shapes = {name: shape for name, shape in shapes.items() if cfg.gated_ffn or not name.endswith("_gate")}
    return {**shapes, "shared_token_gate": (n, h, 1)} if kind == "routed" and cfg.shared_token_gate else shapes


def trunk_param_shapes(cfg: TrunkConfig) -> Dict[str, Tuple[int, ...]]:
    """Every trained tensor of a trunk checkpoint by name: the plan's
    norms and what each kind in it owns, the tensors of a kind stacked
    over the sublayers of that kind, none where the plan has none. The
    ORDER of the keys is part of the result (``_LATE``)."""
    plan, h = trunk_plan(cfg), cfg.hidden
    shapes = {"embed_w": (INPUT_PLANES, h), "embed_b": (h,), **({"mask_embed": (h,)} if cfg.block_length else {})}
    for kinds in (_MIXERS, _FEED_FORWARDS):  # the token mixers' norm and tensors, then the feed-forwards'
        shapes.update({s.norm: (cfg.layers, h) for s in plan if s.kind in kinds and s.norm not in shapes})
        for kind in kinds:
            n = sum(s.kind == kind for s in plan)
            own = _kind_shapes(cfg, kind, n) if n else {}
            assert set(own) <= set(_OWNS[kind]), (kind, set(own) - set(_OWNS[kind]))
            shapes.update(own)
    if cfg.post_norms:
        shapes.update(post_attn_norm=(cfg.layers, h), post_mlp_norm=(cfg.layers, h))
    shapes.update({
        "final_norm": (h,),
        "policy_w": (1, 1, h, cfg.policy_planes), "policy_b": (cfg.policy_planes,),
        "value_w": (1, 1, h, 4), "value_b": (4,),
        "value_fc1_w": (4 * SQUARES, cfg.value_hidden), "value_fc1_b": (cfg.value_hidden,),
        "value_fc2_w": (cfg.value_hidden, 1), "value_fc2_b": (1,),
        **({"denoise_w": (h, SQUARE_CLASSES), "denoise_b": (SQUARE_CLASSES,)} if cfg.block_length else {}),
        **({"exit_gate_w": (h, 1), "exit_gate_b": (1,)} if cfg.loop_steps > 1 else {}),
    })
    return {**{name: shape for name, shape in shapes.items() if name not in _LATE}, **{name: shapes[name] for name in _LATE if name in shapes}}


#: The router MLP's matrices of fan-in ``router_hidden``: initialised by it, not at ``_INIT_STD``.
_ROUTER_HIDDEN = ("router_w1", "router_w2", "router_w3")
#: The fifth block's biases: a vector a layer, stacked (``init_trunk_params`` knows a bias by being a vector, and these are not).
_STACKED_BIASES = ("conv0_b", "conv1_b", "router_down_b", "router_w1_b", "router_w2_b")


def trunk_buffer_shapes(cfg: TrunkConfig) -> Dict[str, Tuple[int, ...]]:
    """What a training state holds beside the trained tensors, outside
    the optimizer: ``expert_bias`` where the routed layers balance."""
    return {"expert_bias": (cfg.routed_layers, cfg.experts)} if cfg.balance_rate else {}


def init_trunk_params(rng: jax.Array, cfg: TrunkConfig = TrunkConfig()) -> Params:
    """Normal(0, 0.02) matrices, unit norm gains, zero biases; the value
    head's last layer starts at zero, as the tower's. A Mamba-2 mixer's
    own tensors start as Mamba-2's: the decay rates ``exp(A_log)``
    uniform in [1, 16], the steps ``softplus(dt_bias)`` log-uniform in
    [0.001, 0.1] and at least 0.0001 (``dt_bias`` their inverse
    softplus), the direct term ``D_skip`` 1, the convolution uniform
    within 1 / sqrt(its taps) under a zero bias; a KDA mixer's rates
    ``exp(kda_A_log)``, steps ``softplus(kda_dt_bias)`` (a channel) and
    taps ``kda_conv`` alike; a GDN mixer's as the public layer resets
    them: rates ``exp(gdn_A_log)`` uniform in (0, 16), ``gdn_dt_bias``
    ones, the head norm's plain gain ones, the taps as the others; and
    under ``zero_centered_norms`` the five norms of ``_ZERO_CENTERED``
    start at ZERO (their gain is ``1 + w``). The fifth block's mix
    starts as a pass: ``conv0_w`` 1 at the token's own tap and 0 at the
    earlier ones, ``conv1_w`` the identity at the token's own tap, both
    biases and the router MLP's zero, the key temperature ``temp`` 1. The
    router MLP's matrices behind its down-projection (``router_w1``,
    ``router_w2``, ``router_w3``) are normal(0, 1 / fan-in): at 0.02 each
    of the three 256-wide layers shrinks what it is given to a sixth, all
    of a token's scores lie within 0.0002 of each other, and the balance
    rule's 0.001 a step then moves ALL of a layer's tokens from expert to
    expert every step (PERF.md section 6, PR 43)."""
    shapes = trunk_param_shapes(cfg)
    keys = dict(zip(shapes, jax.random.split(rng, len(shapes))))
    uniform = lambda name, low, high: jax.random.uniform(keys[name], shapes[name], jnp.float32, low, high)
    params: Params = {}
    for name, shape in shapes.items():
        if cfg.zero_centered_norms and name in _ZERO_CENTERED:
            params[name] = jnp.zeros(shape, jnp.float32)
        elif name == "gdn_A_log":
            params[name] = jnp.log(uniform(name, _GDN_RATE_FLOOR, 16.0))
        elif name.endswith("_norm") or name in ("D_skip", "temp", "gdn_dt_bias"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name == "conv0_w":  # [layers, columns, taps]: the last tap is the token's own
            params[name] = jnp.zeros(shape, jnp.float32).at[..., -1].set(1.0)
        elif name == "conv1_w":  # [layers, heads, taps, in, out]
            params[name] = jnp.zeros(shape, jnp.float32).at[:, :, -1].set(jnp.eye(shape[-1], dtype=jnp.float32))
        elif name in _ROUTER_HIDDEN:  # [layers, router_hidden, .]: by their fan-in (the docstring above says why)
            params[name] = jax.random.normal(keys[name], shape, jnp.float32) / math.sqrt(shape[-2])
        elif name in ("A_log", "kda_A_log"):
            params[name] = jnp.log(uniform(name, 1.0, 16.0))
        elif name in ("dt_bias", "kda_dt_bias"):
            step = jnp.maximum(jnp.exp(uniform(name, math.log(_TIME_STEP_MIN), math.log(_TIME_STEP_MAX))), _TIME_STEP_FLOOR)
            params[name] = step + jnp.log(-jnp.expm1(-step))
        elif name in ("conv_w", "kda_conv", "gdn_conv"):
            params[name] = uniform(name, -1.0, 1.0) / math.sqrt(shape[-1])
        # a bias is a vector (``wkv_b`` is a matrix), or the convolution's, a vector a mixer
        elif (name.endswith("_b") and len(shape) == 1) or name in ("value_fc2_w", "conv_b", *_STACKED_BIASES):
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = jax.random.normal(keys[name], shape, jnp.float32) * _INIT_STD
    return params


def _rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """bfloat16 operands, float32 accumulation and result."""
    return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), preferred_element_type=jnp.float32)


def _row_major(x: jax.Array) -> jax.Array:
    """``x`` and its cotangent pinned to the row-major layout. The
    residual stream starts with it: left to itself XLA lays
    ``[tokens, hidden]`` out tokens-minor or square-major (what the
    embedding's 19-deep product and the heads' convolutions like), and
    every projection next to a kernel, whose operands are row-major by
    contract, then re-tiles its operand or its result inside the
    product (4.2 ms a step at the published sizes, PERF.md section 6,
    PR 32). One pin is enough: the layout follows the stream."""
    return with_layout_constraint(x, Layout(major_to_minor=tuple(range(x.ndim))))


#: Bytes of a gated feed-forward's gate (or up) weight in float32, ``[hidden, width]``: its gradient's size, up to which
#: XLA's own fusion of ``silu x up`` and of its gradient into the products is kept. Read on a v5e inside the two share
#: cells' steps, 16,384 tokens (PERF.md section 6, PR 42): at 8 and 12 MiB (the shared experts, width 1,024 and 1,536)
#: XLA's form is 0.30 and 0.42 ms a layer faster than the kernel form, at 48 MiB (the dense layer, width 6,144) 15.9 and
#: 10.1 ms slower; nothing between was read, and 16 MiB is the VMEM an XLA fusion gets there. By the weight and not by the
#: tokens, so that a net takes one form at every batch: the benchmark's ``correct`` compares 64 boards at a time.
_FUSED_GATE_BYTES = 16 << 20


def _gated_ffn(n: jax.Array, p: Params, kind: str) -> jax.Array:
    """``(silu(n W_g) * (n W_u)) W_d`` on every token: the dense layer's
    feed-forward (``kind`` "dense") and the shared expert ("shared"). One
    algorithm in two forms by the size of its weights: XLA's fusion of
    the plain formula, or ``_gated_products``."""
    gate_w, up_w, down_w = p[f"{kind}_gate"], p[f"{kind}_up"], p[f"{kind}_down"]
    if gate_w.size * 4 > _FUSED_GATE_BYTES:
        return _gated_products(n, gate_w, up_w, down_w)
    return _matmul(jax.nn.silu(_matmul(n, gate_w)) * _matmul(n, up_w), down_w)


def _contract(x: jax.Array, y: jax.Array, x_axis: int, y_axis: int) -> jax.Array:
    """``_matmul`` over any one axis of each (a gradient's transposed products): bfloat16 operands as they are, float32 accumulation and result."""
    return jax.lax.dot_general(x, y, (((x_axis,), (y_axis,)), ((), ())), preferred_element_type=jnp.float32)


def _joined(gate_w: jax.Array, up_w: jax.Array) -> jax.Array:
    """``[W_g | W_u]`` in the bfloat16 cast the step makes anyway, as ``_expert_ffn`` joins the experts'."""
    return jnp.concatenate([gate_w.astype(jnp.bfloat16), up_w.astype(jnp.bfloat16)], axis=1)


@jax.custom_vjp
def _gated_products(n: jax.Array, gate_w: jax.Array, up_w: jax.Array, down_w: jax.Array) -> jax.Array:
    """The gated feed-forward as three products forward and three
    backward, every operand a bfloat16 ARRAY, the activation and its
    gradient made between them by the experts' kernel pair
    (``ops/expert_gate.py``)::

        gu = n [W_g | W_u]   float32 [tokens, 2 x width], ONE product; kept for the gradient
        h  = expert_gate(gu)           bfloat16, float32 arithmetic, rounded once
        out = h W_d                    float32
        d_h  = d_out W_d^T             bfloat16 (the cotangent of a bfloat16 operand)
        d_gu, h = expert_gate_grad(gu, d_h)   bfloat16 [tokens, 2 x width], written once, and ``h`` again
        d W_d = h^T d_out;  d [W_g | W_u] = n^T d_gu (its halves are the two gradients);  d_n = d_gu [W_g | W_u]^T

    Left to autodiff XLA makes no array of the activation's gradient: it
    fuses ``exp``, ``divide`` and eight multiplies over float32 ``gate``
    and ``up`` as a producer into each gradient product's operand, two to
    three times a step, with AdamW behind the same products, and at width
    6,144 those products ran at 1.9x to 3.5x their time at the bfloat16
    peak (PERF.md section 6, PR 42). One rule round the whole
    feed-forward because a ``custom_vjp`` has to return a float32
    cotangent for a float32 ``gu``, and ``d_gu`` is bfloat16: what each
    consuming product rounds it to anyway. The parameters, the checkpoint
    and the optimizer keep ``gate`` and ``up`` apart.

    What is kept for the gradient is the bfloat16 tokens and joined
    weights and ``gu``, not ``h``: the gradient kernel has ``h`` in
    registers and writes it again. The three ``optimization_barrier``s
    hold an array out of the products that read it: the tokens' cast
    (with the norm's two multiplies) and the weights' join, the
    cotangent's cast (with a post-norm's gradient behind it), and ``gu``
    itself, which a step compiled against the chip's memory otherwise
    remakes in the backward pass, 4.6 ms for its 805 MB, where remaking
    three attention products frees as much for less."""
    return _gated_products_fwd(n, gate_w, up_w, down_w)[0]


def _gated_products_fwd(n, gate_w, up_w, down_w):
    n, joined = jax.lax.optimization_barrier((n.astype(jnp.bfloat16), _joined(gate_w, up_w)))
    gu = _matmul(n, joined)
    h = expert_gate(gu, None, _interpret())
    return _matmul(h, down_w), (n, gu, joined, down_w)


def _gated_products_bwd(res, d_out):
    n, gu, joined, down_w = res
    d_out = jax.lax.optimization_barrier(d_out.astype(jnp.bfloat16))  # once, for its two products
    d_h = _contract(d_out, down_w.astype(jnp.bfloat16), 1, 1).astype(jnp.bfloat16)
    gu, d_h = jax.lax.optimization_barrier((gu, d_h))
    d_gu, h = expert_gate_grad(gu, d_h, None, _interpret(), with_h=True)
    d_gate_w, d_up_w = jnp.split(_contract(n, d_gu, 0, 0), 2, axis=1)
    return _contract(d_gu, joined, 1, 1), d_gate_w, d_up_w, _contract(h, d_out, 0, 0)


_gated_products.defvjp(_gated_products_fwd, _gated_products_bwd)


def _ffn(n: jax.Array, p: Params, kind: str, gated: bool) -> jax.Array:
    """The feed-forward every token passes, gated as the three blocks'
    or the fourth block's ``relu(n W_u)^2 W_d``."""
    return _gated_ffn(n, p, kind) if gated else _matmul(jnp.square(jax.nn.relu(_matmul(n, p[f"{kind}_up"]))), p[f"{kind}_down"])


def _dense_layer(x: jax.Array, p: Params, cfg: TrunkConfig, sublayer: Sublayer) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The dense kind: the gated feed-forward every token passes, under ``<layer>.dense``; it counts nothing."""
    with jax.named_scope(f"{sublayer.layer}.dense"):
        return _gated_ffn(_rms_norm(x, p[sublayer.norm], cfg.rms_eps), p, "dense"), {}


def _by_board(y: jax.Array, streams: int = 1) -> jax.Array:
    """``[tokens, columns]`` as the kernels take it, ``[boards, 64, columns]`` (``streams`` copies of a board side by side: ``64 x streams`` rows)."""
    return y.reshape(-1, SQUARES * streams, y.shape[-1])


def _mamba(x: jax.Array, p: Params, cfg: TrunkConfig, sublayer: Sublayer) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The mamba kind: [tokens, hidden] float32, 64 tokens a board -> a
    Mamba-2 mixer's output, same shape, and its two counters. All of it
    runs under ``<layer>.mamba`` but the scan's core (``board_scan``),
    under ``<layer>.scan`` beside it, never inside (module docstring,
    "Mechanism, the mixer"). ``mamba_in``'s columns are the published
    ``[z | x | B | C | dt]``, split on the weights' side as the latent
    projections are."""
    heads, groups, layer = cfg.mamba_heads, cfg.mamba_groups, sublayer.layer
    inner, state = heads * cfg.mamba_head_dim, groups * cfg.state_size
    with jax.named_scope(f"{layer}.mamba"):
        n = _rms_norm(x, p[sublayer.norm], cfg.rms_eps)
        w = p["mamba_in"]
        z, xbc, dt = _matmul(n, w[:, :inner]), _matmul(n, w[:, inner:2 * inner + 2 * state]), _matmul(n, w[:, 2 * inner + 2 * state:])
        xs, bs, cs = mamba_conv(_by_board(xbc), p["conv_w"], p["conv_b"], (inner, state, state), _interpret())
        step = jax.nn.softplus(_by_board(dt) + p["dt_bias"])  # time_step_limit (0, inf) clips nothing
        rate = -jnp.exp(p["A_log"])
        counted, decay = jax.lax.stop_gradient((step, rate))
        across = jnp.exp(jnp.sum(counted[:, 1:] * decay, axis=1))  # exp(c_63 - c_0) [boards, heads]
        counters = {"ssm_dt_mean": jnp.mean(counted), "ssm_decay_min": jnp.min(jnp.mean(across, axis=0))}
    with jax.named_scope(f"{layer}.scan"):
        y = board_scan(xs, bs, cs, step, rate, p["D_skip"], groups, _interpret())
    with jax.named_scope(f"{layer}.mamba"):
        y = mamba_gate_norm(y.reshape(x.shape[0], inner), z, p["mamba_norm"], groups, cfg.rms_eps, _interpret())
        return _matmul(y, p["mamba_out"]), counters


def _shifted_values(v12: jax.Array, kv_heads: int) -> jax.Array:
    """``[v1 | v2]`` [boards, 64, kv_heads x head_dim] float32 as the
    joined value product writes it (``wv1``'s columns, then ``wv2``'s,
    each key-value head's half of a head in turn) -> the values the core
    reads, bfloat16: a head's first half its own ``v1``, its second half
    the PREVIOUS square's ``v2``, zero at square 0. The shift is a move
    of the product's rows, not of its input: ``n_{t-1} W`` is row ``t -
    1`` of ``n W`` (a per-token projection commutes with it), so the
    normed stream is read once and never copied shifted."""
    boards, _, width = v12.shape
    half = width // (2 * kv_heads)
    v1, v2 = v12[..., :width // 2], jnp.pad(v12[:, :-1, width // 2:], ((0, 0), (1, 0), (0, 0)))
    halves = [y.reshape(boards, SQUARES, kv_heads, half) for y in (v1, v2)]
    return jnp.concatenate(halves, axis=-1).reshape(boards, SQUARES, width).astype(jnp.bfloat16)


def _attention(x: jax.Array, p: Params, cfg: TrunkConfig, sublayer: Sublayer) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The attention kind (the first, second and fourth blocks'):
    [tokens, hidden] float32, 64 tokens a board (``64 x sublayer.streams``
    under ``block_length``: a board's copies side by side, which the core
    alone is told) -> the branch's output,
    same shape, before its post-norm; it counts nothing. The projections
    are XLA's; everything between them is ``board_attention``; a gated
    branch's gate and out-projection are ``_gated_out``. As every
    kind's function it enters its own scopes (call it under none of a
    layer's): ``<layer>.attention``."""
    with jax.named_scope(f"{sublayer.layer}.attention"):
        n1 = _rms_norm(x, p[sublayer.norm], cfg.rms_eps)
        q, k, v = (_by_board(_matmul(n1, p[name]), sublayer.streams) for name in ("wq", "wk", "wv"))
        gains = dict(g_q=p["q_norm"], g_k=p["k_norm"]) if cfg.qk_norm else dict(g_q=None, g_k=None, head_dim=cfg.head_dim)
        masked = dict(block_length=cfg.block_length, streams=sublayer.streams) if cfg.block_length else {}  # the ninth block: which rows are which copy
        mixed = board_attention(q, k, v.astype(jnp.bfloat16), theta=cfg.rope_theta if sublayer.rope else None, eps=cfg.rms_eps,
                                interpret=_interpret(), rotary_dim=cfg.rotary_dim, tables=_yarn_tables(cfg) if sublayer.rope_type == "yarn" else None, **gains,
                                **masked)
        mixed = mixed.reshape(x.shape[0], -1)
        if cfg.gated_attention:
            return _gated_out(n1, mixed, p["wgate"], p["wo"]), {}
        return _matmul(mixed, p["wo"]), {}


def _yarn_tables(cfg: TrunkConfig) -> Tuple[np.ndarray, np.ndarray]:
    """The ``full_attention`` layers' tables: YaRN at ``rope_theta`` over all of a head, the attention factor in both (made once a such layer,
    for the kernel and its gradient alike: 64 x ``head_dim`` cosines on the host)."""
    return yarn_rope_tables(cfg.rope_theta, cfg.head_dim, cfg.rope_factor, cfg.original_max_position_embeddings, cfg.beta_fast, cfg.beta_slow,
                            cfg.attention_factor)


@jax.custom_vjp
def _gated_out(n1: jax.Array, mixed: jax.Array, gate_w: jax.Array, out_w: jax.Array) -> jax.Array:
    """The second block's gated out-projection, ``(mixed * sigmoid(n1
    W_gate)) W_o``, as products on ARRAYS, the elementwise work between
    them made once (``_gated_products``' method; ``n1`` the float32
    normed stream, ``mixed`` bfloat16 as the kernel wrote it)::

        gl    = n1 W_gate              float32 from the product
        gated = mixed * sigmoid(gl)    bfloat16, float32 arithmetic, rounded once
        out   = gated W_o              float32
        d_out -> bfloat16              once, for its two products (the post-norm's gradient when the layer has one)
        d_gated = d_out W_o^T          bfloat16 (the cotangent of a bfloat16 operand)
        s = sigmoid(gl);  d_mixed = d_gated * s;  d_gl = d_gated * mixed * s (1 - s);  gated again: ONE pass, three bfloat16 arrays
        d W_o = gated^T d_out;  d W_gate = n1^T d_gl;  d_n1 = d_gl W_gate^T   float32

    Left to autodiff XLA wrote the sigmoid as a float32 ``[tokens,
    inner]`` array, multiplied ``W_o``'s input gradient by it behind the
    product (two results) and made the sigmoid's gradient inside the
    operand of ``W_gate``'s input gradient from three ``[tokens, inner]``
    arrays, two of them float32: 3.18 and 3.40 ms a layer at ``[16384,
    4096]`` against 1.40 at the bfloat16 peak, now 1.45 and 2.41, the
    second with the sum of the four input gradients and the input norm's
    reduces still behind it (PERF.md section 6, PR 50). No rounding is
    added or moved: each array is rounded where the default-precision
    product that reads it rounded it.

    What is kept for the gradient is the bfloat16 normed stream,
    ``mixed`` and the weights: ``gl`` is asked for again, and whether the
    forward's is held or the product made again is XLA's to settle
    against the chip's memory (the cell's step holds it and remakes
    ``W_q``'s product instead: as many products as before, five fewer
    remade). Each ``optimization_barrier`` holds an array out of the
    products that read it: ``mixed`` as ``[tokens, inner]`` bfloat16
    (without it the cast to float32 moves before the reshape, where
    nothing fuses it: a 0.65 ms pass of its own, forward and again
    backward); ``gated``, so that ``W_o``'s product has no sigmoid in
    its operand (2.05 ms against 1.76) and the gate lands where XLA puts
    it cheapest, behind ``gl``'s product as its consumer (1.48 ms against
    1.43 alone: held out as a pass of its own it is 0.77 more); ``d_out``'s
    cast, with the post-norm's gradient behind it; ``gl`` and ``d_gated``
    before the pass; the pass's three results. The forward and the
    gradient each sit under one ``jax.jit`` on the chip (bare under the
    interpreter, ``mamba_mix._called``), so a step's gated layers share
    one trace and one lowering of each."""
    return _gated_out_fwd(n1, mixed, gate_w, out_w)[0]


@jax.jit
def _gated_out_forward(n1, mixed, gate_w, out_w):
    mixed = jax.lax.optimization_barrier(mixed)
    gated = (mixed.astype(jnp.float32) * jax.nn.sigmoid(_matmul(n1, gate_w))).astype(jnp.bfloat16)
    return _matmul(jax.lax.optimization_barrier(gated), out_w)


@jax.jit
def _gated_out_gradient(n1, mixed, gate_w, out_w, d_out):
    d_out = jax.lax.optimization_barrier(d_out.astype(jnp.bfloat16))  # once, for its two products
    d_gated = _contract(d_out, out_w.astype(jnp.bfloat16), 1, 1).astype(jnp.bfloat16)
    gl, d_gated, mixed = jax.lax.optimization_barrier((_matmul(n1, gate_w), d_gated, mixed))
    s, mixed, d_gated = jax.nn.sigmoid(gl), mixed.astype(jnp.float32), d_gated.astype(jnp.float32)
    passed = (d_gated * s, d_gated * mixed * (s * (1.0 - s)), mixed * s)
    d_mixed, d_gl, gated = jax.lax.optimization_barrier(tuple(y.astype(jnp.bfloat16) for y in passed))
    return _contract(d_gl, gate_w.astype(jnp.bfloat16), 1, 1), d_mixed, _contract(n1, d_gl, 0, 0), _contract(gated, d_out, 0, 0)


def _gated_out_fwd(n1, mixed, gate_w, out_w):
    n1 = n1.astype(jnp.bfloat16)
    return _called(_gated_out_forward, _interpret())(n1, mixed, gate_w, out_w), (n1, mixed, gate_w, out_w)


def _gated_out_bwd(res, d_out):
    return _called(_gated_out_gradient, _interpret())(*res, d_out)


_gated_out.defvjp(_gated_out_fwd, _gated_out_bwd)


def _latent_attention(x: jax.Array, p: Params, cfg: TrunkConfig, sublayer: Sublayer) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The latent kind (the third block's), as ``_attention``, and the
    latent's root mean square before its norm. The way from the normed
    stream through the latent to the keys and values runs under
    ``<layer>.latent`` beside ``<layer>.attention``, so that the two add
    up to the branch. The columns (module docstring) are split on the
    weights' side, where a slice costs nothing (it joins the bfloat16
    cast): the kernels' operands are the products' results as they are."""
    layer, split, rank = sublayer.layer, cfg.heads * cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope(f"{layer}.attention"):
        n1 = _rms_norm(x, p[sublayer.norm], cfg.rms_eps)
        q, q_pe = _matmul(n1, p["wq"][:, :split]), _matmul(n1, p["wq"][:, split:])
    with jax.named_scope(f"{layer}.latent"):
        ckv, k_pe = _matmul(n1, p["wkv_a"][:, :rank]), _matmul(n1, p["wkv_a"][:, rank:])
        latent_rms = jnp.sqrt(jnp.mean(jax.lax.stop_gradient(ckv) ** 2))
        c = _rms_norm(ckv, p["kv_norm"], cfg.rms_eps)
        k, v = _matmul(c, p["wkv_b"][:, :split]), _matmul(c, p["wkv_b"][:, split:]).astype(jnp.bfloat16)
    with jax.named_scope(f"{layer}.attention"):
        mixed = board_attention(_by_board(q), _by_board(k), _by_board(v), None, None, cfg.rope_theta, cfg.rms_eps, _interpret(),
                                q_pe=_by_board(q_pe), k_pe=_by_board(k_pe), rotary_dim=None if sublayer.rope else 0)
        return _matmul(mixed.reshape(x.shape[0], -1), p["wo"]), {"latent_rms": latent_rms}


def _cca_attention(x: jax.Array, p: Params, cfg: TrunkConfig, sublayer: Sublayer) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The cca kind (the fifth block's), as ``_attention``, and the mix's
    two counters. Two joined products, each one read of the normed
    stream: [W_q | W_k], whose result ``[boards, 64, (heads + kv_heads) x
    head_dim]`` the mix takes as it is, and [W_v1 | W_v2]. The mix of
    queries and keys (``ops/cca_mix.py``, one kernel pair: conv0
    depthwise along the squares, conv1 a head at a time with bfloat16
    operands, the q-k mean; it also sums the squares of what the
    convolutions changed and of their input, no gradient) and the move of
    the values run under ``<layer>.cca`` beside ``<layer>.attention``."""
    layer = sublayer.layer
    with jax.named_scope(f"{layer}.attention"):
        n1 = _rms_norm(x, p[sublayer.norm], cfg.rms_eps)
        qk = _by_board(_matmul(n1, jnp.concatenate([p["wq"], p["wk"]], axis=1)))
        v12 = _by_board(_matmul(n1, jnp.concatenate([p["wv1"], p["wv2"]], axis=1)))
    with jax.named_scope(f"{layer}.cca"):
        q, k, sums = cca_mix(qk, p["conv0_w"], p["conv0_b"], p["conv1_w"], p["conv1_b"], cfg.heads, cfg.kv_heads, _interpret())
        changed = sums[0] / sums[1]  # the mean square of what the convolutions changed over that of what they were given
        v = _shifted_values(v12, cfg.kv_heads)
    with jax.named_scope(f"{layer}.attention"):
        temp = jnp.broadcast_to(p["temp"][:, None], (cfg.kv_heads, cfg.head_dim))  # a gain a key-value head, the same on its columns
        mixed = board_attention(q, k, v, None, temp, cfg.rope_theta, cfg.rms_eps, _interpret(), rotary_dim=cfg.rotary_dim)
        counters = {"cca_conv_share": jnp.sqrt(changed), "cca_temp_max": jnp.max(jax.lax.stop_gradient(p["temp"]))}
        return _matmul(mixed.reshape(x.shape[0], -1), p["wo"]), counters


def _kda(x: jax.Array, p: Params, cfg: TrunkConfig, sublayer: Sublayer) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The kda kind (the sixth block's): [tokens, hidden] float32, 64
    tokens a board -> a Kimi Delta Attention mixer's output, same shape,
    and its two counters. All of it runs under ``<layer>.kda`` but the
    delta rule's core (``board_delta``, which also makes the l2 norms of
    q and k), under ``<layer>.delta`` beside it, never inside (module
    docstring, "Mechanism, the sixth block"). q, k and v are ONE product
    on the joined weights and ONE convolution with its silu (the fourth
    block's kernel pair, told three widths and a zero bias), whose three
    bfloat16 results are the core's operands as they are; the head norm
    under the sigmoid of the low-rank gate is ``head_norm_gate``, which
    takes the gate's logits."""
    heads, d, layer, tokens = cfg.kda_heads, cfg.kda_head_dim, sublayer.layer, x.shape[0]
    inner = heads * d
    with jax.named_scope(f"{layer}.kda"):
        n = _rms_norm(x, p[sublayer.norm], cfg.rms_eps)
        qkv = _by_board(_matmul(n, jnp.concatenate([p["kda_q"], p["kda_k"], p["kda_v"]], axis=1)))
        q, k, v = mamba_conv(qkv, p["kda_conv"], jnp.zeros((3 * inner,), jnp.float32), (inner, inner, inner), _interpret())
        step = jax.nn.softplus(_matmul(_matmul(n, p["kda_fa"]), p["kda_fb"]) + p["kda_dt_bias"])
        g = step * jnp.repeat(-jnp.exp(p["kda_A_log"]), d)  # a log-decay a channel: alpha = exp(g)
        beta = jax.nn.sigmoid(_matmul(n, p["kda_beta"]))
        kept, written = jax.lax.stop_gradient((g, beta))
        counters = {"kda_state_kept": jnp.mean(jnp.exp(kept)), "kda_beta": jnp.mean(written)}
    with jax.named_scope(f"{layer}.delta"):
        o = board_delta(q, k, v, _by_board(g), _by_board(beta), _interpret())
    with jax.named_scope(f"{layer}.kda"):
        gate = _matmul(_matmul(n, p["kda_ga"]), p["kda_gb"])  # the logits: the sigmoid is the kernel's
        normed = head_norm_gate(o.reshape(tokens, inner), gate, p["kda_o_norm"], "sigmoid", cfg.rms_eps, _interpret())  # a head's own norm, one gain for all heads
        return _matmul(normed, p["kda_out"]), counters


def _gdn(x: jax.Array, p: Params, cfg: TrunkConfig, sublayer: Sublayer) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The gdn kind (the seventh block's): [tokens, hidden] float32, 64
    tokens a board -> a Gated DeltaNet mixer's output, same shape, and its
    two counters. All of it runs under ``<layer>.gdn`` but the delta
    rule's core (``board_delta``'s second form, which also makes the l2
    norms of q and k), under ``<layer>.delta`` beside it, never inside
    (module docstring, "Mechanism, the seventh block"). ``gdn_qkvz``'s
    columns are ``[q | k | v | z]``: z is split off on the weights' side,
    q, k and v are ONE product and ONE convolution with its silu whose
    three bfloat16 results (q and k at the key heads, v at the value
    heads) are the core's operands as they are; ``g`` and ``beta`` go to
    it as they are made, one a value head and token; the head norm under
    ``silu(z)`` is ``head_norm_gate``."""
    heads, d, layer, tokens = cfg.linear_num_value_heads, cfg.linear_value_head_dim, sublayer.layer, x.shape[0]
    key, value = cfg.linear_num_key_heads * cfg.linear_key_head_dim, heads * d
    with jax.named_scope(f"{layer}.gdn"):
        n = _rms_norm(x, p[sublayer.norm], cfg.rms_eps)
        w = p["gdn_qkvz"]
        qkv, z = _by_board(_matmul(n, w[:, :2 * key + value])), _matmul(n, w[:, 2 * key + value:])
        q, k, v = mamba_conv(qkv, p["gdn_conv"], jnp.zeros((2 * key + value,), jnp.float32), (key, key, value), _interpret())
        ba = _matmul(n, p["gdn_ba"])
        beta = jax.nn.sigmoid(ba[:, :heads])
        g = -jnp.exp(p["gdn_A_log"]) * jax.nn.softplus(ba[:, heads:] + p["gdn_dt_bias"])  # a log-decay a value head and token
        kept, written = jax.lax.stop_gradient((g, beta))
        counters = {"gdn_state_kept": jnp.mean(jnp.exp(kept)), "gdn_beta": jnp.mean(written)}
    with jax.named_scope(f"{layer}.delta"):
        o = board_delta(q, k, v, _by_board(g), _by_board(beta), _interpret())
    with jax.named_scope(f"{layer}.gdn"):
        normed = head_norm_gate(o.reshape(tokens, value), z, p["gdn_o_norm"], "silu", cfg.rms_eps, _interpret())  # a head's own norm, one plain gain for all heads, BEFORE the gate
        return _matmul(normed, p["gdn_out"]), counters


def _interpret() -> bool:
    """The trunk's Pallas kernels are one path everywhere: compiled by
    Mosaic on a TPU, run by the Pallas interpreter elsewhere (the CPU of
    the tests), never another path."""
    return jax.default_backend() != "tpu"


def _slots_by_token(rows: jax.Array, k: int) -> jax.Array:
    """``rows_back``'s result [N * k, sub, lanes] as [N, k, sub, lanes],
    for the sums over a token's k where every slot is held. The barrier
    keeps XLA from moving the
    float32 convert (or the cotangent's broadcast) to the kernel's side
    of this reshape, where no fusion reaches it and it is written out
    whole, 2 GiB at the published sizes (PERF.md section 6, PR 27)."""
    return jax.lax.optimization_barrier(rows.reshape(-1, k, *rows.shape[1:]))


class Held(NamedTuple):
    """What a share's moves, gate kernels and sums know of its routing
    (``_held`` makes it; ``None`` where every expert is held). The held
    experts' rows are the first ``extent`` of the sorted order, and no
    kernel between dispatch and combine covers a block past it (the
    grouped products know it as the sum of the held groups' sizes);
    ``mask`` [N, k]
    says which of a token's slots they are; ``scale`` [N * k] are the
    combine weights in sorted order, which the share's sort carries
    along (the gather that makes them otherwise, 1.1 ms over 131,072
    slots, would be the longest operation left in the combine's
    gradient, for the 1/16 of them that is read); ``places`` and
    ``counts`` list the held slots' places in the token-order view, tile
    of tokens by tile (``ops/row_move.py held_places``): the sums over a
    token's slots read those rows of the view and no other."""
    extent: jax.Array  # int32 scalar
    mask: jax.Array  # bool [N, k]
    scale: jax.Array  # float32 [N * k], no gradient
    places: jax.Array  # int32, a run a tile of tokens
    counts: jax.Array  # int32 [tiles of tokens]


def _held(extent: jax.Array, mask: jax.Array, scale: jax.Array) -> Held:
    """At one slot a token there is no sum over a token's slots, and no lists of their places are made (``_held_slots_sum``)."""
    return Held(extent, mask, scale, *(held_places(mask) if mask.shape[1] > 1 else (None, None)))


def _extent(held: Optional[Held]) -> Optional[jax.Array]:
    return None if held is None else held.extent


def _held_slots_sum(rows: jax.Array, order: jax.Array, held: Held, weight: Optional[jax.Array], dtype) -> jax.Array:
    """A share's sorted ``rows`` [N * k, hidden] summed at their tokens
    (under ``weight`` [N, k]), [N, hidden] of ``dtype``: the held rows go
    back to their places in the token-order view (``rows_back`` to its
    extent) and ``rows_sum`` fetches those places alone, one DMA a row;
    nothing passes over the view. At ONE slot a token (top-1) the view
    is the result where the token's slot is held and nothing where it is
    not: a select on the slot's mask (what the view holds at an absent
    slot's place is uninitialised and is selected away, never
    multiplied), which XLA fuses into whatever reads the sum; no kernel
    is called to make a copy, and ``_held`` makes no lists."""
    view = rows_back(rows, order, extent=held.extent, interpret=_interpret())
    if held.mask.shape[1] == 1:
        own = view.astype(jnp.float32) if weight is None else view.astype(jnp.float32) * weight[:, :, None]
        return jnp.where(held.mask[:, :, None], own, 0.0).astype(dtype).reshape(view.shape[0], -1)
    return rows_sum(view, held.places, held.counts, weight, k=held.mask.shape[1], dtype=dtype, interpret=_interpret())


@jax.custom_vjp
def _dispatch(tokens: jax.Array, order: jax.Array, held: Optional[Held] = None) -> jax.Array:
    """Each token's row to its k slots, the slots sorted by expert:
    ``tokens[order // k]``, [N, hidden] bfloat16 -> [N * k, hidden].
    ``order`` [N * k] lists the (token, slot) pairs in sorted order. The
    gradient brings every slot's row back to its place (a permutation:
    ``rows_back``) and sums each token's k, never a scatter-add (24.8 ms
    against 8.9 for the gather at the published sizes, PERF.md section 5).
    With ``held`` the rows past its extent are not moved, either way:
    the result's tail is uninitialised, and the gradient sums a token's
    held slots alone (``_held_slots_sum``)."""
    k = order.shape[0] // tokens.shape[0]
    return rows_out(row_view(tokens), order // k, extent=_extent(held), interpret=_interpret())


def _dispatch_fwd(tokens, order, held):
    return _dispatch(tokens, order, held), (order.reshape(tokens.shape[0], -1), held)


def _dispatch_bwd(res, g):
    order, held = res
    n, k = order.shape
    if held is not None:
        return _held_slots_sum(g, order.reshape(n * k), held, None, g.dtype), None, None
    per_slot = _slots_by_token(rows_back(g, order.reshape(n * k), interpret=_interpret()), k)
    return per_slot.sum(axis=1).reshape(n, -1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out: jax.Array, weight: jax.Array, order: jax.Array, held: Optional[Held] = None) -> jax.Array:
    """The experts' sorted rows ``out`` [N * k, hidden] bfloat16 back in
    token order (``rows_back``) and each token's k summed under
    ``weight`` [N, k], float32 weights and sum: [N, hidden] float32. The
    gradient to ``out`` is the dispatch again with a scale: each slot's
    token's cotangent times the slot's weight in float32, then rounded
    to bfloat16 (``rows_out``). Where every slot is held the rows in
    token order stay in the row view, ``[N, k, hidden // 128, 128]``,
    which is also the residual of the weights' gradient. With ``held``
    the rows of ``out`` past its extent are not read and those of its
    gradient not written; the
    sum takes a token's held slots alone (``_held_slots_sum``); the
    residual is ``out`` itself, and the weights' gradient is made on the
    sorted side: the move that gathers a slot's token's cotangent also
    multiplies it with the slot's row of ``out`` and sums, in float32,
    and a sort keyed on ``order`` (a permutation) brings the sums to
    token order; an absent slot's weight has no gradient (a select: what
    lies past the extent may be NaN)."""
    return _combine_fwd(out, weight, order, held)[0]


def _combine_kept(out, weight, order, held):
    """What the combine's gradient needs of its forward, without the sum
    (``_routed_recomputed`` makes it again and has no use for the sum): a
    share's sorted rows themselves; where every slot is held the rows in
    token order."""
    if held is not None:
        return out, weight, order, held
    return _slots_by_token(rows_back(out, order, interpret=_interpret()), weight.shape[1]), weight, order, held


def _combine_fwd(out, weight, order, held):
    kept = _combine_kept(out, weight, order, held)
    if held is not None:
        return _held_slots_sum(out, order, held, weight, jnp.float32), kept
    mixed = jnp.sum(weight[:, :, None, None] * kept[0].astype(jnp.float32), axis=1)
    return mixed.reshape(weight.shape[0], -1), kept


def _combine_bwd(res, g):
    rows, weight, order, held = res
    n, k = weight.shape
    g = row_view(g)
    if held is not None:
        d_out, dots = rows_out_dot(g, order // k, held.scale, rows, extent=held.extent, interpret=_interpret())
        _, d_weight = jax.lax.sort((order, dots), num_keys=1)
        return d_out, jnp.where(held.mask, d_weight.reshape(n, k), 0.0), None, None
    d_weight = jnp.sum(g[:, None] * rows.astype(jnp.float32), axis=(2, 3))
    d_out = rows_out(g, order // k, weight.reshape(n * k)[order], dtype=rows.dtype, interpret=_interpret())
    return d_out, d_weight, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


#: Largest tile of the grouped product (rows, contraction, columns): the
#: fastest of those tried on a v5e at [262144, 2048] x [64, 2048, 1024]
#: (PERF.md section 5); the next size up does not fit the kernel's VMEM.
_TILE = (512, 1024, 1024)


#: A lane tile, and the columns of a moved row that is whole (8, 128) tiles.
_LANES, _ROW_TILE = 128, 1024


def _tile(width: int, most: int) -> int:
    """The largest tile up to ``most`` that divides ``width``, in whole
    128-lane tiles where the width has them: a tile that does not divide
    is computed whole and half empty (1,536 columns: 768, not 1,024). A
    width that is not whole lanes is the tests' tiny nets' under the
    interpreter; Mosaic is never handed one (``_expert_ffn`` pads an
    expert's width by ``_whole_lanes`` before it gets here)."""
    if width % _LANES and not _interpret():
        raise ValueError(f"a grouped product's dimension of {width} is not whole {_LANES}-lane tiles: pad it on the weights' side "
                         "(_whole_lanes, _padded) before the product")
    step = _LANES if width % _LANES == 0 else 1
    return max(t for t in range(step, min(width, most) + 1, step) if width % t == 0)


def _padded(x: jax.Array, axis: int, size: int) -> jax.Array:
    """``x`` with zeros along ``axis`` up to ``size`` (``x`` itself where it has that size already)."""
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def _whole_lanes(width: int) -> int:
    """THE rule for a dimension of the routed path that Mosaic's tiles do
    not divide, both of its cases: what a width is padded to, with zeros,
    inside the step. An expert's width that is not a multiple of 128
    lanes (1,856 = 14.5 x 128) goes to the next multiple (1,920): up's
    columns and down's rows. ``relu(0)^2 = 0`` (and ``silu(0) x 0``) meets
    down's zero rows, so no number of the result changes. A width under
    128 is left as it is: a tiny net under the interpreter, which
    ``_tile`` lets through there and nowhere else."""
    return width if width < _LANES else -(-width // _LANES) * _LANES


def _whole_rows(hidden: int) -> int:
    """The rule's other case: a moved row. ``ops/row_move.py`` addresses
    a row as ``[hidden // 128, 128]``, and a DMA moves whole (8, 128)
    tiles: a hidden whose 128-lane pieces are not a multiple of 8 (2,688
    = 21 x 128) moves as the next such row (24 x 128 = 3,072), the
    tokens padded before the dispatch, up's rows and down's columns
    beside them, the combine's sum cut back to ``hidden``. A hidden of
    1,024 or less is left as it is (the tests' nets). Either padding is
    made from the parameters' and the tokens' casts, is no parameter, has
    no gradient (a pad's transpose is a slice) and is in no checkpoint
    and in no comparison with a reference."""
    return hidden if hidden <= _ROW_TILE else -(-hidden // _ROW_TILE) * _ROW_TILE


@jax.custom_vjp
def _gmm(rows: jax.Array, weights: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """megablox ``gmm`` on bfloat16 operands, each of its three products
    (this one, and in the gradient ``gmm`` on the transposed weights and
    ``tgmm``) at the tiles of its own shape: megablox's own gradient rule
    gives all three the forward's, whose contraction tile meets the
    columns when the weights are transposed."""
    return _gmm_fwd(rows, weights, group_sizes)[0]


def _tiling(rows: int, contraction: int, columns: int) -> Tuple[int, int, int]:
    tiles = math.gcd(rows, _TILE[0]), _tile(contraction, _TILE[1]), _tile(columns, _TILE[2])
    # 4,096 joined columns of a 2048-wide expert take four tiles of 1,024, 1,536 two of 768: never a tile that is computed half empty
    assert contraction % tiles[1] == 0 and columns % tiles[2] == 0, (contraction, columns, tiles)
    return tiles


def _gmm_fwd(rows, weights, group_sizes):
    (m, k), n = rows.shape, weights.shape[2]
    return megablox_gmm(rows, weights, group_sizes, jnp.bfloat16, _tiling(m, k, n), interpret=_interpret()), (rows, weights, group_sizes)


def _gmm_bwd(res, g):
    rows, weights, group_sizes = res
    (m, k), n = rows.shape, weights.shape[2]
    d_rows = megablox_gmm(g, weights, group_sizes, rows.dtype, _tiling(m, n, k), transpose_rhs=True, interpret=_interpret())
    d_weights = megablox_tgmm(rows.swapaxes(0, 1), g, group_sizes, weights.dtype, _tiling(m, k, n), interpret=_interpret())
    return d_rows, d_weights, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(rows: jax.Array, weights: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``rows[i] @ weights[g(i)]`` where the rows come in runs of
    ``group_sizes`` (int32, one a group of ``weights``; a size may be 0).
    bfloat16 operands and result, float32 accumulation: megablox ``gmm``,
    whose gradients are ``gmm`` on the transposed weights and ``tgmm``
    (one product a group, summed over the group's rows). The number of
    rows has to be a multiple of 8; it is 64 x experts_per_token x
    positions here. The sizes may add up to fewer rows than there are (a
    share of sharded experts: the held groups' sizes, whose rows its sort
    puts first): the kernels visit the tiles of the groups they are given
    and nothing else, so the rows past the sum are not read, and those of
    the result and of the cotangent to ``rows`` are UNINITIALISED, not
    zero, as the tails the row moves leave (``ops/row_move.py``, "The
    extent of a move"); the weights' gradient sums a group's own rows
    alone and is whole."""
    return _gmm(rows.astype(jnp.bfloat16), weights.astype(jnp.bfloat16), group_sizes)


def _route(n2: jax.Array, p: Params, cfg: TrunkConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The router in float32: each token's ``experts_per_token`` experts
    [N, k], their combine weights [N, k], and the scores as a
    distribution over the experts [N, experts] (for the entropy)."""
    if cfg.router_hidden:  # the fifth block's: a down-projection, two GELU layers (erf), then the experts' logits, biases but on the last
        highest = jax.lax.Precision.HIGHEST
        r = jnp.dot(n2, p["router_down"], precision=highest) + p["router_down_b"]
        for name in ("router_w1", "router_w2"):
            r = jax.nn.gelu(jnp.dot(r, p[name], precision=highest) + p[f"{name}_b"], approximate=False)
        logits = jnp.dot(r, p["router_w3"], precision=highest)
    else:
        logits = jnp.dot(n2, p["router_w"], precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "softmax":
        score = probs = jax.nn.softmax(logits, axis=-1)
    else:
        score = jax.nn.sigmoid(logits)
        probs = score / jnp.sum(score, axis=-1, keepdims=True)
    if "expert_bias" in p:  # the choice is on score + bias, the weights on the score; neither has a gradient through the bias
        _, expert = jax.lax.top_k(score + jax.lax.stop_gradient(p["expert_bias"]), cfg.experts_per_token)
        # take_along_axis(score, expert) as a select: a token's experts are distinct (at top-1 there is one), so each of its k sums
        # is one score and zeros, and so is its gradient's. XLA's gather of tokens x k scalars and the scatter that is its gradient cost 2.48 ms a layer at 131,072 slots, the
        # select and the two sums 0.52 (PERF.md section 6, PR 38).
        chosen = expert[:, :, None] == jnp.arange(score.shape[-1], dtype=expert.dtype)
        weight = jnp.sum(jnp.where(chosen, score[:, None, :], 0.0), axis=-1)
    else:
        weight, expert = jax.lax.top_k(score, cfg.experts_per_token)
    if cfg.route_norm:  # over all the chosen, held here or not: the shares of all chips add up
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if cfg.route_scale != 1.0:
        weight = weight * cfg.route_scale
    return expert, weight, probs


#: The leaves a Pallas kernel reads as they are held: the operands of the grouped products (``_expert_ffn``). The trainer keeps
#: their update in the layout the client holds them in (``train/az_trainer.py held_layouts``).
KERNEL_OPERANDS = ("experts_gate", "experts_up", "experts_down")


def _expert_ffn(rows: jax.Array, gate_w: Optional[jax.Array], up_w: jax.Array, down_w: jax.Array, group_sizes: jax.Array,
                extent: Optional[jax.Array]) -> jax.Array:
    """The held experts on the sorted rows, two grouped products round
    the gated activation: gate and up are ONE product on the two weights
    joined along their columns (in the bfloat16 cast the step makes
    anyway), so the rows are read once, and their gradient is one product
    over the joined width, summed in float32 inside the kernel, in place
    of two bfloat16 results and their sum. ``silu(gate) * up`` between
    them is a kernel (``ops/expert_gate.py``) that stops at ``extent`` as
    the moves do: with an extent nothing here passes over a row past it,
    and the tail of every intermediate, of the result and of the
    cotangent to ``rows`` is uninitialised. Without ``gate_w`` an expert
    is ``relu(u W_up)^2 W_down``: the up product alone, then
    ``expert_gate.py``'s second form, ``squared_relu``, a kernel on the
    same grid under the same extent (an XLA elementwise would pass over
    the tail), then the down product. The weights are padded to the
    rows' width (``_whole_rows``: the rows come padded) and to whole
    lanes of their own (``_whole_lanes``)."""
    moved, lanes = rows.shape[1], _whole_lanes(up_w.shape[2])
    into = lambda w: _padded(_padded(w.astype(jnp.bfloat16), 1, moved), 2, lanes)
    down_w = _padded(_padded(down_w, 1, lanes), 2, moved)
    if gate_w is None:
        hidden = squared_relu(grouped_matmul(rows, into(up_w), group_sizes), extent, _interpret())
        return grouped_matmul(hidden, down_w, group_sizes)
    gate_up = jnp.concatenate([into(gate_w), into(up_w)], axis=-1)
    hidden = gated_activation(grouped_matmul(rows, gate_up, group_sizes), extent, _interpret())
    return grouped_matmul(hidden, down_w, group_sizes)


def _routed(n2, weight, order, group_sizes, held: Optional[Held], gate_w, up_w, down_w, layer: str) -> jax.Array:
    """Dispatch, the held experts and combine: [N, hidden] float32 normed
    tokens -> the weighted sum of each token's held slots, same shape
    (between the two every row is ``_whole_rows(hidden)`` wide)."""
    with jax.named_scope(f"{layer}.dispatch"):
        rows = _dispatch(_moved(n2), order, held)
    with jax.named_scope(f"{layer}.experts"):
        out = _expert_ffn(rows, gate_w, up_w, down_w, group_sizes, _extent(held))
    with jax.named_scope(f"{layer}.combine"):
        mixed = _combine(out, weight, order, held)
        return mixed if mixed.shape == n2.shape else mixed[:, :n2.shape[1]]


def _moved(tokens: jax.Array) -> jax.Array:
    """The normed tokens as the moves take them: bfloat16, a row whole tiles."""
    return _padded(tokens.astype(jnp.bfloat16), 1, _whole_rows(tokens.shape[1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _routed_recomputed(n2, weight, order, group_sizes, held: Optional[Held], gate_w, up_w, down_w, layer: str) -> jax.Array:
    """``_routed`` that keeps nothing of slot size for its gradient: the
    sorted rows, the products' intermediates and the rows in token order
    (1.75 GiB a layer at 131,072 slots of hidden 2048, whatever the
    extent of the moves: the buffers keep the dropless worst case's
    shape) are made again in
    the backward pass from the tokens, which the router keeps anyway.
    Written out by parts, each under its own scope and never nested in a
    layer's, because the benchmark's scope table reads two levels of a
    path and ``jax.checkpoint`` would put its own two first."""
    return _routed(n2, weight, order, group_sizes, held, gate_w, up_w, down_w, layer)


def _routed_recomputed_fwd(n2, weight, order, group_sizes, held, gate_w, up_w, down_w, layer):
    args = (n2, weight, order, group_sizes, held, gate_w, up_w, down_w)
    return _routed(*args, layer), args


def _routed_recomputed_bwd(layer, args, g):
    n2, weight, order, group_sizes, held, gate_w, up_w, down_w = args
    # Tied to the cotangent's arrival, as jax.checkpoint ties its own: nothing else keeps XLA from
    # making every layer's rows again at once, as soon as the forward pass has the tokens.
    n2, g = jax.lax.optimization_barrier((n2, g))
    with jax.named_scope(f"{layer}.dispatch"):
        rows, pull_rows = jax.vjp(lambda t: _dispatch(_moved(t), order, held), n2)
    with jax.named_scope(f"{layer}.experts"):
        out, pull_ffn = jax.vjp(lambda *a: _expert_ffn(*a, group_sizes, _extent(held)), rows, gate_w, up_w, down_w)
    with jax.named_scope(f"{layer}.combine"):
        d_out, d_weight, _, _ = _combine_bwd(_combine_kept(out, weight, order, held), _padded(g, 1, _whole_rows(g.shape[1])))
    with jax.named_scope(f"{layer}.experts"):
        d_rows, *d_weights = pull_ffn(d_out)
    with jax.named_scope(f"{layer}.dispatch"):
        (d_n2,) = pull_rows(d_rows)
    return (d_n2, d_weight, None, None, None, *d_weights)


_routed_recomputed.defvjp(_routed_recomputed_fwd, _routed_recomputed_bwd)


def _experts(n2: jax.Array, p: Params, cfg: TrunkConfig, layer: str) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """[N, hidden] float32 normed tokens -> the held routed experts'
    weighted sum [N, hidden] float32, and the layer's routing counters.
    Enters its own scopes: call it under none of a layer's.

    A share sorts its slots on ``(expert - first) mod experts``, so the
    held experts' rows are always rows ``[0, held)`` of the sorted order,
    their groups the first ``count``; that count of rows, the layer's
    own, on the device, is the extent of every move (``Held``). The
    counters keep the experts' own order."""
    n, k = n2.shape[0], cfg.experts_per_token
    first, count = cfg.held
    with jax.named_scope(f"{layer}.router"):
        expert, weight, probs = _route(n2, p, cfg)
    with jax.named_scope(f"{layer}.dispatch"):
        group = expert.reshape(n * k)
        if cfg.held_experts:
            group = (group - first) % cfg.experts
            _, order, scale = jax.lax.sort((group, jnp.arange(n * k, dtype=jnp.int32), jax.lax.stop_gradient(weight).reshape(n * k)),
                                           num_keys=1, is_stable=True)
        else:
            order = jnp.argsort(group, stable=True)
        group_sizes = jnp.sum(group[:, None] == jnp.arange(cfg.experts)[None, :], axis=0, dtype=jnp.int32)
        held = _held(jnp.sum(group_sizes[:count]), group.reshape(n, k) < count, scale) if cfg.held_experts else None
    routed = _routed_recomputed if cfg.recompute_experts else _routed
    # The products get the held groups' sizes alone (a share's come first): they then visit no row past the extent.
    mixed = routed(n2, weight, order, group_sizes[:count], held, p.get("experts_gate"), p["experts_up"], p["experts_down"], layer)
    load = (jnp.roll(group_sizes, first) if cfg.held_experts else group_sizes).astype(jnp.float32)
    entropy = -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-30), axis=-1))
    top1 = {"route_top1_weight": jnp.mean(jax.lax.stop_gradient(weight))} if k == 1 else {}  # the chosen expert's score: the router's only gradient
    return mixed, {"expert_load_max": jnp.max(load), "expert_load_min": jnp.min(load), "router_entropy": entropy,
                   "expert_slots": load, "moved_rows": jnp.asarray(rows_covered(n * k, _extent(held)), jnp.float32), **top1}


def trunk_forward(params: Params, planes: jax.Array, cfg: TrunkConfig = TrunkConfig()):
    """planes [B, 8, 8, 19] -> (policy_logits [B, 4672], value [B]), float32: what is served (the ninth block: the clean stream alone; the tenth: a
    position's pass by the exit rule, ``served_pass``, out of all ``loop_steps`` computed for the batch)."""
    logits, value, _, *gates = trunk_forward_counted(params, planes, cfg)
    if cfg.loop_steps == 1:
        return logits, value
    chosen = served_pass(gates[0], cfg.exit_threshold)
    return jnp.take_along_axis(logits, chosen[None, :, None], axis=0)[0], jnp.take_along_axis(value, chosen[None, :], axis=0)[0]


def exit_log_distribution(gate_logits: jax.Array) -> jax.Array:
    """A looped trunk's exit distribution from its gates' logits ``[T, B]``
    (pass, board), as logarithms ``[T, B]`` float32: ``p_1 = lambda_1``,
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, and the LAST pass takes
    what is left, ``p_T = prod_{j<T} (1 - lambda_j)`` (its own gate is not
    read: the T probabilities sum to 1 exactly), ``lambda = sigmoid(g)``.
    Made from ``log_sigmoid``, so a gate that saturates leaves a large
    negative logarithm and never the logarithm of a product that
    underflowed."""
    g = gate_logits.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g[:-1]), axis=0)  # log prod_{j<=t} (1 - lambda_j), t = 1 .. T - 1
    before = jnp.concatenate([jnp.zeros_like(g[:1]), stay[:-1]], axis=0)  # log prod_{j<t} (1 - lambda_j), t = 1 .. T - 1
    return jnp.concatenate([jax.nn.log_sigmoid(g[:-1]) + before, stay[-1:]], axis=0)


def served_pass(gate_logits: jax.Array, threshold: float) -> jax.Array:
    """The pass each board is served from, int32 ``[B]`` in ``[0, T)``: the
    first at which the exit distribution's cumulative sum reaches
    ``threshold`` (the published ``early_exit_threshold``), the last where
    none does: in float32 the T probabilities may sum to a last bit under
    1, and at the published 1.0 that is the last pass too."""
    reached = jnp.cumsum(jnp.exp(exit_log_distribution(gate_logits)), axis=0) >= threshold
    return jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0), gate_logits.shape[0] - 1).astype(jnp.int32)


def _routed_layer(x: jax.Array, p: Params, cfg: TrunkConfig, sublayer: Sublayer) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The routed kind: the norm under the router's scope, the held
    experts, the shared expert beside them (under its token gate where
    the block has one); the layer's routing counters. Its residual is
    added under the combine's scope."""
    name = sublayer.layer
    with jax.named_scope(f"{name}.router"):
        n2 = _rms_norm(x, p[sublayer.norm], cfg.rms_eps)
    mixed, counters = _experts(n2, p, cfg, name)
    if cfg.shared_width:
        with jax.named_scope(f"{name}.shared"):
            shared = _ffn(n2, p, "shared", cfg.gated_ffn)
            if cfg.shared_token_gate:  # the seventh block's: one sigmoid gate a token, float32 (a sum over the hidden columns, no product)
                gate = jax.nn.sigmoid(jnp.sum(n2 * p["shared_token_gate"][:, 0], axis=-1, keepdims=True))
                shared, counters = gate * shared, {**counters, "shared_gate_mean": jnp.mean(jax.lax.stop_gradient(gate))}
            mixed = mixed + shared
    return mixed, counters


#: Kind of sublayer -> its function, ``(x, p, cfg, sublayer) -> (branch, counters by name)``, which opens its own scopes and is
#: called under none, and the scope under which the loop adds the branch (through the sublayer's post-norm) to the stream.
_KINDS = {"attention": (_attention, "attention"), "latent": (_latent_attention, "attention"), "cca": (_cca_attention, "attention"),
          "mamba": (_mamba, "mamba"), "kda": (_kda, "kda"), "gdn": (_gdn, "gdn"), "dense": (_dense_layer, "dense"), "routed": (_routed_layer, "combine")}

#: The order in which ONE layer's slices are made: nothing but the lowered text depends on it, and the step pins hold that text
#: (``tests/test_hybrid_trunk.py PARENT_STEP_SHA256``). It is the order in which the blocks came: a block layer's feed-forward
#: norm after its mixer's ``wo`` and before what the second and the fifth block brought, a pattern's norm last.
_SLICE_ORDER = tuple(dict.fromkeys((
    "attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wkv_a", "kv_norm", "wkv_b", "wo", "moe_norm", "wgate", "post_attn_norm", "post_mlp_norm",
    *(name for names in _OWNS.values() for name in names), "layer_norm")))


def _reads(sublayer: Sublayer) -> Dict[str, int]:
    """Everything a sublayer reads, tensor -> row: its norms and what its kind owns."""
    norms = {name: sublayer.norm_index for name in (sublayer.norm, sublayer.post_norm) if name}
    return {**norms, **dict.fromkeys(_OWNS[sublayer.kind], sublayer.index)}


def _sliced(params: Params, plan: Tuple[Sublayer, ...]):
    """Each sublayer of ``plan`` with its own tensors out of the stacked
    ``params``, by their names (those the checkpoint has); the slices of
    one layer's sublayers are made together, when its first is reached,
    in ``_SLICE_ORDER`` (no two sublayers of a layer read one tensor)."""
    for _, layer in itertools.groupby(plan, key=lambda sublayer: sublayer.layer):
        layer = tuple(layer)
        rows = {name: row for sublayer in layer for name, row in _reads(sublayer).items() if name in params}
        own = {name: params[name][rows[name]] for name in _SLICE_ORDER if name in rows}
        yield from ((sublayer, {name: own[name] for name in _reads(sublayer) if name in own}) for sublayer in layer)


def sublayer_params(params: Params, sublayer: Sublayer) -> Params:
    """One sublayer's own tensors out of the stacked ``params``."""
    return next(_sliced(params, (sublayer,)))[1]


def centred_gains(params: Params, cfg: TrunkConfig) -> Params:
    """``params`` as every kind's function reads them: under
    ``zero_centered_norms`` the five norms of ``_ZERO_CENTERED`` as their
    gains ``1 + w`` (the parameter, the optimizer's weight decay and the
    checkpoint keep ``w``); else ``params`` themselves."""
    return {name: 1.0 + value if name in _ZERO_CENTERED else value for name, value in params.items()} if cfg.zero_centered_norms else params


def _two_streams(planes: jax.Array, square_masked: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A batch's planes ``[boards, 8, 8, 19]`` and its mask ``[boards,
    64]`` -> the tokens of the clean and the noised copy of every board
    side by side, ``[boards x 128, 19]`` (a masked square's 12 piece
    planes zeroed in the noised copy, the 7 board-wide planes as they
    are), and which of them take the mask embedding, ``[boards x 128,
    1]`` float32: the noised copy's masked squares."""
    tokens = planes.reshape(planes.shape[0], SQUARES, INPUT_PLANES)
    m = square_masked.astype(jnp.float32)[:, :, None]
    pieces = (np.arange(INPUT_PLANES) < PIECE_PLANES).astype(np.float32)
    both, marked = jnp.concatenate([tokens, tokens * (1.0 - m * pieces)], axis=1), jnp.concatenate([jnp.zeros_like(m), m], axis=1)
    return both.reshape(-1, INPUT_PLANES), marked.reshape(-1, 1)


def _one_pass(x: jax.Array, params: Params, cfg: TrunkConfig, plan: Tuple[Sublayer, ...]):
    """The plan walked once over the stream ``x``: the stream after its last sublayer, and what each sublayer counted."""
    counters = []
    for sublayer, p in _sliced(params, plan):
        run, scope = _KINDS[sublayer.kind]
        branch, counted = run(x, p, cfg, sublayer)
        with jax.named_scope(f"{sublayer.layer}.{scope}"):
            x = x + (_rms_norm(branch, p[sublayer.post_norm], cfg.rms_eps) if sublayer.post_norm else branch)
        counters.append(counted)
    return x, counters


def trunk_forward_counted(params: Params, planes: jax.Array, cfg: TrunkConfig, square_masked: Optional[jax.Array] = None):
    """``trunk_forward`` and the counters of the step's metrics: what
    the sublayers counted, folded over the sublayers as ``_FOLDS`` says
    (which also says what each one is), and the whole trunk's own three.
    Told ``square_masked`` (bool ``[B, 64]``, a batch's noise: the ninth
    block's training forward) it carries a clean and a noised copy of
    every board through the layers, 128 tokens a board, the heads read
    the clean copy and a fourth result follows the counters: the
    denoiser's logits ``[B, 64, 13]`` float32 off the noised copy. A
    looped trunk (``loop_steps`` T over 1: the tenth block) walks the
    plan T times over the same weights and exits after every pass: the
    heads come back ``[T, B, ..]``, a sublayer's counters fold over its
    passes too, and the fourth result is the exit gates' logits ``[T,
    B]`` float32 (``exit_log_distribution`` makes the distribution,
    ``trunk_forward`` serves by it)."""
    b, params = planes.shape[0], centred_gains(params, cfg)
    streams = 1 if square_masked is None else 2
    if streams == 2 and not cfg.block_length:
        raise ValueError("square_masked is a block-diffusion trunk's (block_length): this one has no mask embedding and no denoiser")
    # Scope names are a contract (doc/observability.md "Training and compilation"): one scope a part, the layer in its name,
    # because the benchmark's scope table keeps two levels of a path (phase, then this): a pass of a loop is no level of it.
    with jax.named_scope("embed"):
        tokens, marked = (planes.reshape(b * SQUARES, INPUT_PLANES), None) if streams == 1 else _two_streams(planes, square_masked)
        x = _matmul(tokens, params["embed_w"]) + params["embed_b"]
        if marked is not None:  # both copies went through the one embedding; the mask embedding on the noised copy's masked squares
            x = x + marked * params["mask_embed"]
        x = _row_major(x * cfg.embed_scale if cfg.embed_scale != 1.0 else x)
    plan, passes, counters = trunk_plan(cfg, streams), [], []
    for _ in range(cfg.loop_steps):  # the same plan over the same slices: a weight's gradient is the sum over its passes
        x, counted = _one_pass(x, params, cfg, plan)
        passes.append(x)
        counters += counted
    if cfg.loop_steps > 1:
        x = jnp.stack(passes)  # [T, tokens, hidden]: one final norm, one call of the heads, one gate for all the exits
    with jax.named_scope("final_norm"):
        x = _rms_norm(x, params["final_norm"], cfg.rms_eps)
    if streams == 2:
        x, noised = (x.reshape(b, 2, SQUARES, cfg.hidden)[:, copy] for copy in range(2))
    features = x.reshape(cfg.loop_steps * b, 8, 8, cfg.hidden).astype(jnp.bfloat16)
    routed = [c["expert_slots"] for c in counters if "expert_slots" in c]  # none: a trunk of dense layers alone
    slots = jnp.stack(routed) if routed else None
    if slots is not None and cfg.loop_steps > 1:  # an expert's slots of a step: its layer's passes summed
        slots = jnp.sum(slots.reshape(cfg.loop_steps, -1, cfg.experts), axis=0)
    heads = policy_value_heads(params, features)
    folded = {}
    for name, fold in _FOLDS.items():  # the whole trunk's own are made where the result's key order has them
        values = [c[name] for c in counters if name in c]
        if fold and values:
            folded[name] = fold(jnp.stack(values))
        elif name == "expert_slots" and slots is not None:
            folded[name] = slots
        elif name == "held_slots" and cfg.held_experts and slots is not None:
            folded[name] = jnp.sum(slots[:, cfg.held[0]:sum(cfg.held)])
        elif name == "expert_bias_abs_max" and "expert_bias" in params:
            folded[name] = jnp.max(jnp.abs(params["expert_bias"]))
        elif name == "loop_update_rms" and cfg.loop_steps > 1:
            before, after = jax.lax.stop_gradient((passes[-2], passes[-1]))
            folded[name] = jnp.sqrt(jnp.mean(jnp.square(after - before)) / jnp.mean(jnp.square(before)))
    if streams == 2:
        with jax.named_scope("denoise"):
            return (*heads, folded, _matmul(noised.reshape(b * SQUARES, cfg.hidden), params["denoise_w"]).reshape(b, SQUARES, -1) + params["denoise_b"])
    if cfg.loop_steps > 1:
        with jax.named_scope("exit_gate"):  # float32, a sum over the hidden columns and a mean over a board's squares: no product
            gate = jnp.mean(jnp.sum(x * params["exit_gate_w"][:, 0], axis=-1).reshape(cfg.loop_steps, b, SQUARES), axis=-1) + params["exit_gate_b"]
        return (*(head.reshape(cfg.loop_steps, b, *head.shape[1:]) for head in heads), folded, gate)
    return (*heads, folded)


#: The trunk's counters in the result's key order, and how each folds over the sublayers that return it (``None``: the trunk's own).
_FOLDS = {
    "expert_load_max": jnp.max,  # the most and ...
    "expert_load_min": jnp.min,  # ... the fewest slots any expert of any routed layer received
    "router_entropy": jnp.mean,  # nats: of the softmax, or of the sigmoid scores over their sum
    "expert_slots": None,  # every routed layer's slots an expert, held or not, [routed layers, experts]: what the balance update reads
    "moved_rows": jnp.sum,  # the rows each of a routed layer's moves covers: every slot, or a share's held count rounded up to whole blocks
    "held_slots": None,  # a share's: the slots that fell on the held experts, summed over the layers
    "expert_bias_abs_max": None,  # with an ``expert_bias`` among the params
    "latent_rms": jnp.mean,  # the root mean square of the key-value latent before its norm: a latent that collapses or blows up
    "cca_conv_share": jnp.mean,  # the root mean square of what the two convolutions changed, ``c - [q~ | k~]``, over that of ``[q~ | k~]``: 0.0017 (the
    # rounding of conv1's bfloat16 operand) while they still pass their input; a mixing path that is dead or has taken over shows here
    "cca_temp_max": jnp.max,  # the largest key temperature of any head of any layer
    "route_top1_weight": jnp.mean,  # at one expert a token, the chosen expert's score: at 1.0 the router has no gradient left, at 1 / experts it has not chosen
    "ssm_dt_mean": jnp.mean,  # the mean step ``D_t`` after its softplus, over tokens, heads and mixers
    "ssm_decay_min": jnp.min,  # the smallest decay across a board, ``exp(c_63 - c_0)``, of any head of any mixer, mean over boards: a head that forgets a board
    "kda_state_kept": jnp.mean,  # the mean decay ``alpha = exp(g)`` a square, over tokens, heads, channels and KDA mixers: 1 a state that never forgets, 0 one that holds nothing
    "kda_beta": jnp.mean,  # the mean ``beta``, the share of a square's value written over what the state held for its key: 0 a mixer that writes nothing
    "gdn_state_kept": jnp.mean,  # the mean decay ``exp(g)`` a square, over tokens, value heads and GDN mixers: 1 a state that never forgets, 0 one that holds nothing
    "gdn_beta": jnp.mean,  # the mean ``beta`` of the GDN mixers, as ``kda_beta``
    "shared_gate_mean": jnp.mean,  # the mean of the shared expert's sigmoid gate over tokens and layers: 0 a shared expert switched off, 1 one that is never gated
    "loop_update_rms": None,  # a looped trunk's: the root mean square of what the LAST pass changed, ``h_T - h_{T-1}``, over that of ``h_{T-1}``: near 0 a loop that
    # has stopped moving (its last pass buys nothing), over 1 one that runs away
}


def balanced_bias(bias: jax.Array, slots: jax.Array, rate: float) -> jax.Array:
    """``expert_bias`` [routed layers, experts] after a step whose routed
    layers sent ``slots`` rows to each expert: up by ``rate`` for an
    expert under its layer's mean load, down for one over it, the
    layer's mean change taken out (the block's auxiliary-loss-free
    balancing)."""
    change = rate * jnp.sign(jnp.mean(slots, axis=-1, keepdims=True) - slots)
    return bias + change - jnp.mean(change, axis=-1, keepdims=True)


#: What a trunk checkpoint carries beside its tensors: the fields of
#: ``TrunkConfig`` that no shape determines, as float64. A file of the
#: first block alone has the first three; the rest default to it.
HPARAMS = "trunk_hparams"
_HPARAMS = ("experts_per_token", "rope_theta", "rms_eps", "embed_scale", "route_scale", "balance_rate", "sliding_window",
            "sigmoid", "route_norm", "first_held", "nope_mask", "head_dim", "mamba_groups", "rotary_dim", "zero_centered")
#: What a file with ``full_attention_layers`` carries after them (no other file: one of the seven older blocks is what it was).
_ROPE_HPARAMS = ("full_mask", "yarn", "rope_factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "attention_factor")
#: What a file with ``block_length`` carries after both (no other file but a looped trunk's, where it reads 0).
_BLOCK_HPARAMS = ("block_length",)
#: What a looped trunk's file (``loop_steps`` over 1) carries after all three (no other file).
_LOOP_HPARAMS = ("loop_steps", "exit_threshold")
#: A pattern's checkpoint carries the pattern itself, its characters as bytes.
PATTERN = "trunk_pattern"
#: A checkpoint whose mixer is told by layer carries ``mixers``, each layer's kind as its place in ``_MIXERS``.
MIXERS = "trunk_mixers"


def trunk_checkpoint(params: Params, cfg: TrunkConfig) -> Dict[str, np.ndarray]:
    """The arrays of a trunk ``.npz``: the tensors (``expert_bias`` among
    them where the state has one) and ``trunk_hparams`` (``_HPARAMS``
    names its values): sliding_window 0 for none, sigmoid scores 0 or 1,
    the first held expert (-1: all are held), the layers without RoPE as
    a bit mask, what no shape of the fourth block gives (head_dim: there
    are no qk-norm gains to read it from; mamba_groups) and the fifth's
    rotary_dim (0: RoPE on all of a head; its kernel sizes, head width
    and router width are shapes) and the seventh's zero-centred norms (0
    or 1; its sizes and its token gate are shapes); after them, in a file
    with ``full_attention_layers`` alone (a file of the seven older blocks
    is what it was), the eighth's: those layers as a bit mask,
    ``rope_type`` yarn 0 or 1, and YaRN's five numbers; after those, in a
    file with ``block_length`` alone, the ninth's block length (its mask
    embedding and denoiser are tensors); after that, in a looped trunk's
    file alone, the tenth's ``loop_steps`` and ``exit_threshold`` (its gate
    is two tensors; that it has no routed layer is the absence of a
    router's). A pattern's file carries the pattern
    too (``trunk_pattern``, its characters as bytes), one whose mixer is
    told by layer its ``mixers`` (``trunk_mixers``).
    ``recompute_experts`` is the trainer's and in no file."""
    arrays = {k: np.asarray(v) for k, v in params.items()}
    later = (_ROPE_HPARAMS + _BLOCK_HPARAMS + _LOOP_HPARAMS) if cfg.loop_steps > 1 else (_ROPE_HPARAMS + _BLOCK_HPARAMS) if cfg.block_length else \
        _ROPE_HPARAMS if cfg.full_attention_layers else ()
    arrays[HPARAMS] = _hparams(cfg)[:len(_HPARAMS) + len(later)]
    if cfg.pattern:
        arrays[PATTERN] = np.frombuffer(cfg.pattern.encode("ascii"), np.uint8)
    if cfg.mixers:
        arrays[MIXERS] = np.asarray([_MIXERS.index(kind) for kind in cfg.mixers], np.uint8)
    return arrays


def _hparams(cfg: TrunkConfig) -> np.ndarray:
    """Every value ``_HPARAMS``, ``_ROPE_HPARAMS``, ``_BLOCK_HPARAMS`` and ``_LOOP_HPARAMS`` name, of ``cfg``."""
    return np.asarray([
        cfg.experts_per_token, cfg.rope_theta, cfg.rms_eps, cfg.embed_scale, cfg.route_scale, cfg.balance_rate,
        cfg.sliding_window or 0, cfg.router_score == "sigmoid", cfg.route_norm,
        cfg.held_experts[0] if cfg.held_experts else -1, sum(1 << i for i in cfg.nope_layers),
        cfg.head_dim, cfg.mamba_groups, cfg.rotary_dim or 0, cfg.zero_centered_norms,
        sum(1 << i for i in cfg.full_attention_layers), cfg.rope_type == "yarn", cfg.rope_factor, cfg.original_max_position_embeddings,
        cfg.beta_fast, cfg.beta_slow, cfg.attention_factor, cfg.block_length, cfg.loop_steps, cfg.exit_threshold], np.float64)


def _attention_sizes(params: Params, shape, hp: Dict[str, float]) -> Dict[str, object]:  # the head width from the qk-norm's gains, or the file's
    head_dim = shape("q_norm")[1] if "q_norm" in params else int(hp["head_dim"])
    return dict(heads=shape("wq")[2] // head_dim, head_dim=head_dim, qk_norm="q_norm" in params,
                kv_heads=None if shape("wk") == shape("wq") else shape("wk")[2] // head_dim)


def _latent_sizes(params: Params, shape, hp: Dict[str, float]) -> Dict[str, object]:  # its four widths and the head count from five shapes
    rank, score, key_value, value = shape("kv_norm")[1], shape("wq")[2], shape("wkv_b")[2], shape("wo")[1]
    rope = shape("wkv_a")[2] - rank
    heads = (score - key_value + value) // rope if rope > 0 else 0  # heads x (nope + rope) - heads x (nope + value) + heads x value
    if heads < 1:
        raise ValueError(f"trunk checkpoint: mismatched shapes: wkv_a {shape('wkv_a')} leaves no RoPE key beside a latent of {rank}, "
                         f"or wq {shape('wq')}, wkv_b {shape('wkv_b')} and wo {shape('wo')} no head")
    return dict(heads=heads, kv_lora_rank=rank, qk_rope_head_dim=rope, qk_nope_head_dim=(key_value - value) // heads, v_head_dim=value // heads)


def _cca_sizes(params: Params, shape, hp: Dict[str, float]) -> Dict[str, object]:  # the head width from conv1's taps, the kernel sizes from both
    head_dim = shape("conv1_w")[-1]
    return dict(heads=shape("wq")[2] // head_dim, head_dim=head_dim, kv_heads=shape("wk")[2] // head_dim, cca=(shape("conv0_w")[2], shape("conv1_w")[2]))


def _kda_sizes(params: Params, shape, hp: Dict[str, float]) -> Dict[str, object]:  # the heads from the rates, a head's width from its norm's gain
    return dict(kda_heads=shape("kda_A_log")[1], kda_head_dim=shape("kda_o_norm")[1], conv_kernel=shape("kda_conv")[2])


def _gdn_sizes(params: Params, shape, hp: Dict[str, float]) -> Dict[str, object]:  # the value heads from the rates, a head's width from its norm's gain
    heads, d, (_, channels, taps) = shape("gdn_A_log")[1], shape("gdn_o_norm")[1], shape("gdn_conv")
    return dict(linear_num_key_heads=(channels - heads * d) // (2 * d), linear_num_value_heads=heads, linear_key_head_dim=d, linear_value_head_dim=d,
                conv_kernel=taps)


def _mamba_sizes(params: Params, shape, hp: Dict[str, float]) -> Dict[str, object]:  # the groups from the file, the rest from three shapes
    heads, inner, (_, channels, taps), groups = shape("dt_bias")[1], shape("mamba_norm")[1], shape("conv_w"), int(hp["mamba_groups"])
    return dict(mamba_heads=heads, mamba_head_dim=inner // heads, mamba_groups=groups, conv_kernel=taps,
                state_size=(channels - inner) // (2 * groups) if groups else 0)


#: Token mixer -> the tensors of its row of ``_OWNS`` that its sizes are read from, and the reader (above) of its fields of
#: ``TrunkConfig`` from those tensors' shapes (``shape``) and the file's values (``hp``).
_SIZES = {"attention": (("wq", "wk", "wo"), _attention_sizes), "latent": (("kv_norm", "wkv_a", "wkv_b"), _latent_sizes),
          "cca": (("conv0_w", "conv1_w", "wk", "wv1", "wv2", "temp"), _cca_sizes), "mamba": (("mamba_norm", "dt_bias", "conv_w"), _mamba_sizes),
          "kda": (("kda_A_log", "kda_o_norm", "kda_conv"), _kda_sizes), "gdn": (("gdn_A_log", "gdn_o_norm", "gdn_conv"), _gdn_sizes)}
assert all(set(names) <= set(_OWNS[kind]) for kind, (names, _) in _SIZES.items())


def trunk_config_from_params(params: Params) -> TrunkConfig:
    """The ``TrunkConfig`` of a checkpoint, from its shapes, its
    ``trunk_hparams`` and, a pattern's, its ``trunk_pattern``; a
    ValueError names what does not fit."""
    pattern = bytes(np.asarray(params[PATTERN], np.uint8)).decode("ascii") if PATTERN in params else None
    places = [int(i) for i in np.asarray(params[MIXERS]).reshape(-1)] if MIXERS in params else None
    if places is not None and any(not 0 <= i < len(_MIXERS) for i in places):
        raise ValueError(f"trunk checkpoint: {MIXERS} {places} are not places in {_MIXERS}")
    by_layer = None if places is None else tuple(_MIXERS[i] for i in places)
    router = "router_w3" if "router_down" in params else "router_w"  # the MLP router's last matrix: its columns are the experts
    norm = "attn_norm" if pattern is None else "layer_norm"
    # A trunk of dense layers alone (the tenth block) has no tensor of a routed layer at all: the file is read as one by that absence beside a dense
    # feed-forward's, and a file with SOME of a routed layer's tensors is still a routed trunk's with the others missing.
    routerless = "dense_up" in params and not any(name in params for name in _OWNS["routed"])
    if routerless:
        required, told_by = ("wq", "wo", norm, "dense_up"), "dense_up without a routed layer's tensors"
    else:
        required = (router, "experts_gate", "wq", "wo", norm, "experts_up") if pattern is None and by_layer is None else (router, "experts_up", norm)
        told_by = next((f"its {name}" for name in ("router_w", "router_down", PATTERN, MIXERS, "embed_w") if name in params), "nothing of a trunk's")
    not_one = lambda missing: f"not a trunk checkpoint (read as one by {told_by}): missing {missing}; got keys {sorted(params)[:8]}..."
    missing = [k for k in (*required, "value_fc1_b", "policy_b", HPARAMS) if k not in params]
    if missing:
        raise ValueError(not_one(missing))
    given = [float(v) for v in np.asarray(params[HPARAMS]).reshape(-1)]
    defaults = _hparams(TrunkConfig())
    names = (*_HPARAMS, *_ROPE_HPARAMS, *_BLOCK_HPARAMS, *_LOOP_HPARAMS)
    if not 3 <= len(given) <= len(names):
        raise ValueError(f"trunk checkpoint: {HPARAMS} has {len(given)} values, not 3 to {len(names)}")
    hp = dict(zip(names, [*given, *defaults[len(given):]]))
    with_block = len(names) - len(_LOOP_HPARAMS)
    if len(given) == with_block and not hp["block_length"]:  # only a file with a block length or a loop carries one (``trunk_checkpoint``)
        raise ValueError(f"trunk checkpoint: {HPARAMS} has {len(given)} values, the last a block length of 0: a file without one has {with_block - 1} at most")
    if len(given) > with_block and hp["loop_steps"] < 2:  # only a looped trunk's file carries the loop's numbers
        raise ValueError(f"trunk checkpoint: {HPARAMS} has {len(given)} values, loop_steps {hp['loop_steps']:g} among them: a file without a loop has {with_block} at most")
    shape = lambda name: tuple(int(n) for n in np.shape(params[name]))
    width_of = lambda name: shape(name)[2] if name in params else 0
    if pattern is not None:  # the mixers its characters name
        mixers, what = [kind for kind, mark in (("mamba", "M"), ("attention", "*")) if mark in pattern], f"a pattern {pattern!r}"
    elif by_layer is not None:  # the mixers its layers name
        mixers, what = [kind for kind in _MIXERS if kind in by_layer], f"mixers {by_layer}"
    elif "conv0_w" in params:
        mixers, what = ["cca"], "compressed convolutional attention (conv0_w)"
    elif "kv_norm" in params:
        mixers, what = ["latent"], "a latent (kv_norm)"
    else:
        mixers, what = ["attention"], None
    missing = [k for kind in mixers for k in _SIZES[kind][0] if k not in params]
    if missing:
        raise ValueError(f"trunk checkpoint: {what} without {missing}" if what else not_one(missing))
    sizes = {field: value for kind in mixers for field, value in _SIZES[kind][1](params, shape, hp).items()}
    (routed, _, experts), (layers, hidden) = (0, 0, TrunkConfig.experts) if routerless else shape(router), shape(norm)
    layout = dict(pattern=pattern) if pattern is not None else dict(layers=layers, dense_layers=layers - routed, mixers=by_layer)
    return _checked(params, len(given), lambda: TrunkConfig(
        hidden=hidden, **layout, **sizes,
        experts=experts, experts_per_token=int(round(hp["experts_per_token"])), expert_width=TrunkConfig.expert_width if routerless else shape("experts_up")[3],
        rope_theta=hp["rope_theta"], rms_eps=hp["rms_eps"],
        value_hidden=shape("value_fc1_b")[0], policy_planes=shape("policy_b")[0],
        nope_layers=tuple(i for i in range(layers) if int(hp["nope_mask"]) >> i & 1),
        sliding_window=int(hp["sliding_window"]) or None,
        gated_attention="wgate" in params, post_norms="post_attn_norm" in params, embed_scale=hp["embed_scale"],
        dense_width=width_of("dense_up"), shared_width=width_of("shared_up"), gated_ffn=("dense_gate" if routerless else "experts_gate") in params,
        router_score="sigmoid" if hp["sigmoid"] else "softmax", route_norm=bool(hp["route_norm"]), route_scale=hp["route_scale"],
        held_experts=None if hp["first_held"] < 0 or routerless else (int(hp["first_held"]), shape("experts_up")[1]),
        balance_rate=hp["balance_rate"], rotary_dim=int(hp["rotary_dim"]) or None,
        shared_token_gate="shared_token_gate" in params, zero_centered_norms=bool(hp["zero_centered"]),
        full_attention_layers=tuple(i for i in range(layers) if int(hp["full_mask"]) >> i & 1), rope_type="yarn" if hp["yarn"] else "default",
        rope_factor=hp["rope_factor"], original_max_position_embeddings=int(hp["original_max_position_embeddings"]),
        beta_fast=hp["beta_fast"], beta_slow=hp["beta_slow"], attention_factor=hp["attention_factor"],
        router_hidden=shape("router_down")[2] if "router_down" in params else 0, block_length=int(hp["block_length"]),
        loop_steps=int(round(hp["loop_steps"])), exit_threshold=hp["exit_threshold"],
    ))


def _checked(params: Params, given: int, make) -> TrunkConfig:
    """``make()``'s configuration if the file is exactly what it describes."""
    try:
        cfg = make()
    except ValueError as err:
        raise ValueError(f"trunk checkpoint: mismatched shapes: {err}") from err
    expected = {**trunk_param_shapes(cfg), **trunk_buffer_shapes(cfg), HPARAMS: (given,), **({PATTERN: (cfg.layers,)} if cfg.pattern else {}),
                **({MIXERS: (cfg.layers,)} if cfg.mixers else {})}
    got = {k: tuple(np.shape(v)) for k, v in params.items()}
    if expected != got:
        diff = set(expected) ^ set(got) or {k for k in expected if expected[k] != got[k]}
        raise ValueError(f"trunk checkpoint does not match any {cfg}: mismatched keys {sorted(diff)}")
    return cfg


def attention_heads_paired(cfg: TrunkConfig) -> float:
    """The share of the plan's (attention layer, query head)s whose scores
    ``board_attention`` makes two a product: the plain pair's and (since PR
    63) the block-masked pair's, by the group alone (``paired_heads``); 0 of
    a latent layer's, whose kernels are another body. Static: the choice is
    the shapes'. ``AzTrainer``'s ``train_init`` span carries it."""
    cores = [sublayer for sublayer in trunk_plan(cfg) if sublayer.kind in ("attention", "latent", "cca")]
    grouped = sum(sublayer.kind != "latent" for sublayer in cores)
    return grouped * paired_heads(cfg.heads, cfg.kv_heads or cfg.heads) / (len(cores) * cfg.heads) if cores else 0.0

"""Pallas TPU kernels for the two float32 chains of a Mamba-2 mixer over
the 64 squares of a board (``models/trunk.py _mamba``), one on each side
of the scan (``ops/board_scan.py``): the convolution with its silu, and
the gate with its grouped norm; and, since PR 55, for the chain after the
core of the two delta mixers (``_gdn``, ``_kda``): each head's norm under
its gate. Each is a memory-bound pass that reads its operands once and
writes its results once, a few boards in VMEM a grid step; left to XLA
each was a dozen float32 passes (PERF.md section 6, PR 44 and PR 55).

``mamba_conv(u, conv_w, conv_b, widths)`` with ``u`` float32 ``[boards,
64, columns]``, the x B C product's result as it is written, ``conv_w``
``[columns, taps]`` and ``conv_b`` ``[columns]``, gives the scan's
operands, one bfloat16 ``[boards, 64, width]`` for each of ``widths``
(x, B, C: they add up to ``columns``). ``t - s`` is an earlier square of
the same board, nothing before square 0::

    a[t] = b + sum_k w[:, k] * u[t - (taps - 1) + k]       depthwise, float32
    out  = silu(a)                                          float32, rounded once to bfloat16, cut into ``widths``

A grid step holds a few boards' rows ``[boards * 64, columns]`` and
works ONE board's lane tile ``[64, 128]`` at a time (8 vregs a value, so
a turn's values live in registers from its loads to its stores): a
shift along the squares is a rotation of the sublanes and a select on
the square's index (what the rotation brings in from the board's far end
is selected away, as ``ops/cca_mix.py``'s conv0). Nothing ``[..,
columns]`` is written and then sliced, and no float32 copy is kept: the
gradient kernel ``mamba_conv_grad`` reads ``u`` and the three bfloat16
cotangents once, makes ``a`` again (``taps`` multiply-adds: the one
thing recomputed), and writes the cotangent of ``u`` once, rounded to
bfloat16 (below); the taps' and the bias's gradients are summed over the grid's steps in VMEM
(their blocks stay where they are from step to step, 8 partial rows
each: whole vregs added, the last sum over 8 made outside) and leave
once, float32.

``mamba_gate_norm(y, z, gain, groups, eps)`` with ``y`` bfloat16
``[tokens, inner]`` (the scan's result), ``z`` float32 ``[tokens,
inner]`` (the z product's result) and ``gain`` ``[inner]`` gives what
the out-projection reads, bfloat16 ``[tokens, inner]``::

    g   = y * silu(z)                                       float32
    out = g * rsqrt(mean over the group's columns of g^2 + eps) * gain      ``groups`` groups of ``inner // groups`` columns

A grid step holds whole rows of two boards and works 16 rows of a group
at a time: at 512 columns a group its mean square is four lane tiles
added and one sum across lanes, so no ``[tokens, groups, width]`` view
and no broadcast reaches HBM. ``mamba_gate_norm_grad`` reads y, z and
the result's bfloat16 cotangent once, makes ``g`` and its norm again,
and writes y's cotangent (bfloat16, what ``board_scan_grad`` takes) and
z's (rounded to bfloat16, below) once; the gain's gradient is resident
across the grid as the taps' is.

``head_norm_gate(o, z, gain, gate, eps)`` with ``o`` bfloat16 ``[tokens,
heads x d]`` (the delta rule's result as ``ops/board_delta.py`` writes
it), ``z`` float32 ``[tokens, heads x d]`` (a product's result:
``gdn_qkvz``'s z columns; the logits ``(n W_ga) W_gb`` of KDA's low-rank
gate), ONE ``gain`` ``[d]`` for all heads and ``gate`` ``"silu"`` (Gated
DeltaNet) or ``"sigmoid"`` (Kimi Delta Attention) gives what the
out-projection reads, bfloat16 ``[tokens, heads x d]``. A different
equation of different published blocks than the pair above (the norm
BEFORE the gate, a head its own group, one gain), so a pair of its own
that shares the grid, the row loop and the helpers, and no body::

    n   = o * rsqrt(mean over the head's d columns of o^2 + eps) * gain     float32; at d = 128 a head is ONE lane tile
    out = n * silu(z)      or      n * sigmoid(z)                           rounded once to bfloat16

The heads are found from the shapes (``heads x d`` columns under a gain of
``d``); the bodies read the gain laid along them, a gain a column, so that
the grid, the blocks and their checks are ``mamba_gate_norm``'s; inside a
grid step the bodies work 32 rows at a time and eight heads a turn of a
loop over the heads (``_HEAD_ROWS``, ``_HEADS_A_TURN``: all heads unrolled
ran no faster and cost seconds of every start).
``head_norm_gate_grad`` reads o, z and the result's bfloat16 cotangent
once, makes ``n`` and the gate again (the one thing recomputed), and
writes o's cotangent (bfloat16, what ``board_delta_grad`` takes) and z's
(rounded to bfloat16, below) once; the gain's gradient is resident across
the grid, partial rows ``[8, heads x d]``, summed over the 8 and over the
heads outside.

Every value is rounded where JAX's own formula and its transposes round
it: the arithmetic is float32, the results that a bfloat16 product, the
scan or the delta rule reads are bfloat16, and so are their cotangents.
**The cotangents of ``u`` and of ``z``** (both pairs' ``z``) are float32 in name (the gradient
rules return them so, as a float32 operand's must be) and bfloat16 in
value: each is the result of a product with bfloat16 operands
(``trunk._matmul``), and all that reads its cotangent are that product's
two transposes, which round it to bfloat16 first. The kernels write it
rounded, once, in half the bytes; XLA folds the widening and the
products' narrowing away and the products read the kernels' arrays as
they are (``tests/test_trunk_tpu_compile.py``). A caller that feeds ``u``
or ``z`` from anything but such a product gets its cotangent to 8 bits;
all three callers meet it: ``_mamba``'s z and ``_gdn``'s are columns of
the layer's in-projection, ``_kda``'s the second product of its low-rank
gate.
The kernels
take every width that is whole 128-lane tiles (a group too); off the TPU
they run under the Pallas interpreter, which takes any.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fishnet_tpu.ops.board_attention import SQUARES
from fishnet_tpu.ops.cca_mix import _earlier, _later  # a shift along the squares: a rotation of sublanes and a select, here of ONE board's rows

__all__ = ["head_norm_gate", "mamba_conv", "mamba_gate_norm"]

_LANES = 128
#: Boards a grid step of the convolution pair: at 6,144 columns the gradient's blocks (u and its cotangent float32, three
#: bfloat16 cotangents) are 3.75 MiB a board, twice buffered. On a v5e 2, 4 and 8 read within 0.02 ms of each other
#: (PERF.md section 6, PR 44).
_CONV_BOARDS = 4
#: Rows a grid step of the gate-norm pair: at 4,096 columns the gradient's blocks (z and its cotangent float32; y, its
#: cotangent and the result's bfloat16) are 56 KiB a row, twice buffered. 64 to 512 read within 0.013 ms of each other.
_NORM_ROWS = 128
#: Rows the gate-norm pair works at a time inside a grid step: one packed bfloat16 tile, so that a group's ``[16, 512]``
#: float32 values (8 vregs each) live in registers from the loads to the stores. 8 and 32 read the same within 0.01 ms.
_NORM_CHUNK = 16
#: Sublanes of a vreg: a sum over rows is kept as 8 partial rows (adds of whole vregs) and finished outside the kernel.
_SUBLANES = 8
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20)


def _whole_tiles(name: str, interpret: bool, *widths: int) -> None:
    if not interpret and any(width % _LANES for width in widths):
        raise ValueError(f"{name}: widths {widths} are not whole {_LANES}-lane tiles")


def _partial_rows(x: jax.Array) -> jax.Array:
    """``[rows, lanes]`` -> ``[8, lanes]`` whose sum over rows is ``x``'s: whole vregs added, no sum across sublanes."""
    return jnp.sum(x.reshape(-1, _SUBLANES, x.shape[-1]), axis=0)


def _board_tiles(rows: int, widths: Tuple[int, ...], body) -> None:
    """``body(i, rows, lanes, source)`` for every board of a block's ``rows`` and every lane tile of the results side by side
    (``lanes`` in result ``i``, ``source`` in their joined columns; no tile straddles two results): ONE board's ``[64, 128]``
    a turn, 8 vregs a value, so that a turn's values live in registers from its loads to its stores."""
    def board(at, _):
        squares = pl.ds(pl.multiple_of(at * SQUARES, SQUARES), SQUARES)
        first = 0
        for i, width in enumerate(widths):
            tile = math.gcd(width, _LANES)

            def turn(j, _, i=i, tile=tile, first=first):
                body(i, squares, pl.ds(pl.multiple_of(j * tile, tile), tile), pl.ds(pl.multiple_of(first + j * tile, tile), tile))
                return 0

            jax.lax.fori_loop(0, width // tile, turn, 0)
            first += width
        return 0

    jax.lax.fori_loop(0, rows // SQUARES, board, 0)


def _conv(u: jax.Array, w_ref, b_ref, lanes):
    """``a`` for one board's lane tile, the shifted ``u`` a tap (the taps' gradient multiplies them again) and each row's square."""
    taps = w_ref.shape[0]
    square = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    shifted = [_earlier(u, taps - 1 - k, square) for k in range(taps)]
    return b_ref[:, lanes] + sum(w_ref[k:k + 1, lanes] * shifted[k] for k in range(taps)), shifted, square


def _conv_kernel(u_ref, w_ref, b_ref, *out_refs):
    def body(i, squares, lanes, source):
        a = _conv(u_ref[squares, source], w_ref, b_ref, source)[0]
        out_refs[i][squares, lanes] = (a * jax.nn.sigmoid(a)).astype(out_refs[i].dtype)

    _board_tiles(u_ref.shape[0], tuple(ref.shape[1] for ref in out_refs), body)


def _conv_grad_kernel(u_ref, w_ref, b_ref, *refs):
    f32 = jnp.float32
    d_refs, (du_ref, dw_ref, db_ref) = refs[:-3], refs[-3:]
    taps = w_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, f32)
        db_ref[...] = jnp.zeros(db_ref.shape, f32)

    def body(i, squares, lanes, source):
        a, shifted, square = _conv(u_ref[squares, source], w_ref, b_ref, source)
        s = jax.nn.sigmoid(a)
        da = d_refs[i][squares, lanes].astype(f32) * (s * (1.0 + a * (1.0 - s)))  # silu'(a) = s + a s (1 - s)
        db_ref[:, source] = db_ref[:, source] + _partial_rows(da)
        du = jnp.zeros(da.shape, f32)
        for k in range(taps):
            du = du + w_ref[k:k + 1, source] * _later(da, taps - 1 - k, square)
            dw_ref[k, :, source] = dw_ref[k, :, source] + _partial_rows(shifted[k] * da)
        du_ref[squares, source] = du.astype(du_ref.dtype)

    _board_tiles(u_ref.shape[0], tuple(ref.shape[1] for ref in d_refs), body)


def _whole(*shape: int) -> pl.BlockSpec:
    """An operand every grid step sees whole; as a result, one that stays in VMEM across the grid and leaves once."""
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _conv_specs(u: jax.Array, conv_w: jax.Array, widths: Tuple[int, ...], interpret: bool):
    boards, squares, columns = u.shape
    if squares != SQUARES or sum(widths) != columns or conv_w.shape[0] != columns:
        raise ValueError(f"mamba_conv: u {u.shape} is not [boards, {SQUARES}, {' + '.join(map(str, widths))}] under taps {conv_w.shape}")
    _whole_tiles("mamba_conv", interpret, *widths)
    rows = math.gcd(boards, _CONV_BOARDS) * SQUARES
    by_rows = lambda lanes: pl.BlockSpec((rows, lanes), lambda i: (i, 0))
    return boards * SQUARES // rows, by_rows(columns), [by_rows(width) for width in widths], _whole(conv_w.shape[1], columns), _whole(1, columns)


def _conv_operands(u, conv_w, conv_b):
    """The operands as the kernels read them: tokens down the rows, the taps down the sublanes."""
    return u.reshape(-1, u.shape[-1]), conv_w.astype(jnp.float32).T, conv_b.astype(jnp.float32).reshape(1, -1)


#: A mixer's kernels are called under ``jax.jit``: a step's three mixers (and the forward-only program beside it) then share
#: ONE trace of each kernel and one Mosaic lowering a program, where a bare ``pallas_call`` is traced and lowered again at every
#: call site, at every start (ROADMAP S11; PERF.md section 6, PR 44). The call sites' scopes still name each call's operations.
_conv_jit = functools.partial(jax.jit, static_argnames=("widths", "interpret"))
_norm_jit = functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))


def _called(call, interpret: bool):
    """``call`` under its ``jax.jit`` where Mosaic compiles the kernel, bare under the interpreter: there a kernel is loops
    of XLA's own, which XLA names after the call's scope, and a step's text may not follow its scopes
    (``tests/test_train_observability.py``); nothing is lowered for a chip there to be shared."""
    return call.__wrapped__ if interpret else call


@_conv_jit
def _conv_call(u, conv_w, conv_b, *, widths: Tuple[int, ...], interpret: bool):
    steps, u_spec, out_specs, w_spec, b_spec = _conv_specs(u, conv_w, widths, interpret)
    outs = pl.pallas_call(
        _conv_kernel,
        grid=(steps,),
        in_specs=[u_spec, w_spec, b_spec],
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((u.shape[0] * SQUARES, width), jnp.bfloat16) for width in widths],
        compiler_params=_PARAMS,
        name="mamba_conv",
        interpret=interpret,
    )(*_conv_operands(u, conv_w, conv_b))
    return tuple(out.reshape(u.shape[0], SQUARES, -1) for out in outs)


@_conv_jit
def _conv_grad_call(u, conv_w, conv_b, cotangents, *, widths: Tuple[int, ...], interpret: bool):
    steps, u_spec, d_specs, w_spec, b_spec = _conv_specs(u, conv_w, widths, interpret)
    taps, columns = conv_w.shape[1], u.shape[-1]
    du, dw, db = pl.pallas_call(
        _conv_grad_kernel,
        grid=(steps,),
        in_specs=[u_spec, w_spec, b_spec, *d_specs],
        out_specs=[u_spec, _whole(taps, _SUBLANES, columns), _whole(_SUBLANES, columns)],
        out_shape=[jax.ShapeDtypeStruct((u.shape[0] * SQUARES, columns), jnp.bfloat16), jax.ShapeDtypeStruct((taps, _SUBLANES, columns), jnp.float32),
                   jax.ShapeDtypeStruct((_SUBLANES, columns), jnp.float32)],
        compiler_params=_PARAMS,
        name="mamba_conv_grad",
        interpret=interpret,
    )(*_conv_operands(u, conv_w, conv_b), *(d.astype(jnp.bfloat16).reshape(-1, d.shape[-1]) for d in cotangents))
    return du.reshape(u.shape).astype(u.dtype), dw.sum(axis=1).T.astype(conv_w.dtype), db.sum(axis=0).astype(conv_b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def mamba_conv(u: jax.Array, conv_w: jax.Array, conv_b: jax.Array, widths: Tuple[int, ...], interpret: bool = False) -> Tuple[jax.Array, ...]:
    """``silu(conv(u))`` cut into ``widths`` (module docstring): ``u``
    float32 ``[boards, 64, sum(widths)]`` -> one bfloat16 ``[boards, 64,
    width]`` a width."""
    return _called(_conv_call, interpret)(u, conv_w, conv_b, widths=widths, interpret=interpret)


def _mamba_conv_fwd(u, conv_w, conv_b, widths, interpret):
    return mamba_conv(u, conv_w, conv_b, widths, interpret), (u, conv_w, conv_b)


def _mamba_conv_bwd(widths, interpret, residuals, cotangents):
    return _called(_conv_grad_call, interpret)(*residuals, tuple(cotangents), widths=widths, interpret=interpret)


mamba_conv.defvjp(_mamba_conv_fwd, _mamba_conv_bwd)


def _row_chunks(rows: int, body, at_a_time: int = 0) -> None:
    """``body(rows)`` for every ``_NORM_CHUNK`` (or ``at_a_time``) rows of a block, in turn."""
    chunk = math.gcd(rows, at_a_time or _NORM_CHUNK)

    def turn(at, _):
        body(pl.ds(pl.multiple_of(at * chunk, chunk), chunk))
        return 0

    jax.lax.fori_loop(0, rows // chunk, turn, 0)


def _gated(y_ref, z_ref, rows, lanes, eps: float):
    """A group's ``y``, ``sigmoid(z)``, ``silu(z)``, the normed ``g = y * silu(z)`` and ``rsqrt(mean g^2 + eps)`` ``[rows, 1]``."""
    y, z = y_ref[rows, lanes].astype(jnp.float32), z_ref[rows, lanes]
    s = jax.nn.sigmoid(z)
    silu = z * s
    g = y * silu
    r = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return y, s, silu, g * r, r


def _gate_norm_kernel(y_ref, z_ref, gain_ref, out_ref, *, groups: int, eps: float):
    width = y_ref.shape[1] // groups

    def body(rows):
        for group in range(groups):  # unrolled: a loop over the groups too reads 0.2 ms a call slower on a v5e (PERF.md section 6, PR 44)
            lanes = slice(group * width, (group + 1) * width)
            normed = _gated(y_ref, z_ref, rows, lanes, eps)[3]
            out_ref[rows, lanes] = (normed * gain_ref[:, lanes]).astype(out_ref.dtype)

    _row_chunks(y_ref.shape[0], body)


def _gate_norm_grad_kernel(y_ref, z_ref, gain_ref, d_ref, dy_ref, dz_ref, dgain_ref, *, groups: int, eps: float):
    width = y_ref.shape[1] // groups

    @pl.when(pl.program_id(0) == 0)
    def _():
        dgain_ref[...] = jnp.zeros(dgain_ref.shape, jnp.float32)

    def body(rows):
        for group in range(groups):
            lanes = slice(group * width, (group + 1) * width)
            y, s, silu, normed, r = _gated(y_ref, z_ref, rows, lanes, eps)
            d = d_ref[rows, lanes].astype(jnp.float32)
            dgain_ref[:, lanes] = dgain_ref[:, lanes] + _partial_rows(d * normed)
            dn = d * gain_ref[:, lanes]
            dg = r * (dn - normed * jnp.mean(dn * normed, axis=-1, keepdims=True))
            dy_ref[rows, lanes] = (dg * silu).astype(dy_ref.dtype)
            dz_ref[rows, lanes] = (dg * y * (s + silu * (1.0 - s))).astype(dz_ref.dtype)

    _row_chunks(y_ref.shape[0], body)


def _norm_specs(y: jax.Array, z: jax.Array, gain: jax.Array, groups: int, interpret: bool, name: str = "mamba_gate_norm"):
    tokens, inner = y.shape
    if z.shape != y.shape or gain.shape != (inner,) or inner % groups:
        raise ValueError(f"{name}: y {y.shape}, z {z.shape} and gain {gain.shape} are not [tokens, inner] twice and [inner] in {groups} groups")
    _whole_tiles(name, interpret, inner // groups)
    rows = math.gcd(tokens, _NORM_ROWS)
    return tokens // rows, pl.BlockSpec((rows, inner), lambda i: (i, 0)), _whole(1, inner)


@_norm_jit
def _gate_norm_call(y, z, gain, *, groups: int, eps: float, interpret: bool):
    steps, by_rows, whole = _norm_specs(y, z, gain, groups, interpret)
    return pl.pallas_call(
        functools.partial(_gate_norm_kernel, groups=groups, eps=eps),
        grid=(steps,),
        in_specs=[by_rows, by_rows, whole],
        out_specs=by_rows,
        out_shape=jax.ShapeDtypeStruct(y.shape, jnp.bfloat16),
        compiler_params=_PARAMS,
        name="mamba_gate_norm",
        interpret=interpret,
    )(y.astype(jnp.bfloat16), z.astype(jnp.float32), gain.astype(jnp.float32).reshape(1, -1))


@_norm_jit
def _gate_norm_grad_call(y, z, gain, d, *, groups: int, eps: float, interpret: bool):
    steps, by_rows, whole = _norm_specs(y, z, gain, groups, interpret)
    dy, dz, dgain = pl.pallas_call(
        functools.partial(_gate_norm_grad_kernel, groups=groups, eps=eps),
        grid=(steps,),
        in_specs=[by_rows, by_rows, whole, by_rows],
        out_specs=[by_rows, by_rows, _whole(_SUBLANES, y.shape[1])],
        out_shape=[jax.ShapeDtypeStruct(y.shape, jnp.bfloat16), jax.ShapeDtypeStruct(z.shape, jnp.bfloat16), jax.ShapeDtypeStruct((_SUBLANES, y.shape[1]), jnp.float32)],
        compiler_params=_PARAMS,
        name="mamba_gate_norm_grad",
        interpret=interpret,
    )(y.astype(jnp.bfloat16), z.astype(jnp.float32), gain.astype(jnp.float32).reshape(1, -1), d.astype(jnp.bfloat16))
    return dy.astype(y.dtype), dz.astype(z.dtype), dgain.sum(axis=0).astype(gain.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def mamba_gate_norm(y: jax.Array, z: jax.Array, gain: jax.Array, groups: int, eps: float, interpret: bool = False) -> jax.Array:
    """The grouped RMS norm of ``y * silu(z)`` under ``gain`` (module
    docstring): ``y`` bfloat16 and ``z`` float32 ``[tokens, inner]`` ->
    bfloat16 ``[tokens, inner]``."""
    return _called(_gate_norm_call, interpret)(y, z, gain, groups=groups, eps=eps, interpret=interpret)


def _mamba_gate_norm_fwd(y, z, gain, groups, eps, interpret):
    return mamba_gate_norm(y, z, gain, groups, eps, interpret), (y, z, gain)


def _mamba_gate_norm_bwd(groups, eps, interpret, residuals, d):
    return _called(_gate_norm_grad_call, interpret)(*residuals, d, groups=groups, eps=eps, interpret=interpret)


mamba_gate_norm.defvjp(_mamba_gate_norm_fwd, _mamba_gate_norm_bwd)


# -- the head norm under its gate: the delta mixers' (``trunk._gdn``, ``trunk._kda``) ------------------------------------------------

_GATES = ("silu", "sigmoid")
#: Heads unrolled a turn of the loop over a block's heads, and rows the pair works at a time. A loop's turns do not overlap, so a turn
#: has to hold enough independent heads and rows to hide a sum across lanes, and every unrolled head is traced and lowered at every
#: start. On a v5e, ``[8192, 4096]`` in 32 heads, forward + gradient ms (PERF.md section 6, PR 55): all 32 heads unrolled at 16 rows
#: 0.387 + 0.499, and 3.2 s more of ``setup_s`` than the parent's XLA lines; 8 heads at 32 rows 0.391 + 0.504 for a quarter of the
#: trace; 8 at 16 rows 0.417 + 0.591, 4 at 64 0.397 + 0.504, 4 at 16 0.510 + 0.939, 1 at 16 1.811 + 3.111.
_HEADS_A_TURN = 8
_HEAD_ROWS = 32
_head_jit = functools.partial(jax.jit, static_argnames=("gate", "eps", "interpret"))


def _head_normed(o_ref, z_ref, gain_ref, rows, lanes, gate: str, eps: float):
    """A head's ``o r`` with ``r = rsqrt(mean o^2 + eps)`` ``[rows, 1]``, ``r``, ``n = o r gain``, ``z``, ``sigmoid(z)`` and the gate's value."""
    o, z = o_ref[rows, lanes].astype(jnp.float32), z_ref[rows, lanes]
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    s = jax.nn.sigmoid(z)
    unit = o * r
    return unit, r, unit * gain_ref[:, lanes], z, s, (z * s if gate == "silu" else s)


def _head_turns(heads: int, width: int, body) -> None:
    """``body(lanes)`` for every head of a block's columns: ``_HEADS_A_TURN`` heads unrolled a turn of a loop over the rest, at
    dynamic offsets of whole heads (tile-aligned at a head of whole lane tiles)."""
    unrolled = math.gcd(heads, _HEADS_A_TURN)

    def turn(at, _):
        for head in range(unrolled):
            body(pl.ds(pl.multiple_of((at * unrolled + head) * width, width), width))
        return 0

    jax.lax.fori_loop(0, heads // unrolled, turn, 0)


def _head_norm_kernel(o_ref, z_ref, gain_ref, out_ref, *, heads: int, gate: str, eps: float):
    def body(rows):
        def head(lanes):
            _, _, n, _, _, a = _head_normed(o_ref, z_ref, gain_ref, rows, lanes, gate, eps)
            out_ref[rows, lanes] = (n * a).astype(out_ref.dtype)

        _head_turns(heads, o_ref.shape[1] // heads, head)

    _row_chunks(o_ref.shape[0], body, _HEAD_ROWS)


def _head_norm_grad_kernel(o_ref, z_ref, gain_ref, d_ref, do_ref, dz_ref, dgain_ref, *, heads: int, gate: str, eps: float):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dgain_ref[...] = jnp.zeros(dgain_ref.shape, jnp.float32)

    def body(rows):
        def head(lanes):
            unit, r, n, z, s, a = _head_normed(o_ref, z_ref, gain_ref, rows, lanes, gate, eps)
            d = d_ref[rows, lanes].astype(jnp.float32)
            slope = s * (1.0 + z * (1.0 - s)) if gate == "silu" else s * (1.0 - s)  # silu'(z) = s + z s (1 - s); sigmoid'(z) = s (1 - s)
            dz_ref[rows, lanes] = (d * n * slope).astype(dz_ref.dtype)
            dn = d * a
            dgain_ref[:, lanes] = dgain_ref[:, lanes] + _partial_rows(dn * unit)
            du = dn * gain_ref[:, lanes]
            do_ref[rows, lanes] = (r * (du - unit * jnp.mean(du * unit, axis=-1, keepdims=True))).astype(do_ref.dtype)

        _head_turns(heads, o_ref.shape[1] // heads, head)

    _row_chunks(o_ref.shape[0], body, _HEAD_ROWS)


def _by_head(o: jax.Array, gain: jax.Array, gate: str):
    """The heads, and the one gain laid along them: a gain a column, as ``_norm_specs`` checks it and the bodies read it."""
    if gate not in _GATES or o.ndim != 2 or gain.ndim != 1 or o.shape[1] % gain.shape[0]:
        raise ValueError(f"head_norm_gate: o {o.shape} under a gain {gain.shape} and the gate {gate!r} is not [tokens, heads x d] under [d] and one of {_GATES}")
    heads = o.shape[1] // gain.shape[0]
    return heads, jnp.tile(gain.astype(jnp.float32), heads)


@_head_jit
def _head_norm_call(o, z, gain, *, gate: str, eps: float, interpret: bool):
    heads, gains = _by_head(o, gain, gate)
    steps, by_rows, whole = _norm_specs(o, z, gains, heads, interpret, "head_norm_gate")
    return pl.pallas_call(
        functools.partial(_head_norm_kernel, heads=heads, gate=gate, eps=eps),
        grid=(steps,),
        in_specs=[by_rows, by_rows, whole],
        out_specs=by_rows,
        out_shape=jax.ShapeDtypeStruct(o.shape, jnp.bfloat16),
        compiler_params=_PARAMS,
        name="head_norm_gate",
        interpret=interpret,
    )(o.astype(jnp.bfloat16), z.astype(jnp.float32), gains.reshape(1, -1))


@_head_jit
def _head_norm_grad_call(o, z, gain, d, *, gate: str, eps: float, interpret: bool):
    heads, gains = _by_head(o, gain, gate)
    steps, by_rows, whole = _norm_specs(o, z, gains, heads, interpret, "head_norm_gate")
    do, dz, dgain = pl.pallas_call(
        functools.partial(_head_norm_grad_kernel, heads=heads, gate=gate, eps=eps),
        grid=(steps,),
        in_specs=[by_rows, by_rows, whole, by_rows],
        out_specs=[by_rows, by_rows, _whole(_SUBLANES, o.shape[1])],
        out_shape=[jax.ShapeDtypeStruct(o.shape, jnp.bfloat16), jax.ShapeDtypeStruct(z.shape, jnp.bfloat16), jax.ShapeDtypeStruct((_SUBLANES, o.shape[1]), jnp.float32)],
        compiler_params=_PARAMS,
        name="head_norm_gate_grad",
        interpret=interpret,
    )(o.astype(jnp.bfloat16), z.astype(jnp.float32), gains.reshape(1, -1), d.astype(jnp.bfloat16))
    return do.astype(o.dtype), dz.astype(z.dtype), dgain.reshape(-1, gain.shape[0]).sum(axis=0).astype(gain.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def head_norm_gate(o: jax.Array, z: jax.Array, gain: jax.Array, gate: str, eps: float, interpret: bool = False) -> jax.Array:
    """Each head's RMS norm of ``o`` under the one ``gain``, times
    ``silu(z)`` or ``sigmoid(z)`` (module docstring): ``o`` bfloat16 and
    ``z`` float32 ``[tokens, heads x d]``, ``gain`` ``[d]`` -> bfloat16
    ``[tokens, heads x d]``."""
    return _called(_head_norm_call, interpret)(o, z, gain, gate=gate, eps=eps, interpret=interpret)


def _head_norm_gate_fwd(o, z, gain, gate, eps, interpret):
    return head_norm_gate(o, z, gain, gate, eps, interpret), (o, z, gain)


def _head_norm_gate_bwd(gate, eps, interpret, residuals, d):
    return _called(_head_norm_grad_call, interpret)(*residuals, d, gate=gate, eps=eps, interpret=interpret)


head_norm_gate.defvjp(_head_norm_gate_fwd, _head_norm_gate_bwd)

"""What a Mamba-2 mixer passes into and out of the chunked scan, one pass each.

The scan's products want a chunk's tokens in the lanes: a head of 64 does
not fill them, so ``x`` and ``y`` cross the scan as ``[chunks, B, H x P,
chunk]`` (``ops/chunked_scan.py``), while the projections on either side
hold the tokens major, ``[B, T, H x P]``.  Left to the compiler the change
between the two is a slice, a transposing copy, a pad and two copies that
move the chunks to the front, each over the whole array, and the gate and
norm after the scan run over a float32 copy of ``y``.  Here the pass that
makes the values also turns them, a block at a time in VMEM:

``conv_to_chunks``  the causal depthwise convolution and its SiLU, read as
                    the projection wrote its input and written cut, zeros
                    past T, ``x`` turned and ``B``, ``C`` not.  Its backward
                    pass reads the cotangent as the scan's backward wrote
                    it, computes the sum again and writes the cotangent of
                    the sum, tokens major, with a chunk's part of the
                    kernel's and the bias' gradients.
``gated_norm``      ``rmsnorm(y silu(z)) w`` over rows of ``C`` (or, told
                    groups, over each of a row's runs of ``C / groups``
                    channels: a block is then one group's columns), reading
                    ``y`` as the scan writes it and ``z`` as the projection does;
                    its backward pass reads ``y``, ``z`` and the cotangent,
                    computes the row again and writes ``dy`` in the scan's
                    layout (zeros past T) and ``dz`` in the projection's.

Sums, sigmoids, the mean square and the norm are float32 inside a block and
nothing float32 of an activation's size is written.  A block is one chunk of
one sequence: whole rows of ``C`` for the norm, a chunk by at most 512
columns for the convolution, with the 16 rows before the chunk as a second
block of the same array.  Off the TPU the kernels run in Pallas' interpreter
(``blocked_attention.INTERPRET``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ape_x_dqn_tpu.ops.chunked_scan import chunks_of
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked

_F32 = jnp.float32


def _call(kernel, vmem=None, **kw):
    """``vmem``: bytes of VMEM for a kernel whose blocks do not fit the compiler's default."""
    interpret = jax.default_backend() != "tpu" if blocked.INTERPRET is None else blocked.INTERPRET
    return pl.pallas_call(kernel, interpret=interpret, compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel",) * len(kw["grid"]), vmem_limit_bytes=vmem), **kw)


def _columns(c: int) -> int:
    """The columns of a turned block: whole lanes, at most 512."""
    return next((w for w in (512, 256, 128) if c % w == 0), c)


def _own(tokens: int, shape):
    """Which rows of a chunk's block [chunk, columns] are the sequence's own."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) + pl.program_id(1) * shape[0] < tokens


def _turn(v, dtype):
    return v.astype(_F32).T.astype(dtype)


def _silu_slope(v, sig):
    """``d silu(v) / dv`` from ``sig = sigmoid(v)``."""
    return sig * (1.0 + v * (1.0 - sig))


def _conv(v_ref, before_ref, k_ref, bias_ref, tokens):
    """(which of a chunk's rows are the sequence's own; for each tap the rows
    it multiplies, [chunk, columns] float32 with zeros before t = 0 and past
    T; the convolution's sum over them with the bias)."""
    halo, taps, (q, w) = before_ref.shape[1], k_ref.shape[0], v_ref.shape[1:]
    own = _own(tokens, (q, w))
    rows = jnp.concatenate([jnp.where(pl.program_id(1) == 0, 0.0, before_ref[0].astype(_F32)),
                            jnp.where(own, v_ref[0].astype(_F32), 0.0)], 0)
    lag = halo - (taps - 1)
    under = [rows[lag + j:lag + j + q] for j in range(taps)]
    return own, under, bias_ref[...] + sum(k_ref[j:j + 1] * under[j] for j in range(taps))


def _conv_kernel(v_ref, before_ref, k_ref, bias_ref, o_ref, *, tokens, turned):
    own, _, pre = _conv(v_ref, before_ref, k_ref, bias_ref, tokens)
    act = jnp.where(own, pre * jax.nn.sigmoid(pre), 0.0)
    o_ref[0, 0] = _turn(act, o_ref.dtype) if turned else act.astype(o_ref.dtype)


def _conv_bwd_kernel(v_ref, before_ref, k_ref, bias_ref, d_ref, dpre_ref, dk_ref, *, tokens, turned):
    own, under, pre = _conv(v_ref, before_ref, k_ref, bias_ref, tokens)
    sig = jax.nn.sigmoid(pre)
    d = _turn(d_ref[0, 0], _F32) if turned else d_ref[0, 0].astype(_F32)
    dpre = jnp.where(own, d * _silu_slope(pre, sig), 0.0)
    dpre_ref[0] = dpre.astype(dpre_ref.dtype)
    dk_ref[0, 0] = jnp.concatenate(                                 # the taps' sums, then the bias'
        [jnp.sum(dpre * rows, 0, keepdims=True) for rows in under]
        + [jnp.sum(dpre, 0, keepdims=True)], 0)


def _conv_specs(v, taps: int, chunk: int, turned: bool):
    rows, tokens, c = v.shape
    n, padded = chunks_of(tokens, chunk)
    q, w = padded // n, _columns(c)
    halo = min(q, 16)                       # whole sublane tiles of the compute type
    if q % halo or (n > 1 and halo < taps - 1):
        raise ValueError(f"a chunk of {q} tokens cannot hold the {taps - 1} rows before a chunk")
    major = pl.BlockSpec((1, q, w), lambda b, i, j: (b, i, j))
    before = pl.BlockSpec((1, halo, w), lambda b, i, j: (b, jnp.maximum(i * (q // halo) - 1, 0), j))
    cut = (pl.BlockSpec((1, 1, w, q), lambda b, i, j: (i, b, j, 0)) if turned
           else pl.BlockSpec((1, 1, q, w), lambda b, i, j: (i, b, 0, j)))
    taps_spec = pl.BlockSpec((taps, w), lambda b, i, j: (0, j))
    bias_spec = pl.BlockSpec((1, w), lambda b, i, j: (0, j))
    shape = (n, rows, c, q) if turned else (n, rows, q, c)
    return (rows, n, c // w), [major, before, taps_spec, bias_spec], cut, shape, w


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def conv_to_chunks(v, kernel, bias, chunk: int, turned: bool):
    """``silu(bias + sum_j kernel[:, j] v[t - (taps - 1) + j])``, the causal
    depthwise convolution of ``v`` [B, T, C] with ``kernel`` [C, taps] and
    its SiLU, written cut in chunks with zeros past T: [chunks, B, C, chunk]
    if ``turned``, else [chunks, B, chunk, C]; the sum float32."""
    grid, ins, cut, shape, _ = _conv_specs(v, kernel.shape[1], chunk, turned)
    return _call(
        functools.partial(_conv_kernel, tokens=v.shape[1], turned=turned), grid=grid, in_specs=ins,
        out_specs=cut, out_shape=jax.ShapeDtypeStruct(shape, v.dtype))(
            v, v, kernel.T.astype(_F32), bias[None].astype(_F32))


def _conv_fwd(v, kernel, bias, chunk, turned):
    return conv_to_chunks(v, kernel, bias, chunk, turned), (v, kernel, bias)


def _conv_bwd(chunk, turned, kept, d):
    v, kernel, bias = kept
    taps = kernel.shape[1]
    grid, ins, cut, _, w = _conv_specs(v, taps, chunk, turned)
    dpre, dk = _call(
        functools.partial(_conv_bwd_kernel, tokens=v.shape[1], turned=turned), grid=grid,
        in_specs=ins + [cut],
        out_specs=[ins[0], pl.BlockSpec((1, 1, taps + 1, w), lambda b, i, j: (b, i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((*grid[:2], taps + 1, v.shape[2]), _F32)])(
            v, v, kernel.T.astype(_F32), bias[None].astype(_F32), d)
    dk = jnp.sum(dk, (0, 1))
    # a token's input reached the taps - 1 tokens after it too
    later = jnp.pad(dpre, ((0, 0), (0, taps - 1), (0, 0)))
    dv = sum(later[:, taps - 1 - j:taps - 1 - j + v.shape[1]] * kernel[:, j].astype(v.dtype)
             for j in range(taps))
    return dv, dk[:taps].T.astype(kernel.dtype), dk[taps].astype(bias.dtype)


conv_to_chunks.defvjp(_conv_fwd, _conv_bwd)


def _gated_row(y_ref, z_ref, eps):
    """(``y`` [chunk, C], ``z``, ``sigmoid(z)``, the gated row ``g`` and its
    ``rsqrt(mean(g^2) + eps)``), float32."""
    y, z = _turn(y_ref[0, 0], _F32), z_ref[0].astype(_F32)
    sig = jax.nn.sigmoid(z)
    g = y * (z * sig)
    return y, z, sig, g, jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)


def _gated_norm_kernel(y_ref, z_ref, w_ref, o_ref, *, eps):
    _, _, _, g, r = _gated_row(y_ref, z_ref, eps)
    o_ref[0] = (g * r * w_ref[...]).astype(o_ref.dtype)


def _gated_norm_bwd_kernel(y_ref, z_ref, w_ref, d_ref, dy_ref, dz_ref, dw_ref, *, eps, tokens):
    y, z, sig, g, r = _gated_row(y_ref, z_ref, eps)
    own = _own(tokens, y.shape)
    d = jnp.where(own, d_ref[0].astype(_F32), 0.0)                  # the block's rows past T hold anything
    normed = jnp.where(own, g * r, 0.0)
    dw_ref[0, 0] = jnp.sum(d * normed, 0, keepdims=True)
    dn = d * w_ref[...]
    dg = r * (dn - normed * jnp.mean(dn * normed, -1, keepdims=True))
    dy_ref[0, 0] = _turn(jnp.where(own, dg * (z * sig), 0.0), dy_ref.dtype)
    dz_ref[0] = (dg * y * _silu_slope(z, sig)).astype(dz_ref.dtype)


def _norm_specs(y, z, blocks: int, float32_rows: int, groups: int):
    """Grid, the three block specs, the weight's gradient's and the VMEM a
    pass needs: its ``blocks`` of a chunk by ``C`` twice (one in flight) and
    ``float32_rows`` such arrays in float32: 29 MiB forward and 53 backward
    at C = 4,096 and a chunk of 256 (compiled for a v5e the kernels take 24
    and 48; the default is 16 of the chip's 128, and what a kernel reserves
    the compiler cannot prefetch into: PERF.md section 6, PR 35).  With
    ``groups`` over 1 a block is one group's ``C / groups`` columns and the
    grid has the groups as its last axis."""
    n, rows, c, q = y.shape
    if c % groups:
        raise ValueError(f"{c} channels are not {groups} groups")
    c //= groups
    g = lambda at: at or (0,)  # noqa: E731  the group's block; one group has no grid axis
    cut = pl.BlockSpec((1, 1, c, q), lambda b, i, *at: (i, b, *g(at), 0))
    major = pl.BlockSpec((1, q, c), lambda b, i, *at: (b, i, *g(at)))
    weight = pl.BlockSpec((1, c), lambda b, i, *at: (0, *g(at)))
    dweight = pl.BlockSpec((1, 1, 1, c), lambda b, i, *at: (b, i, 0, *g(at)))
    vmem = q * c * (2 * blocks * y.dtype.itemsize + 4 * float32_rows) + 2 ** 20
    return (rows, n) + (groups,) * (groups > 1), cut, major, weight, dweight, vmem


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_norm(y, z, w, eps: float, groups: int = 1):
    """``rmsnorm(y silu(z)) w`` over the last axis of ``z`` [B, T, C], in
    ``z``'s type, the mean square over each of ``groups`` runs of ``C /
    groups`` channels; ``y`` [chunks, B, C, chunk] as the scan writes it,
    ``w`` [C] float32.  Gate, mean square and norm in float32."""
    grid, cut, major, weight, _, vmem = _norm_specs(y, z, 3, 4, groups)
    return _call(functools.partial(_gated_norm_kernel, eps=eps), vmem, grid=grid,
                 in_specs=[cut, major, weight], out_specs=major,
                 out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype))(y, z, w[None].astype(_F32))


def _gated_norm_fwd(y, z, w, eps, groups):
    return gated_norm(y, z, w, eps, groups), (y, z, w)


def _gated_norm_bwd(eps, groups, kept, d):
    y, z, w = kept
    grid, cut, major, weight, dweight, vmem = _norm_specs(y, z, 5, 8, groups)
    n, rows, c, _ = y.shape
    dy, dz, dw = _call(
        functools.partial(_gated_norm_bwd_kernel, eps=eps, tokens=z.shape[1]), vmem, grid=grid,
        in_specs=[cut, major, weight, major],
        out_specs=[cut, major, dweight],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype), jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((rows, n, 1, c), _F32)])(y, z, w[None].astype(_F32), d)
    return dy, dz, jnp.sum(dw, (0, 1, 2)).astype(w.dtype)


gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)

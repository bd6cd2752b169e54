"""Blocked masked attention: causal, or causal inside a window of the last
``window`` keys, grouped-query, never holding a ``[T, T]`` score tensor.

The kernels are JAX's splash attention (``jax.experimental.pallas.ops.tpu.
splash_attention``): flash attention over blocks of queries and keys with the
mask given as data, so that a block the mask empties is never visited, in the
forward pass, in a recomputation and in the two backward kernels (dq, dk/dv)
alike; scores and softmax in float32.  What is this module's: the mask of a
layer kind (``window`` or none), the block sizes and the padded length from
the shapes (``plan``), the padding (keys past the end lie in no query's
causal past, the padded queries' rows are cut off and get no gradient), and
what the shapes alone say of the work: ``pairs_in_mask`` and
``blocks_visited``.

Off the TPU the kernels run in Pallas' interpreter (``INTERPRET`` None); a
test that compiles for a described TPU sets ``INTERPRET`` False.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

INTERPRET: Optional[bool] = None
_LANES = 128


@dataclasses.dataclass(frozen=True)
class Plan:
    """The padded length and the kernels' block sizes for one sequence."""

    padded: int
    block_q: int
    block_kv: int
    block_kv_compute: int


def plan(tokens: int, window: Optional[int]) -> Plan:
    """From the shapes.  Under a window: square blocks of the window's size
    in whole lanes, at most 512 (a query block then sees its own and the
    block before it), the length padded to whole blocks.  Causal alone: the
    length padded to a multiple of 256 and cut in two blocks each way; past
    2,048 tokens blocks of 512.  A block's keys are multiplied all at once.
    (Read on a TPU v5e at B=8, 8 key-value heads of 128, T=1,568, forward
    and backward, against smaller and larger blocks: PERF.md section 6, PR
    32.  At heads of 64, 32 over 8, the causal plan is used as it stands and
    was read at this one block size: PERF.md section 6, PR 34.)"""
    up = lambda n, m: -(-n // m) * m  # noqa: E731
    if window is not None:
        block = min(up(window, _LANES), 512)
        return Plan(up(tokens, block), block, block, block)
    padded = up(tokens, 2 * _LANES)
    if padded > 2048:
        return Plan(up(tokens, 512), 512, 512, 512)
    return Plan(padded, padded // 2, padded // 2, padded // 2)


def pairs_in_mask(tokens: int, window: Optional[int]) -> int:
    """(query, key) pairs the mask lets through: key ``j`` for query ``i``
    if ``j <= i`` and, under a window, ``j > i - window``."""
    w = tokens if window is None else min(window, tokens)
    return w * (w + 1) // 2 + (tokens - w) * w


def blocks_visited(tokens: int, window: Optional[int]) -> tuple:
    """(blocks of the forward kernel's grid that hold a pair in the mask,
    blocks of the whole grid), a head, over the padded length."""
    p = plan(tokens, window)
    visited = 0
    for r0 in range(0, p.padded, p.block_q):
        for c0 in range(0, p.padded, p.block_kv):
            # key - query runs from c0 - r1 to c1 - r0 over the block
            reaches = c0 - (r0 + p.block_q - 1) <= 0
            inside = window is None or (c0 + p.block_kv - 1) - r0 >= -(window - 1)
            visited += reaches and inside
    return visited, (p.padded // p.block_q) * (p.padded // p.block_kv)


@functools.lru_cache(maxsize=None)
def _kernel(p: Plan, heads: int, window: Optional[int], interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks,
    )

    shape = (p.padded, p.padded)
    mask = (masks.CausalMask(shape) if window is None
            else masks.LocalMask(shape, (window - 1, 0), 0))
    sizes = kernel.BlockSizes(
        block_q=p.block_q, block_kv=p.block_kv, block_kv_compute=p.block_kv_compute,
        block_q_dkv=p.block_q, block_kv_dkv=p.block_kv,
        block_kv_dkv_compute=p.block_kv_compute,
        block_q_dq=p.block_q, block_kv_dq=p.block_kv)
    with jax.ensure_compile_time_eval():  # the mask's tables are constants of any trace
        return kernel.make_splash_mha_single_device(
            masks.MultiHeadMask([mask] * heads), block_sizes=sizes, interpret=interpret)


def blocked_attention(q, k, v, window: Optional[int] = None):
    """``softmax(q k^T + mask) v``: ``q`` [B, H, T, D], scaled already; ``k``,
    ``v`` [B, KV, T, D], each key-value head serving ``H / KV`` query heads in
    order; -> [B, H, T, D] in ``q``'s type."""
    heads, tokens = q.shape[1], q.shape[2]
    p = plan(tokens, window)
    interpret = jax.default_backend() != "tpu" if INTERPRET is None else INTERPRET
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, p.padded - tokens), (0, 0)))  # noqa: E731
    out = jax.vmap(_kernel(p, heads, window, interpret))(pad(q), pad(k), pad(v))
    return out[:, :, :tokens]

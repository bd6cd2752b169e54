"""Blocked masked attention: causal, or causal inside a window of the last
``window`` keys, grouped-query, never holding a ``[T, T]`` score tensor.

Three kernels of this module's own under one ``jax.custom_vjp``: the forward
pass (flash attention: a running maximum, sum and output in VMEM, float32),
and the two backward kernels, dq and dk/dv, which compute a block's scores
again from the kept log-sum.  A block's rows are a key-value head's whole
group of query heads over ``block_q`` tokens: a block of ``q`` [B, H, T, D]
is [G, block_q, D], a key-value head's heads being adjacent, and a program
holds it as [G x block_q, D], so that the keys it loads serve every head
that reads them and a short run of tokens still fills the array.  Scores and
softmax are float32, the products in the inputs' type.

The mask is drawn in the kernel from the block's offsets (key ``j`` for
query ``i`` if ``j <= i`` and, under a window, ``j > i - window``), only in
the blocks its edges cross; a block wholly outside it is not in the walk at
all: the grid's last axis is a schedule of the visited blocks, prefetched
(``_schedule``).  The length is not padded: the last block of queries or keys
reads past the end, what it reads there is masked or zeroed before it can
reach a sum, and its rows past the end are not written.  The log-sum and
``di = rowsum(o x do)`` cross HBM as [B, KV, G, T] float32; ``di`` is made in
the dq kernel, where ``o`` and ``do`` are in VMEM.  dk/dv sum over the group
in the kernel.

What the shapes alone say of the work: ``plan``, ``pairs_in_mask``,
``blocks_visited`` and ``pairs_computed``.

Off the TPU the kernels run in Pallas' interpreter (``INTERPRET`` None); a
test that compiles for a described TPU sets ``INTERPRET`` False.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET: Optional[bool] = None
_LANES = 128
_F32 = jnp.float32
_MASKED = -0.7 * float(np.finfo(np.float32).max)   # finite: a row of it has a maximum to subtract
_NT = (((1,), (1,)), ((), ()))                      # a @ b^T
_FIRST, _LAST, _EDGE, _END = 1, 2, 4, 8             # a step's flags in the schedule
_ROWS = 256                                         # a causal block's rows reach this: ``plan``


@dataclasses.dataclass(frozen=True)
class Plan:
    """The kernels' block sizes, all three alike: the tokens of a block's
    rows (times the group) and the keys of a block."""

    block_q: int
    block_kv: int


def plan(tokens: int, window: Optional[int], group: int) -> Plan:
    """From the shapes.  Causal alone: 512 keys by the fewest tokens, whole
    lanes and a power of two, whose rows over the key-value head's ``group``
    of query heads reach 256: 256 tokens where a key-value head has one query
    head, 128 under any larger group.  Under a window: square blocks of half
    the window in whole lanes, at most 256 (a query block then sees its own
    key block and up to two before it), whatever the group.

    Read on a TPU v5e (PERF.md section 6, PR 38) at B=8, T=1,568, 8
    key-value heads, each kernel apart, ms forward / forward keeping the
    log-sum / dq / dk-dv, by tokens x keys:

    =========  =======================  =======================  =======================
    block      G=9, D=128, window 512   G=6, D=128, causal       G=4, D=64, causal
    =========  =======================  =======================  =======================
    128 x 128  4.06 / 4.23 / 5.85 / 4.62  5.00 / 5.07 / 6.25 / 5.60  4.82 / 5.10 / 6.17 / 5.20
    128 x 256  3.31 / 3.58 / 4.98 / 4.33  3.61 / 3.73 / 4.91 / 4.54  3.37 / 3.56 / 4.89 / 4.10
    128 x 512  3.29 / 3.53 / 5.42 / 5.12  2.94 / 3.09 / 4.49 / 4.49  2.61 / 2.77 / 4.35 / 3.89
    256 x 128  4.04 / 4.22 / 5.95 / 5.28  4.42 / 4.59 / 5.89 / 5.49  3.92 / 4.07 / 5.30 / 4.78
    256 x 256  3.01 / 3.29 / 4.78 / 4.36  3.17 / 3.40 / 4.66 / 4.58  2.87 / 3.02 / 4.39 / 3.93
    256 x 512  3.31 / 3.58 / 5.54 / 5.33  2.99 / 3.20 / 4.62 / 4.76  2.58 / 2.70 / 4.23 / 3.97
    =========  =======================  =======================  =======================

    (the rows of 128 keys an earlier draft's, within 6% of the final
    kernels where both were read).  Read again with a shared key operand
    (PR 42: latent attention, 8 query heads each with its own keys, G=1,
    D=128 beside a shared part of 64, causal; the dk/dv time with the sum of
    the shared key's gradient over the heads):

    =========  =======================
    block      G=1, D=128+64, causal
    =========  =======================
    128 x 128  3.40 / 3.68 / 4.34 / 4.73
    128 x 256  2.16 / 2.30 / 2.52 / 3.31
    128 x 512  1.51 / 1.58 / 2.04 / 2.74
    256 x 128  2.38 / 2.51 / 3.52 / 2.57
    256 x 256  1.52 / 1.62 / 1.97 / 1.87
    256 x 512  1.09 / 1.16 / 1.80 / 1.67
    512 x 512  1.06 / 1.14 / 1.79 / 1.80
    =========  =======================

    And at the two cells whose every key-value head has one query head (PR
    57; bfloat16, T=1,568, causal, 20 executions on the host's clock:
    ``kanana2_q_ep8``'s latent layers, B=8, 32 heads, the shared part of 64
    and the sum of its gradient over the heads; ``olmoh_q_l4``'s full layer,
    B=4, 30 heads, no shared operand):

    ==========  =========================  =========================
    block       G=1, D=128+64, 8 x 32      G=1, D=128, 4 x 30
    ==========  =========================  =========================
    128 x 256   8.48 / 9.01 / 10.56 / 13.40  3.67 / 3.91 / 4.40 / 4.26
    128 x 512   5.73 / 6.01 / 8.37 / 10.94   2.43 / 2.56 / 3.01 / 3.06
    128 x 1024  5.05 / 5.27 / 8.06 / 11.15   2.02 / 2.10 / 2.69 / 2.74
    256 x 256   5.88 / 6.16 / 7.95 / 7.24    2.40 / 2.54 / 3.09 / 3.46
    256 x 512   4.09 / 4.29 / 7.11 / 6.47    1.58 / 1.66 / 2.36 / 2.67
    256 x 1024  4.16 / 4.32 / 7.32 / 6.86    1.46 / 1.55 / 2.29 / 2.53
    512 x 256   5.37 / 5.61 / 7.69 / 7.71    2.05 / 2.18 / 2.77 / 2.79
    512 x 512   3.97 / 4.15 / 6.96 / 6.96    1.40 / 1.50 / 2.20 / 2.34
    512 x 1024  4.41 / 4.57 / 7.42 / 7.53    1.54 / 1.62 / 2.25 / 2.44
    ==========  =========================  =========================

    At G=1 a block of 128 tokens is 128 rows.  A learner step's six calls a
    layer (two forwards, two keeping the log-sum, dq, dk/dv) read 42.79 ms
    at 128 x 512, 30.33 at 256 x 512 and 30.18 at 512 x 512 with the shared
    operand, 16.04, 11.51 and 10.35 without: 256 rows take 28-29% off either,
    512 rows nothing more of the first and a tenth more of the second, which
    is 0.2% of its cell's step, so the rule stops at 256.  The groups of the
    other cells (4, 6, 8, 9) fill 512 to 1,152 rows at 128 tokens, and their
    256-token rows above read no faster.  Blocks of 128 keys compute the fewest
    pairs outside the mask (1.34 and 1.21 times the pairs in it) and are the
    slowest: a step's reductions over the lanes and its fixed cost weigh
    more than the pairs saved; 1,024 keys gain only at 128 rows.  The head's
    size did not move the choice, so the rule does not read it (G=8, D=128,
    causal, two key-value heads: the reading of PR 39 is in PERF.md section
    6).  JAX's splash attention, a query head at a time in 512 x 512 and 896
    x 896 blocks over a padded length: 6.80 / 24.45 (forward / forward and
    backward), 5.43 / 18.20 and 3.76 / 12.22."""
    del tokens
    if window is None:
        block_q = _LANES
        while group * block_q < _ROWS:
            block_q *= 2
        return Plan(block_q, 512)
    side = min(max(-(-(window // 2) // _LANES) * _LANES, _LANES), 256)
    return Plan(side, side)


def pairs_in_mask(tokens: int, window: Optional[int]) -> int:
    """(query, key) pairs the mask lets through: key ``j`` for query ``i``
    if ``j <= i`` and, under a window, ``j > i - window``."""
    w = tokens if window is None else min(window, tokens)
    return w * (w + 1) // 2 + (tokens - w) * w


@functools.lru_cache(maxsize=None)
def _visits(tokens: int, window: Optional[int], bq: int, bkv: int) -> tuple:
    """((query block, key block, whether an edge crosses it, whether it
    reaches past the end), ...) for the blocks that hold a pair in the mask,
    by query blocks.  An edge is the diagonal, the window's trailing edge or
    the end of the sequence."""
    out = []
    for i in range(-(-tokens // bq)):
        q0, q1 = i * bq, i * bq + bq - 1
        for j in range(-(-tokens // bkv)):
            k0, k1 = j * bkv, j * bkv + bkv - 1
            # key - query runs from k0 - q1 to k1 - q0 over the block's own tokens
            reaches = k0 <= min(q1, tokens - 1)
            inside = window is None or min(k1, tokens - 1) > q0 - window
            end = max(q1, k1) >= tokens
            whole = k1 <= q0 and not end and (window is None or k0 > q1 - window)
            if reaches and inside:
                out.append((i, j, not whole, end))
    return tuple(out)


def blocks_visited(tokens: int, window: Optional[int], group: int) -> tuple:
    """(blocks of the forward kernel's grid that hold a pair in the mask,
    blocks of the whole grid), a query head of a key-value head's ``group``."""
    p = plan(tokens, window, group)
    return (len(_visits(tokens, window, p.block_q, p.block_kv)),
            -(-tokens // p.block_q) * -(-tokens // p.block_kv))


def pairs_computed(tokens: int, window: Optional[int], group: int) -> int:
    """(query, key) pairs the forward kernel computes a score for, a query
    head of a key-value head's ``group``: the visited blocks' whole extents."""
    p = plan(tokens, window, group)
    return blocks_visited(tokens, window, group)[0] * p.block_q * p.block_kv


@functools.lru_cache(maxsize=None)
def _schedule(tokens: int, window: Optional[int], bq: int, bkv: int, by_keys: bool):
    """The walk as three int32 arrays, a step each: its query block, its key
    block and its flags (first or last of its row of blocks, crossed by an
    edge, reaching past the end).  A row is a query block's (forward, dq) or
    a key block's (dk/dv)."""
    visits = _visits(tokens, window, bq, bkv)
    row = (lambda v: v[1]) if by_keys else (lambda v: v[0])
    visits = sorted(visits, key=lambda v: (row(v), v))
    flags = [(_FIRST if s == 0 or row(visits[s - 1]) != row(v) else 0)
             | (_LAST if s == len(visits) - 1 or row(visits[s + 1]) != row(v) else 0)
             | (_EDGE if v[2] else 0) | (_END if v[3] else 0) for s, v in enumerate(visits)]
    as_array = lambda xs: np.asarray(xs, np.int32)  # noqa: E731
    return as_array([v[0] for v in visits]), as_array([v[1] for v in visits]), as_array(flags)


def _keep(q0, k0, shape, q_axis: int, bq: int, window: Optional[int], tokens: Optional[int]):
    """The mask over a block of scores whose ``q_axis`` runs over the group's
    rows (``block_q`` tokens, again for every head of the group) and whose
    other axis over the block's keys; with ``tokens``, the block reaches past
    the end and a query there keeps nothing."""
    i = q0 + (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) & (bq - 1))
    j = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    keep = j <= i
    if window is not None:
        keep &= j > i - window
    if tokens is not None:
        keep &= i < tokens
    return keep


def _own_rows(x, first, tokens: int, period: Optional[int] = None):
    """``x`` [rows, D] with the rows of tokens past the end zeroed; the rows
    are tokens from ``first``, starting again every ``period`` rows."""
    r = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    r = r if period is None else r & (period - 1)
    return jnp.where(first + r < tokens, x, jnp.zeros_like(x))


def _lanes(x, n: int):
    """``x`` [rows, 128], every lane alike, as [rows, n]."""
    return x[:, :n] if n <= _LANES else jnp.tile(x, (1, n // _LANES))


def _to_lanes(col, ref):
    """Write ``col`` [G x bq, 128], every lane alike, to ``ref`` [G, bq]: a
    row's number goes from the sublanes to the lanes."""
    turned = col.T
    g, bq = ref.shape
    for h in range(g):
        ref[h:h + 1, :] = turned[:1, h * bq:(h + 1) * bq]


def _from_lanes(ref):
    """``ref`` [G, bq] as a column [G x bq, 1]."""
    return jnp.concatenate([ref[h][:, None] for h in range(ref.shape[0])], 0)


def _row(ref):
    """``ref`` [G, bq] as one row [1, G x bq]."""
    return jnp.concatenate([ref[h:h + 1, :] for h in range(ref.shape[0])], 1)


def _shared_scores(qs_ref, ks):
    """The second product of a block's scores: the group's shared query parts
    ``qs_ref`` [G, bq, S] against the one shared key block ``ks`` [bkv, S] (a
    ref, or its rows as loaded)."""
    g, bq, width = qs_ref.shape
    return jax.lax.dot_general(qs_ref[...].reshape(g * bq, width), ks[...], _NT,
                               preferred_element_type=_F32)


def _run(flag, step):
    """``step(edge, end)`` for what the flags say of the block: wholly inside
    the mask, crossed by an edge, or reaching past the end too."""
    pl.when(flag & _EDGE == 0)(lambda: step(False, False))
    pl.when(flag & (_EDGE | _END) == _EDGE)(lambda: step(True, False))
    pl.when(flag & _END != 0)(lambda: step(True, True))


def _fwd_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, *rest,
                tokens: int, window: Optional[int], shared: bool = False):
    if shared:
        qs_ref, ks_ref, *rest = rest
    o_ref, *rest = rest
    lse_ref = rest[0] if len(rest) == 4 else None
    m_ref, l_ref, acc_ref = rest[-3:]
    g, bq, d = q_ref.shape
    bkv = k_ref.shape[0]
    s = pl.program_id(2)
    flag = flag_ref[s]

    @pl.when(flag & _FIRST != 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(edge: bool, end: bool):
        q, k, v = q_ref[...].reshape(g * bq, d), k_ref[...], v_ref[...]
        q0, k0 = qi_ref[s] * bq, kj_ref[s] * bkv
        scores = jax.lax.dot_general(q, k, _NT, preferred_element_type=_F32)
        if shared:
            scores += _shared_scores(qs_ref, ks_ref)
        if edge:
            scores = jnp.where(_keep(q0, k0, scores.shape, 0, bq, window, None), scores, _MASKED)
        if end:     # keys past the end are masked; what stands in their rows of v is not a number
            v = _own_rows(v, k0, tokens)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - _lanes(m_next, bkv))
        alpha = jnp.exp(m_prev - m_next)
        # the sum stays a lane's own until the row's last block: no reduction over lanes a step
        l_ref[...] = alpha * l_ref[...] + sum(
            p[:, c:c + _LANES] for c in range(0, bkv, _LANES))
        m_ref[...] = m_next
        acc_ref[...] = _lanes(alpha, d) * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=_F32)

    _run(flag, step)

    @pl.when(flag & _LAST != 0)
    def _():
        l = jnp.broadcast_to(l_ref[...].sum(axis=-1, keepdims=True), l_ref.shape)
        o_ref[...] = (acc_ref[...] / _lanes(l, d)).reshape(g, bq, d).astype(o_ref.dtype)
        if lse_ref is not None:
            _to_lanes(m_ref[...] + jnp.log(l), lse_ref)


def _dq_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, *rest,
               tokens: int, window: Optional[int], shared: bool = False):
    if shared:
        qs_ref, ks_ref, o_ref, do_ref, lse_ref, dq_ref, dqs_ref, di_ref, *rest = rest
        lse_col, di_col, acc_ref, acc_s = rest
    else:
        o_ref, do_ref, lse_ref, dq_ref, di_ref, lse_col, di_col, acc_ref = rest
    g, bq, d = q_ref.shape
    bkv = k_ref.shape[0]
    s = pl.program_id(2)
    flag = flag_ref[s]

    @pl.when(flag & _FIRST != 0)
    def _():
        di = jnp.sum(o_ref[...].reshape(g * bq, d).astype(_F32)
                     * do_ref[...].reshape(g * bq, d).astype(_F32), axis=-1, keepdims=True)
        di_col[...] = jnp.broadcast_to(di, di_col.shape)
        _to_lanes(di_col[...], di_ref)
        lse_col[...] = jnp.broadcast_to(_from_lanes(lse_ref), lse_col.shape)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if shared:
            acc_s[...] = jnp.zeros_like(acc_s)

    def step(edge: bool, end: bool):
        q, do = q_ref[...].reshape(g * bq, d), do_ref[...].reshape(g * bq, d)
        k, v = k_ref[...], v_ref[...]
        q0, k0 = qi_ref[s] * bq, kj_ref[s] * bkv
        if end:
            k, v = _own_rows(k, k0, tokens), _own_rows(v, k0, tokens)
        scores = jax.lax.dot_general(q, k, _NT, preferred_element_type=_F32)
        if shared:
            ks = _own_rows(ks_ref[...], k0, tokens) if end else ks_ref[...]
            scores += _shared_scores(qs_ref, ks)
        if edge:
            scores = jnp.where(_keep(q0, k0, scores.shape, 0, bq, window, None), scores, _MASKED)
        p = jnp.exp(scores - _lanes(lse_col[...], bkv))
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=_F32)
        ds = p * (dp - _lanes(di_col[...], bkv))
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=_F32)
        if shared:
            acc_s[...] += jnp.dot(ds.astype(ks.dtype), ks, preferred_element_type=_F32)

    _run(flag, step)

    @pl.when(flag & _LAST != 0)
    def _():
        dq_ref[...] = acc_ref[...].reshape(g, bq, d).astype(dq_ref.dtype)
        if shared:
            dqs_ref[...] = acc_s[...].reshape(dqs_ref.shape).astype(dqs_ref.dtype)


def _dkv_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, *rest,
                tokens: int, window: Optional[int], shared: bool = False):
    if shared:
        qs_ref, ks_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref, dks_ref, dk_acc, dv_acc, dks_acc = rest
    else:
        do_ref, lse_ref, di_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    g, bq, d = q_ref.shape
    bkv = k_ref.shape[0]
    s = pl.program_id(2)
    flag = flag_ref[s]

    @pl.when(flag & _FIRST != 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if shared:
            dks_acc[...] = jnp.zeros_like(dks_acc)

    def step(edge: bool, end: bool):
        # the scores turned, [keys, the group's rows]: the sums over the rows are products
        q, do = q_ref[...].reshape(g * bq, d), do_ref[...].reshape(g * bq, d)
        k, v, lse, di = k_ref[...], v_ref[...], _row(lse_ref), _row(di_ref)
        q0, k0 = qi_ref[s] * bq, kj_ref[s] * bkv
        if end:     # queries past the end keep nothing; their rows are not numbers
            q, do = _own_rows(q, q0, tokens, bq), _own_rows(do, q0, tokens, bq)
            i = q0 + (jax.lax.broadcasted_iota(jnp.int32, di.shape, 1) & (bq - 1))
            di = jnp.where(i < tokens, di, 0.0)
        scores = jax.lax.dot_general(k, q, _NT, preferred_element_type=_F32)
        if shared:
            qs = qs_ref[...].reshape(g * bq, qs_ref.shape[-1])
            qs = _own_rows(qs, q0, tokens, bq) if end else qs
            scores += jax.lax.dot_general(ks_ref[...], qs, _NT, preferred_element_type=_F32)
        p = jnp.exp(scores - lse)
        if edge:
            p = jnp.where(_keep(q0, k0, scores.shape, 1, bq, window, tokens if end else None),
                          p, 0.0)
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=_F32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=_F32)
        ds = p * (dp - di)
        dk_acc[...] += jnp.dot(ds.astype(q.dtype), q, preferred_element_type=_F32)
        if shared:
            dks_acc[...] += jnp.dot(ds.astype(qs.dtype), qs, preferred_element_type=_F32)

    _run(flag, step)

    @pl.when(flag & _LAST != 0)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if shared:
            dks_ref[...] = dks_acc[...].astype(dks_ref.dtype)


def _call(kernel, name, by_keys: bool, inputs, specs: str, out_shape, out_specs: str, scratch,
          score_arrays: int, window, interpret: bool):
    """One kernel over (B, KV, the steps of its walk).  ``inputs``: ``q`` [B,
    H, T, D], ``k`` [B, KV, T, D] and the rest; ``specs`` names each array's
    blocks: ``r`` a group's rows of [B, H, T, D], ``k`` a block of keys of
    [B, KV, T, D], ``l`` a group's tokens, in the lanes, of [B, KV, G, T];
    with a shared key, ``R`` a group's rows of [B, H, T, S], ``S`` a block of
    the one shared key [B, 1, T, S], whatever the head, and ``K`` a block of
    keys of [B, KV, T, S].
    ``scratch``: (rows, columns) of its float32 scratch, where ``r`` stands
    for the rows of a block of queries and ``k`` for a block's keys.  It asks
    for the VMEM its blocks (twice: one in flight), its scratch and
    ``score_arrays`` float32 arrays of a block of scores need."""
    q, k = inputs[:2]
    tokens, g, d = q.shape[2], q.shape[1] // k.shape[1], q.shape[3]
    p = plan(tokens, window, g)
    bq, bkv = p.block_q, p.block_kv
    spec = {"r": pl.BlockSpec((None, g, bq, d), lambda b, h, s, qi, kj, fl: (b, h, qi[s], 0)),
            "k": pl.BlockSpec((None, None, bkv, d), lambda b, h, s, qi, kj, fl: (b, h, kj[s], 0)),
            "l": pl.BlockSpec((None, None, g, bq), lambda b, h, s, qi, kj, fl: (b, h, 0, qi[s]))}
    shared = "S" in specs
    if shared:
        w = inputs[specs.index("S")].shape[3]
        spec.update(
            R=pl.BlockSpec((None, g, bq, w), lambda b, h, s, qi, kj, fl: (b, h, qi[s], 0)),
            S=pl.BlockSpec((None, None, bkv, w), lambda b, h, s, qi, kj, fl: (b, 0, kj[s], 0)),
            K=pl.BlockSpec((None, None, bkv, w), lambda b, h, s, qi, kj, fl: (b, h, kj[s], 0)))
        kernel = functools.partial(kernel, shared=True)
    size = {"r": g * bq, "k": bkv}
    scratch = [pltpu.VMEM((size[r], c), _F32) for r, c in scratch]
    schedule = _schedule(tokens, window, bq, bkv, by_keys)
    need = (sum(2 * math.prod(n for n in spec[c].block_shape if n) * x.dtype.itemsize
                for c, x in zip(specs + out_specs, [*inputs, *out_shape]))
            + sum(4 * math.prod(x.shape) for x in scratch) + score_arrays * 4 * g * bq * bkv)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(q.shape[0], k.shape[1], len(schedule[0])),
        in_specs=[spec[c] for c in specs], out_specs=[spec[c] for c in out_specs],
        scratch_shapes=scratch)
    return pl.pallas_call(
        functools.partial(kernel, tokens=tokens, window=window), grid_spec=grid,
        out_shape=out_shape, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=need + (4 << 20)))(*schedule, *inputs)


def _like(*arrays):
    return [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in arrays]


def _forward(q, k, v, window, interpret, keep_lse: bool, shared=()):
    """The output; with ``keep_lse`` also the log-sum, [B, KV, G, T] float32.
    ``shared``: () or (the queries' shared part [B, H, T, S], the one shared
    key [B, 1, T, S])."""
    lse = jax.ShapeDtypeStruct((*k.shape[:2], q.shape[1] // k.shape[1], q.shape[2]), _F32)
    out = _call(_fwd_kernel, "attn_fwd_lse" if keep_lse else "attn_fwd", False,
                [q, k, v, *shared], "rkk" + "RS" * bool(shared),
                _like(q) + [lse] * keep_lse, "rl"[:1 + keep_lse],
                [("r", _LANES), ("r", _LANES), ("r", q.shape[3])], 4, window, interpret)
    return out if keep_lse else out[0]


def _dq(q, k, v, o, lse, do, window, interpret, shared=()):
    """(dq, with a shared key the shared part's gradient too, and ``di`` [B,
    KV, G, T] float32 for ``_dkv``)."""
    also = bool(shared)
    return _call(_dq_kernel, "attn_dq", False, [q, k, v, *shared, o, do, lse],
                 "rkk" + "RS" * also + "rrl", _like(q, *shared[:1], lse), "r" + "R" * also + "l",
                 [("r", _LANES), ("r", _LANES), ("r", q.shape[3])]
                 + [("r", x.shape[3]) for x in shared[:1]], 5, window, interpret)


def _dkv(q, k, v, lse, di, do, window, interpret, shared=()):
    """(dk, dv; with a shared key also its gradient a key-value head, [B, KV,
    T, S]: the grid's head axis is parallel, so a head's program cannot add
    into another's block, and the sum over the heads is the caller's)."""
    by_head = [jax.ShapeDtypeStruct((*k.shape[:3], x.shape[3]), x.dtype) for x in shared[1:]]
    return _call(_dkv_kernel, "attn_dkv", True, [q, k, v, *shared, do, lse, di],
                 "rkk" + "RS" * bool(shared) + "rll", _like(k, v) + by_head, "kk" + "K" * bool(shared),
                 [("k", q.shape[3])] * 2 + [("k", x.shape[3]) for x in shared[1:]],
                 5, window, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, window, interpret):
    return _forward(q, k, v, window, interpret, keep_lse=False)


def _attend_fwd(q, k, v, window, interpret):
    o, lse = _forward(q, k, v, window, interpret, keep_lse=True)
    return o, (q, k, v, o, lse)


def _attend_bwd(window, interpret, kept, do):
    q, k, v, o, lse = kept
    dq, di = _dq(q, k, v, o, lse, do, window, interpret)
    return (dq, *_dkv(q, k, v, lse, di, do, window, interpret))


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _attend_shared(q, k, v, q_shared, k_shared, window, interpret):
    return _forward(q, k, v, window, interpret, False, (q_shared, k_shared))


def _attend_shared_fwd(q, k, v, q_shared, k_shared, window, interpret):
    o, lse = _forward(q, k, v, window, interpret, True, (q_shared, k_shared))
    return o, (q, k, v, q_shared, k_shared, o, lse)


def _attend_shared_bwd(window, interpret, kept, do):
    q, k, v, q_shared, k_shared, o, lse = kept
    shared = (q_shared, k_shared)
    dq, dqs, di = _dq(q, k, v, o, lse, do, window, interpret, shared)
    dk, dv, dks = _dkv(q, k, v, lse, di, do, window, interpret, shared)
    dks = jnp.sum(dks.astype(_F32), axis=1, keepdims=True).astype(k_shared.dtype)
    return dq, dk, dv, dqs, dks


_attend_shared.defvjp(_attend_shared_fwd, _attend_shared_bwd)


def blocked_attention(q, k, v, window: Optional[int] = None, q_shared=None, k_shared=None):
    """``softmax(q k^T + mask) v``: ``q`` [B, H, T, D], scaled already; ``k``,
    ``v`` [B, KV, T, D], each key-value head serving ``H / KV`` query heads in
    order; -> [B, H, T, D] in ``q``'s type.  With ``q_shared`` [B, H, T, S]
    (scaled too) and ``k_shared`` [B, 1, T, S], one key for every head, the
    scores are ``q k^T + q_shared k_shared^T`` (latent attention's rotary
    part): each program loads its block of the one shared key, which is never
    laid out a head at a time."""
    interpret = jax.default_backend() != "tpu" if INTERPRET is None else INTERPRET
    if q_shared is None:
        return _attend(q, k, v, window, interpret)
    return _attend_shared(q, k, v, q_shared, k_shared, window, interpret)

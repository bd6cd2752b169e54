"""A gated delta-rule recurrence in chunks, with a backward pass that walks the
chunks in reverse (Kimi Delta Attention, arXiv:2510.26692, in its chunked
WY / UT form).

Per head, with a state ``S`` in R^{K x V}, a log decay ``g_t <= 0`` per key
channel and a write strength ``beta_t`` (up to 2: a negative eigenvalue):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T    S_{-1} = 0
    o_t = S_t^T q_t

The state is multiplied by a matrix that depends on the token's key, so the
tokens of a chunk act on each other through a triangular system.  The
sequence is cut in chunks of ``chunk`` tokens (padded at the end with ``beta
= 0``, ``g = 0`` and zero ``q``, ``k``, ``v``: no decay, no write; the padded
rows are cut off and get no gradient) and walked a chunk at a time, carrying
the state across.  Inside a chunk, with ``G_i`` the running sum of ``g`` from
the chunk's first token to ``i`` (a vector of K):

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)       j < i, strictly lower
    T    = (I + A)^-1                                    unit lower triangular
    W    = T (beta k exp(G)),   U = T (beta v)
    V'   = U - W S_prev
    o_i  = (q_i exp(G_i)) S_prev + sum_{j <= i} (sum_c q_ic k_jc exp(G_ic - G_jc)) V'_j
    S    = Diag(exp(G_end)) S_prev + sum_j (k_j exp(G_end - G_j)) V'_j^T

**Every decay that is formed is ``exp`` of a difference ``G_i - G_j`` with
``j <= i``, or of ``G_i`` alone**: ``exp(-G_j)`` overflows float32 once a
chunk's decay passes e^-88.  The scores over pairs of tokens are therefore
made in sub-blocks of ``SUB`` rows, as the published kernels make them: a
pair in two different sub-blocks is referred to the later one's first row,
``exp(G_i - G_first) exp(G_first - G_j)``, both factors at most 1, so that the
sum over the channels is a matrix product; a pair inside one sub-block has
its difference formed before the exponential, under the mask.  ``T`` is made
by blocks too: diagonal blocks of ``INVERSE_BASE`` rows inverted by the
doubling product ``(I - A)(I + A^2)(I + A^4)`` (exact for a nilpotent block,
and short enough at 8 rows that its alternating powers do not cancel: keys
that share a direction give entries near ``beta``, and over 64 rows their
powers reach 1e9), then blocks merged two and two, ``[[T1, 0], [-T2 A21 T1,
T2]]``, while their count is even, and by block substitution after that.

**The inverse is made where it lies** (``_unit_lower_inverse_of``, one for
both forms): the blocks, the doubling product and the merges on whole ``[C,
C]`` matrices under block masks, ten ``[64, 64]`` products a chunk of 64 and
nothing gathered, padded or joined (blocks gathered into ``[.., 8, 8, 8]`` to
``[.., 32, 32]`` were three fifths of a chunk's time on a TPU v5e, lanes an
eighth full, and autodiff through them some forty small arrays a chunk).  It
is pulled back by ``dA = -T^T dT T^T``: two products, ``T`` alone kept.

Running sums, decays, ``T`` and the state are float32; the products take
operands in ``q``'s type and accumulate in float32.  ``q``, ``k``, ``v`` and
``g`` cross the walk as ``[chunks, B, H, chunk, 128]``: a head's 128 fills a
TPU's lanes, a chunk's tokens the sublanes.

**The scalar-gate form** (Gated DeltaNet, arXiv:2412.06464): where the log
decay is one scalar a head and token (``g`` [B, H, T], not [B, H, T, K]) it
leaves the sums over the key channels,

    A_ij = beta_i (k_i . k_j) exp(G_i - G_j),   scores_ij = (q_i . k_j) exp(G_i - G_j)

so a chunk's pair scores are one product of ``[2 C, K]`` by ``[K, C]`` and a
``[C, C]`` mask of decays, each the exponential of a difference formed before
it and under the mask: no sub-blocks, and ``g`` crosses the walk as 4 B a
token and head.  ``W``, ``U``, ``V'``, ``o`` and the state are the lines above
with ``exp(G)`` a scalar a row (``_chunk_scalar``: ``_state_free``, which
makes everything up to ``W``, ``U``, the scores and the decayed ``q`` and
``k``, then ``_carry``, the four products that read the state); the cut, the
padding and the walk are shared.  The same entry takes both forms and tells
them apart by ``g``'s rank; keys and values need not have one width (the
state is [K, V]), and a head's K and V lie in the lanes as they are, padded
by the compiler's tiles to whole lanes (96 of 128, 192 of 256).

The backward pass is written by hand (``jax.custom_vjp``) in
``chunked_scan.py``'s manner: it keeps the inputs and each chunk's incoming
state (``[chunks, B, H, K, V]`` float32) and walks the chunks from the last
to the first, computing each again and pulling the cotangents of its outputs
and of its outgoing state back through it; nothing of ``[chunk, chunk]`` a
head outlives its chunk in either pass.  The two walks stand beside each
other and are not one: ``chunked_scan._chunk`` takes its heads' parameters
between the chunk's own operands, and the state-space cell's compiled
program is held instruction for instruction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.ops.chunked_scan import cut, join
from ape_x_dqn_tpu.utils.profiling import launch, part, pass_

SUB = 16                         # rows of a sub-block of the pair scores
INVERSE_BASE = 8                 # rows of a diagonal block inverted by the doubling product
_HIGHEST = jax.lax.Precision.HIGHEST


def _divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` up to ``most``."""
    return max(r for r in range(1, min(most, n) + 1) if n % r == 0)


def sub_rows(chunk: int) -> int:
    """Rows of a sub-block of the pair scores in a chunk of ``chunk``."""
    return _divisor(chunk, SUB)


def _inverse_in_place(a):
    """``(I + a)^-1`` for ``a`` [..., C, C] float32, strictly lower
    triangular: its diagonal blocks (the largest divisor of C up to
    ``INVERSE_BASE`` rows) by the doubling product, then blocks merged two
    and two while their count is even, and by block substitution after that
    (module docstring), on whole ``[C, C]`` matrices under block masks: a
    diagonal block stays where it lies, so nothing is gathered, padded or
    joined and every product is ``[C, C]`` by ``[C, C]`` with the chunk's
    tokens in the lanes (a product with the zeros round a block adds nothing
    to its sums)."""
    c = a.shape[-1]
    size = _divisor(c, INVERSE_BASE)
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)  # noqa: E731
    block_of = lambda s: jnp.arange(c) // s  # noqa: E731
    within = lambda s: block_of(s)[:, None] == block_of(s)[None, :]  # noqa: E731
    eye = jnp.eye(c, dtype=a.dtype)
    x = -jnp.where(within(size), a, 0.0)       # the diagonal blocks
    inv, power, reach = eye + x, x, 2          # sum of x^k, k < reach
    while reach < size:
        power = mm(power, power)
        inv, reach = mm(inv, eye + power), 2 * reach
    while (c // size) % 2 == 0:                # two and two: [[T1, 0], [-T2 A21 T1, T2]]
        below = jnp.where(within(2 * size) & ~within(size), a, 0.0)
        inv, size = inv - mm(inv, mm(below, inv)), 2 * size
    rows = block_of(size)
    for i in range(1, c // size):              # block substitution, a block row at a time
        own = (rows == i)[:, None]
        left = jnp.where(own & (rows < i)[None, :], a, 0.0)
        inv = inv - mm(jnp.where(own, inv, 0.0), mm(left, inv))
    return inv


@jax.custom_vjp
def _unit_lower_inverse_of(a):
    """``T = (I + a)^-1`` by ``_inverse_in_place``, pulled back by ``dA = -T^T
    dT T^T``: ``T`` alone is kept for the backward pass."""
    return _inverse_in_place(a)


def _inverse_fwd(a):
    t = _inverse_in_place(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(tt, jnp.matmul(dt, tt, precision=_HIGHEST), precision=_HIGHEST),)


_unit_lower_inverse_of.defvjp(_inverse_fwd, _inverse_bwd)


def _chunk(state, q, k, v, g, beta):
    """One chunk: (``state`` [B, H, K, V] float32, the chunk's ``q``, ``k``
    [B, H, C, K], ``v`` [B, H, C, V], ``g`` [B, H, C, K] float32, ``beta`` [B,
    H, C] float32) -> (the state after it, ``o`` [B, H, C, V] in ``q``'s type)."""
    cd, f32 = q.dtype, jnp.float32
    b, h, c, kw = q.shape
    rows = sub_rows(c)
    n = c // rows
    dot = lambda spec, x, y: jnp.einsum(  # noqa: E731
        spec, x.astype(cd), y.astype(cd), preferred_element_type=f32)
    run = jnp.cumsum(g, axis=-2)                                   # G: [B, H, C, K]
    qf, kf = q.astype(f32), k.astype(f32)
    by_block = lambda x: x.reshape(b, h, n, rows, *x.shape[3:])    # noqa: E731
    run_b, q_b, k_b = by_block(run), by_block(qf), by_block(kf)
    first = run_b[:, :, :, 0]                                      # a sub-block's first row

    # pairs in two sub-blocks: down to the later one's first row, up from it
    down = jnp.exp(run_b - first[:, :, :, None])                   # exp(G_i - G_first) <= 1
    up = jnp.exp(jnp.minimum(first[:, :, :, None] - run[:, :, None], 0.0))   # [B, H, n, C, K]
    across = dot("bhnrk,bhnjk->bhnrj", jnp.concatenate([k_b * down, q_b * down], axis=3),
                 kf[:, :, None] * up)                              # [B, H, n, 2R, C]
    # pairs in one sub-block: the difference before the exponential
    same = jnp.tril(jnp.ones((rows, rows), bool))                  # s <= r
    decay = jnp.exp(jnp.where(same[..., None],
                              run_b[:, :, :, :, None] - run_b[:, :, :, None, :], -jnp.inf))
    kk = jnp.sum(k_b[:, :, :, :, None] * k_b[:, :, :, None, :] * decay, axis=-1)   # [B, H, n, R, R]
    qk = jnp.sum(q_b[:, :, :, :, None] * k_b[:, :, :, None, :] * decay, axis=-1)
    strict = jnp.tril(jnp.ones((rows, rows), f32), -1)
    own = jnp.concatenate([jnp.concatenate([kk * strict, qk], axis=3)] * n, axis=-1)   # [B, H, n, 2R, C]
    # a sub-block's rows: the earlier blocks' columns, its own, nothing later
    later = (jnp.arange(c)[None, :] // rows - jnp.arange(n)[:, None])[:, None, :]      # j's block less i's
    pairs = jnp.where(later < 0, across, jnp.where(later == 0, own, 0.0))
    a = beta[..., None] * pairs[:, :, :, :rows].reshape(b, h, c, c)
    scores = pairs[:, :, :, rows:].reshape(b, h, c, c)

    t = _unit_lower_inverse_of(a)
    decayed = jnp.exp(run)                                         # exp(G_i) <= 1
    wu = dot("bhij,bhjx->bhix", t,
             jnp.concatenate([kf * decayed, v.astype(f32)], axis=-1) * beta[..., None])
    w, u = wu[..., :kw], wu[..., kw:]
    moved = u - dot("bhik,bhkv->bhiv", w, state)                   # V'
    o = dot("bhik,bhkv->bhiv", qf * decayed, state) + dot("bhij,bhjv->bhiv", scores, moved)
    to_end = jnp.exp(run[:, :, -1:] - run)                         # exp(G_end - G_j) <= 1
    state = decayed[:, :, -1, :, None] * state + dot("bhjk,bhjv->bhkv", kf * to_end, moved)
    return state, o.astype(cd)


def _dot_in(cd):
    """``einsum`` of operands cast to ``cd``, summed in float32."""
    return lambda spec, x, y: jnp.einsum(
        spec, x.astype(cd), y.astype(cd), preferred_element_type=jnp.float32)


def _state_free(q, k, v, g, beta):
    """The half of a scalar-gate chunk that does not read the carried state,
    over any leading dimensions (``[B, H]`` in the walk): ``q``, ``k`` [...,
    C, K], ``v`` [..., C, V], ``g``, ``beta`` [..., C] float32 -> what
    ``_carry`` takes after the state: ``W`` [..., C, K], ``U`` [..., C, V]
    float32, the scores [..., C, C], ``q exp(G)`` and ``k exp(G_end - G)``
    [..., C, K], ``exp(G_end)`` [..., 1, 1] float32; the four that are
    operands of products alone in ``q``'s type, as the products take them.
    It stays in the walk's loop: made for a group of chunks at once its
    arrays leave the chip's fast memory and the walk is slower at every
    group's size (PERF.md section 6, PR 52)."""
    cd, f32 = q.dtype, jnp.float32
    c, kw = q.shape[-2], q.shape[-1]
    dot = _dot_in(cd)
    with jax.named_scope("scalar_gate"):
        run = jnp.cumsum(g, axis=-1)                                   # G: [..., C]
        earlier = jnp.tril(jnp.ones((c, c), bool))                     # j <= i
        # masked before the exponential: a later token's G_i - G_j is positive
        decay = jnp.exp(jnp.where(earlier, run[..., :, None] - run[..., None, :], -jnp.inf))
        # k.k over q.k, one product: [..., 2C, C]
        pairs = dot("...rk,...jk->...rj", jnp.concatenate([k, q], axis=-2), k)
        a = beta[..., None] * jnp.tril(pairs[..., :c, :] * decay, -1)
        scores = pairs[..., c:, :] * decay

        t = _unit_lower_inverse_of(a)
        decayed = jnp.exp(run)[..., None]                              # exp(G_i) <= 1
        kf = k.astype(f32)
        wu = dot("...ij,...jx->...ix", t,
                 jnp.concatenate([kf * decayed, v.astype(f32)], axis=-1) * beta[..., None])
        to_end = jnp.exp(run[..., -1:] - run)[..., None]               # exp(G_end - G_j) <= 1
        return (wu[..., :kw].astype(cd), wu[..., kw:], scores.astype(cd),
                (q.astype(f32) * decayed).astype(cd), (kf * to_end).astype(cd),
                decayed[..., -1:, :])


def _carry(state, w, u, scores, reads, writes, kept):
    """What of a scalar-gate chunk reads the state: (``state`` [B, H, K, V]
    float32, a chunk of ``_state_free``'s outputs) -> (the state after the
    chunk, ``o`` [B, H, C, V] in ``q``'s type)."""
    cd, dot = w.dtype, _dot_in(w.dtype)
    with jax.named_scope("scalar_gate"):
        moved = u - dot("bhik,bhkv->bhiv", w, state)                   # V'
        o = dot("bhik,bhkv->bhiv", reads, state) + dot("bhij,bhjv->bhiv", scores, moved)
        state = kept * state + dot("bhjk,bhjv->bhkv", writes, moved)
        return state, o.astype(cd)


def _chunk_scalar(state, q, k, v, g, beta):
    """``_chunk`` under one log decay a head and token: ``g`` [B, H, C]
    float32; ``state`` [B, H, K, V], ``q``, ``k`` [B, H, C, K], ``v`` [B, H,
    C, V], K and V any two widths (module docstring)."""
    return _carry(state, *_state_free(q, k, v, g, beta))


def _chunk_of(q, g):
    """The chunk's function for ``g``: a decay a key channel, or one a head."""
    return _chunk if g.ndim == q.ndim else _chunk_scalar


def _walk(q, k, v, g, beta, keep: bool):
    """(o [chunks, B, H, C, V]; with ``keep`` each chunk's incoming state,
    [chunks, B, H, K, V], else None)."""
    with part("delta_scan"):
        first = jnp.zeros((*q.shape[1:3], q.shape[-1], v.shape[-1]), jnp.float32)

        step = _chunk_of(q, g)

        def body(state, chunk):
            after, o = step(state, *chunk)
            return after, (o, state if keep else None)

        return jax.lax.scan(body, first, (q, k, v, g, beta))[1]


@jax.custom_vjp
def delta_chunks(q, k, v, g, beta):
    """``o`` [chunks, B, H, C, V] in ``q``'s type of the recurrence above, over
    a sequence already cut in chunks, zeros past its end.

    ``q``, ``k`` [chunks, B, H, C, K]; ``v`` [chunks, B, H, C, V]; ``g``
    [chunks, B, H, C, K] float32, at most 0, or [chunks, B, H, C] where a
    head has one decay (the scalar-gate form); ``beta`` [chunks, B, H, C]
    float32."""
    return _walk(q, k, v, g, beta, keep=False)[0]


def _delta_fwd(q, k, v, g, beta):
    o, states = _walk(q, k, v, g, beta, keep=True)
    return o, (q, k, v, g, beta, states)


def _delta_bwd(kept, do):
    """The cotangents of ``delta_chunks``' inputs from ``do``, all cut."""
    *inputs, states = kept
    step = _chunk_of(inputs[0], inputs[3])
    with part("delta_scan"):
        def body(d_after, chunk):
            state, *own, doc = chunk
            with pass_("again"):                               # the chunk, computed again
                _, pull = jax.vjp(step, state, *own)
            d_state, *d_own = pull((d_after, doc))
            return d_state, tuple(d_own)

        _, d_inputs = jax.lax.scan(body, jnp.zeros_like(states[0]), (states, *inputs, do),
                                   reverse=True)
        return d_inputs


delta_chunks.defvjp(_delta_fwd, _delta_bwd)


def chunked_delta(q, k, v, g, beta, chunk: int):
    """``delta_chunks`` for a caller that holds a head's tokens uncut: ``q``,
    ``k`` [B, H, T, K], ``v`` [B, H, T, V], ``g`` [B, H, T, K] float32 (or
    [B, H, T]: the scalar-gate form), ``beta`` [B, H, T] float32 -> ``o``
    [B, H, T, V].  The cut pads with
    zeros, which is ``beta = 0`` and ``g = 0``."""
    # once a trace and layer kind, in the launch log: which form was traced
    wide = g.ndim == q.ndim
    with part("delta_scan"), launch.span(
            "scan_path", path="per_channel" if wide else "scalar", heads=q.shape[1],
            key=q.shape[-1], value=v.shape[-1], inverse="in_place"):
        by_chunk = lambda x: cut(jnp.moveaxis(x, 2, 1), chunk)  # noqa: E731  [chunks, B, C, H, ..]
        heads_first = lambda x: jnp.moveaxis(x, 3, 2)           # noqa: E731  [chunks, B, H, C, ..]
        o = delta_chunks(*(heads_first(by_chunk(x)) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(join(jnp.moveaxis(o, 2, 3), q.shape[2]), 1, 2)


"""A selective state-space scan in chunks, with a backward pass that walks the
chunks in reverse (Mamba-2's recurrence, Dao & Gu 2024, arXiv:2405.21060, in
its chunked dual form).

Per head, with a state ``S`` in R^{P x N}, a decay ``A < 0`` and a skip ``D``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_{-1} = 0
    y_t = S_t C_t + D x_t

``B_t`` and ``C_t`` are shared by all heads (one group).  The sequence is cut
in chunks of ``chunk`` tokens (padded at the end with ``dt = 0`` and ``x =
0``: decay 1, no input; the padded rows are cut off and get no gradient) and
walked a chunk at a time, carrying the state across.  Inside a chunk, with
``L_i`` the running sum of ``dt A`` from the chunk's first token to ``i``:

    y_i = sum_{j <= i} exp(L_i - L_j) (C_i . B_j) dt_j x_j      the chunk's own tokens
        + exp(L_i) S_prev C_i                                     what came before it
        + D x_i
    S   = exp(L_end) S_prev + sum_j exp(L_end - L_j) dt_j x_j B_j^T

so the work is matrix products: the scores ``C B^T`` once for all heads, a
product of ``[chunk, chunk]`` by ``[chunk, P]`` a head, and two products with
the state.  Running sums, decays and the state are float32; the products
take operands in ``x``'s type and accumulate in float32.

The backward pass is written by hand (``jax.custom_vjp``): it keeps the
inputs and each chunk's incoming state (``[chunks, B, H, P, N]`` float32)
and walks the chunks from the last to the first, computing each again and
pulling the cotangents of its outputs and of its outgoing state back through
it; nothing of ``[chunk, chunk]`` a head outlives its chunk in either pass,
where reverse-mode differentiation of the walk would stack every chunk's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.utils.profiling import part


def chunks_of(tokens: int, chunk: int) -> tuple:
    """(chunks walked, tokens after padding) for a sequence of ``tokens``; a
    sequence shorter than ``chunk`` is one chunk of its own length."""
    q = min(chunk, tokens)
    n = -(-tokens // q)
    return n, n * q


def _cut(v, chunk: int):
    """[B, T, ...] -> [chunks, B, chunk, ...], zeros past T."""
    n, padded = chunks_of(v.shape[1], chunk)
    v = jnp.pad(v, ((0, 0), (0, padded - v.shape[1])) + ((0, 0),) * (v.ndim - 2))
    return jnp.moveaxis(v.reshape(v.shape[0], n, padded // n, *v.shape[2:]), 1, 0)


def _join(v, tokens: int):
    """[chunks, B, chunk, ...] -> [B, T, ...]."""
    v = jnp.moveaxis(v, 0, 1)
    return v.reshape(v.shape[0], -1, *v.shape[3:])[:, :tokens]


def _chunk(state, x, dt, a, b, c, d):
    """One chunk: (``state`` [B, H, P, N] float32, the chunk's ``x`` [B, Q, H,
    P], ``dt`` [B, Q, H] float32, ``b``, ``c`` [B, Q, N], the heads' ``a``,
    ``d`` [H]) -> (the state after it, ``y`` [B, Q, H, P] in ``x``'s type)."""
    cd, f32 = x.dtype, jnp.float32
    run = jnp.cumsum(dt * a, axis=1)                         # L: [B, Q, H]
    by_head = jnp.moveaxis(run, 2, 1)                        # [B, H, Q]
    q = x.shape[1]
    earlier = jnp.tril(jnp.ones((q, q), bool))               # j <= i
    # masked before the exponential: a later token's L_i - L_j is positive
    decay = jnp.exp(jnp.where(earlier, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    scores = jnp.einsum("bin,bjn->bij", c, b, preferred_element_type=f32)
    xdt = x.astype(f32) * dt[..., None]                      # dt_j x_j
    y = jnp.einsum("bhij,bjhp->bihp", (scores[:, None] * decay).astype(cd), xdt.astype(cd),
                   preferred_element_type=f32)
    y += jnp.exp(run)[..., None] * jnp.einsum(
        "bin,bhpn->bihp", c, state.astype(cd), preferred_element_type=f32)
    y += d[:, None] * x.astype(f32)
    to_end = jnp.exp(run[:, -1:] - run)                      # exp(L_end - L_j)
    state = jnp.exp(run[:, -1])[..., None, None] * state + jnp.einsum(
        "bjhp,bjn->bhpn", (xdt * to_end[..., None]).astype(cd), b, preferred_element_type=f32)
    return state, y.astype(cd)


def _walk(x, dt, a, b, c, d, chunk: int, keep: bool):
    """(y [B, T, H, P]; with ``keep`` each chunk's incoming state, [chunks,
    B, H, P, N], else None)."""
    with part("ssm_scan"):
        first = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[-1]), jnp.float32)

        def body(state, cut):
            xc, dtc, bc, cc = cut
            after, y = _chunk(state, xc, dtc, a, bc, cc, d)
            return after, (y, state if keep else None)

        _, (ys, states) = jax.lax.scan(body, first, tuple(_cut(v, chunk) for v in (x, dt, b, c)))
        return _join(ys, x.shape[1]), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def chunked_scan(x, dt, a, b, c, d, chunk: int):
    """``y`` [B, T, H, P] in ``x``'s type of the recurrence above.

    ``x`` [B, T, H, P]; ``dt`` [B, T, H] float32, positive; ``a`` [H]
    float32, negative; ``b``, ``c`` [B, T, N]; ``d`` [H] float32; ``chunk``
    the tokens of a chunk (``chunks_of``)."""
    return _walk(x, dt, a, b, c, d, chunk, keep=False)[0]


def _chunked_scan_fwd(x, dt, a, b, c, d, chunk: int):
    y, states = _walk(x, dt, a, b, c, d, chunk, keep=True)
    return y, (x, dt, a, b, c, d, states)


def _chunked_scan_bwd(chunk: int, kept, dy):
    x, dt, a, b, c, d, states = kept
    with part("ssm_scan"):
        def body(carry, cut):
            d_after, da, dd = carry
            state, xc, dtc, bc, cc, dyc = cut
            _, pull = jax.vjp(_chunk, state, xc, dtc, a, bc, cc, d)   # the chunk, computed again
            d_state, dx, ddt, da_c, db, dc, dd_c = pull((d_after, dyc))
            return (d_state, da + da_c, dd + dd_c), (dx, ddt, db, dc)

        zero = jnp.zeros_like
        (_, da, dd), cuts = jax.lax.scan(
            body, (zero(states[0]), zero(a), zero(d)),
            (states, *(_cut(v, chunk) for v in (x, dt, b, c, dy))), reverse=True)
        dx, ddt, db, dc = (_join(v, x.shape[1]) for v in cuts)
        return dx, ddt, da, db, dc, dd


chunked_scan.defvjp(_chunked_scan_fwd, _chunked_scan_bwd)

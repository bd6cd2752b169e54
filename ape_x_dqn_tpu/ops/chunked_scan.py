"""A selective state-space scan in chunks, with a backward pass that walks the
chunks in reverse (Mamba-2's recurrence, Dao & Gu 2024, arXiv:2405.21060, in
its chunked dual form).

Per head, with a state ``S`` in R^{P x N}, a decay ``A < 0`` and a skip ``D``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_{-1} = 0
    y_t = S_t C_t + D x_t

``B_t`` and ``C_t`` are shared by the heads of a group: by all heads where
there is one group, else by ``H / G`` consecutive heads each, and the groups
are walked side by side (``_by_groups``: the chunk below, mapped over them).
The sequence is cut
in chunks of ``chunk`` tokens (padded at the end with ``dt = 0`` and ``x =
0``: decay 1, no input; the padded rows are cut off and get no gradient) and
walked a chunk at a time, carrying the state across.  Inside a chunk, with
``L_i`` the running sum of ``dt A`` from the chunk's first token to ``i``:

    y_i = sum_{j <= i} exp(L_i - L_j) (C_i . B_j) dt_j x_j      the chunk's own tokens
        + exp(L_i) S_prev C_i                                     what came before it
        + D x_i
    S   = exp(L_end) S_prev + sum_j exp(L_end - L_j) dt_j x_j B_j^T

so the work is matrix products: the scores ``C B^T`` once a group, a
product of ``[chunk, chunk]`` by ``[chunk, P]`` a head, and two products with
the state.  Running sums, decays and the state are float32; the products
take operands in ``x``'s type and accumulate in float32.

The layout ``x`` and ``y`` cross the walk in is ``[chunks, B, H, P, chunk]``,
a chunk's tokens last: a head's ``P`` of 64 does not fill a TPU's 128 lanes,
so the compiler holds these operands with the tokens in the lanes whatever
order they are written in, and from ``[B, T, H, P]`` it got there by a
transposing copy, a pad and two copies that bring the chunks to the front,
each over the whole array, in either direction.  ``scan_chunks`` therefore
takes the sequence already cut and turned, ``dt`` as ``[chunks, B, H,
chunk]``, ``B`` and ``C`` as ``[chunks, B, chunk, N]`` (``N`` of 128 fills
the lanes; ``[chunks, B, G, chunk, N]`` where there are groups), and every product of ``_chunk`` contracts over the last axis of
an operand as it lies; a caller makes that layout where it makes the values
(``ops/pallas/scan_layout.py``); one that holds ``[B, T, H, P]`` cuts and
joins with ``cut`` and ``join``.

The backward pass is written by hand (``jax.custom_vjp``): it keeps the
inputs and each chunk's incoming state (``[chunks, B, H, P, N]`` float32)
and walks the chunks from the last to the first, computing each again and
pulling the cotangents of its outputs and of its outgoing state back through
it; nothing of ``[chunk, chunk]`` a head outlives its chunk in either pass,
where reverse-mode differentiation of the walk would stack every chunk's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.utils.profiling import part, pass_


def chunks_of(tokens: int, chunk: int) -> tuple:
    """(chunks walked, tokens after padding) for a sequence of ``tokens``; a
    sequence shorter than ``chunk`` is one chunk of its own length."""
    q = min(chunk, tokens)
    n = -(-tokens // q)
    return n, n * q


def cut(v, chunk: int, tokens_last: bool = False):
    """[B, T, ...] -> [chunks, B, chunk, ...] (``tokens_last``: [chunks, B,
    ..., chunk]), zeros past T."""
    n, padded = chunks_of(v.shape[1], chunk)
    v = jnp.pad(v, ((0, 0), (0, padded - v.shape[1])) + ((0, 0),) * (v.ndim - 2))
    v = jnp.moveaxis(v.reshape(v.shape[0], n, padded // n, *v.shape[2:]), 1, 0)
    return jnp.moveaxis(v, 2, -1) if tokens_last else v


def join(v, tokens: int, tokens_last: bool = False):
    """``cut``'s inverse: -> [B, T, ...]."""
    v = jnp.moveaxis(jnp.moveaxis(v, -1, 2) if tokens_last else v, 0, 1)
    return v.reshape(v.shape[0], -1, *v.shape[3:])[:, :tokens]


def _chunk(state, x, dt, a, b, c, d):
    """One chunk: (``state`` [B, H, P, N] float32, the chunk's ``x`` [B, H, P,
    Q], ``dt`` [B, H, Q] float32, ``b``, ``c`` [B, Q, N], the heads' ``a``,
    ``d`` [H]) -> (the state after it, ``y`` [B, H, P, Q] in ``x``'s type)."""
    cd, f32 = x.dtype, jnp.float32
    run = jnp.cumsum(dt * a[:, None], axis=-1)               # L: [B, H, Q]
    q = x.shape[-1]
    earlier = jnp.tril(jnp.ones((q, q), bool))               # j <= i
    # masked before the exponential: a later token's L_i - L_j is positive
    decay = jnp.exp(jnp.where(earlier, run[..., :, None] - run[..., None, :], -jnp.inf))
    scores = jnp.einsum("bin,bjn->bij", c, b, preferred_element_type=f32)
    xdt = x.astype(f32) * dt[:, :, None]                     # dt_j x_j
    y = jnp.einsum("bhpj,bhij->bhpi", xdt.astype(cd), (scores[:, None] * decay).astype(cd),
                   preferred_element_type=f32)
    y += jnp.exp(run)[:, :, None] * jnp.einsum(
        "bhpn,bin->bhpi", state.astype(cd), c, preferred_element_type=f32)
    y += d[:, None, None] * x.astype(f32)
    to_end = jnp.exp(run[..., -1:] - run)                    # exp(L_end - L_j)
    state = jnp.exp(run[..., -1])[..., None, None] * state + jnp.einsum(
        "bhpj,bjn->bhpn", (xdt * to_end[:, :, None]).astype(cd), b, preferred_element_type=f32)
    return state, y.astype(cd)


def _by_groups(groups: int):
    """``_chunk`` for ``b``, ``c`` [B, G, Q, N]: the heads lie in ``groups``
    runs of consecutive ones, and a run reads its own group's ``b`` and ``c``."""

    def split(v, axis: int):
        return v.reshape(*v.shape[:axis], groups, -1, *v.shape[axis + 1:])

    def chunk(state, x, dt, a, b, c, d):
        after, y = jax.vmap(_chunk, in_axes=(1, 1, 1, 0, 1, 1, 0), out_axes=1)(
            split(state, 1), split(x, 1), split(dt, 1), split(a, 0), b, c, split(d, 0))
        return after.reshape(state.shape), y.reshape(x.shape)

    return chunk


def _chunk_of(b):
    """The chunk's function for ``b`` cut in chunks: with a group axis or without."""
    return _chunk if b.ndim == 4 else _by_groups(b.shape[2])


def _walk(x, dt, a, b, c, d, keep: bool):
    """(y [chunks, B, H, P, Q]; with ``keep`` each chunk's incoming state,
    [chunks, B, H, P, N], else None)."""
    with part("ssm_scan"):
        first = jnp.zeros((*x.shape[1:4], b.shape[-1]), jnp.float32)
        one = _chunk_of(b)

        def body(state, chunk):
            xc, dtc, bc, cc = chunk
            after, y = one(state, xc, dtc, a, bc, cc, d)
            return after, (y, state if keep else None)

        return jax.lax.scan(body, first, (x, dt, b, c))[1]


@jax.custom_vjp
def scan_chunks(x, dt, a, b, c, d):
    """``y`` [chunks, B, H, P, Q] in ``x``'s type of the recurrence above,
    over a sequence already cut in chunks, zeros past its end.

    ``x`` [chunks, B, H, P, Q]; ``dt`` [chunks, B, H, Q] float32, positive;
    ``a`` [H] float32, negative; ``b``, ``c`` [chunks, B, Q, N], or [chunks,
    B, G, Q, N] where the heads read them by groups of ``H / G``; ``d`` [H]
    float32."""
    return _walk(x, dt, a, b, c, d, keep=False)[0]


def _pull(kept, dy):
    """The cotangents of ``scan_chunks``' inputs from ``dy``, all cut."""
    x, dt, a, b, c, d, states = kept
    with part("ssm_scan"):
        one = _chunk_of(b)

        def body(carry, chunk):
            d_after, da, dd = carry
            state, xc, dtc, bc, cc, dyc = chunk
            with pass_("again"):                                      # the chunk, computed again
                _, pull = jax.vjp(one, state, xc, dtc, a, bc, cc, d)
            d_state, dx, ddt, da_c, db, dc, dd_c = pull((d_after, dyc))
            return (d_state, da + da_c, dd + dd_c), (dx, ddt, db, dc)

        zero = jnp.zeros_like
        (_, da, dd), (dx, ddt, db, dc) = jax.lax.scan(
            body, (zero(states[0]), zero(a), zero(d)), (states, x, dt, b, c, dy), reverse=True)
        return dx, ddt, da, db, dc, dd


def _scan_fwd(x, dt, a, b, c, d):
    y, states = _walk(x, dt, a, b, c, d, keep=True)
    return y, (x, dt, a, b, c, d, states)


scan_chunks.defvjp(_scan_fwd, _pull)

"""Stratified inverse-CDF sampling over priorities.

The device replay's hot op is "given priorities p[0..C) and B stratified
target masses, find the B leaf indices whose prefix-sum intervals contain
them".  The flat XLA spelling (``cumsum`` + ``searchsorted``) writes and
reads again the whole C-length prefix array in HBM: measured on a real v5e
at C=2M it pays O(C) of traffic a call.  ``sample_indices`` is
``_two_level_sample``, a radix-sqrt(C) two-level inverse-CDF (the TPU-native
sum-tree) that does O(C/chunk) + O(B chunk) work; the flat spelling stays as
the tests' oracle.  A streaming Pallas kernel (one pass over the priorities,
a running carry in SMEM) was measured on the same chip, paid about 1 us a
tile of grid overhead, two orders behind the two-level sampler, and is gone
(PR 46); the module keeps its place under ``ops/pallas/`` for its importers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _xla_sample(priorities: jax.Array, targets: jax.Array) -> jax.Array:
    """Reference spelling: full cumsum + searchsorted (side='right' so a
    target exactly on a boundary selects the next nonzero-mass leaf)."""
    cdf = jnp.cumsum(priorities)
    idx = jnp.searchsorted(cdf, targets, side="right")
    return jnp.clip(idx, 0, priorities.shape[0] - 1).astype(jnp.int32)


def _two_level_sample(priorities: jax.Array, targets: jax.Array,
                      chunk: int = 1024) -> jax.Array:
    """Two-level inverse-CDF: the TPU-native sum-tree.

    A pointer-chasing O(log C) tree serializes on the VPU, and a flat cumsum
    is O(C) of HBM traffic per call — measured 1.8–3.2 ms at C=2M on a real
    v5e, which caps the fused learner at ~500 steps/s.  The two-level split
    does O(C/chunk) + O(B·chunk) work instead: one bandwidth-friendly
    row-reduce builds per-chunk masses, a tiny cumsum picks each target's
    chunk, and a B×chunk row cumsum resolves the leaf — ~5 µs at C=100k.
    This is exactly a radix-√C sum-tree with both levels vectorized.

    Same proportional-mass semantics as ``_xla_sample`` (indices may differ
    by a few leaves where float32 accumulation order shifts a boundary —
    immaterial for mass-proportional sampling).
    """
    C = priorities.shape[0]
    if C % chunk != 0:
        pad = chunk - C % chunk
        priorities = jnp.concatenate(
            [priorities, jnp.zeros((pad,), priorities.dtype)]
        )
    rows = priorities.reshape(-1, chunk).astype(jnp.float32)  # [R, chunk]
    row_mass = jnp.sum(rows, axis=1)                          # [R]
    row_cdf = jnp.cumsum(row_mass)
    targets = targets.astype(jnp.float32)
    r = jnp.clip(
        jnp.searchsorted(row_cdf, targets, side="right"), 0, rows.shape[0] - 1
    )
    rel = targets - (row_cdf[r] - row_mass[r])                # mass within row
    picked = rows[r]                                          # [B, chunk] gather
    cdf = jnp.cumsum(picked, axis=1)
    # side="right" per row: count of prefix entries <= rel.
    pos = jnp.sum((cdf <= rel[:, None]).astype(jnp.int32), axis=1)
    pos = jnp.minimum(pos, chunk - 1)
    return jnp.clip(r * chunk + pos, 0, C - 1).astype(jnp.int32)


def sample_indices(priorities: jax.Array, targets: jax.Array) -> jax.Array:
    """Stratified inverse-CDF lookup: indices [B] for target masses [B], by
    the two-level sampler."""
    return _two_level_sample(priorities, targets)

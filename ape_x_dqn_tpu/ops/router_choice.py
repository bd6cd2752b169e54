"""A router's choice as a selection: a token's ``k`` largest biased scores,
the groups it keeps and its raw gates, by rounds of "the largest not yet
taken, the first output that holds it".  Nothing is sorted and nothing is
gathered.

What it returns is what ``top_k`` and ``take_along_axis`` return, to the bit:
the largest first, of two equal scores the one with the smaller index first
(what a stable descending sort puts first), the gate the chosen output's
score as it lay in ``scores``.  With ``biased = scores + bias``:

- with groups: a group's score is its largest plus its second largest
  (largest, mask its first index, largest again), the ``groups_kept`` largest
  groups are kept by the rounds below over [T, groups], the other groups'
  outputs are -inf;
- ``k`` rounds: the largest of what is left, the first index that holds it
  *among the outputs not yet taken* (``left``: an output's index, or E once
  it is taken, so that a row that runs into -inf takes its earliest untaken
  output and never one twice), the gate ``sum(where(index == first, scores,
  0))``: one value and zeros, so no bit of it moves.

Plain reductions over a token's outputs: the TPU's compiler lays [T, E] out
with the tokens in the lanes, keeps it in VMEM and runs a round as two
reduce fusions over it.  Timed apart on a v5e they are no slower, at any
expert cell's shapes, than the same rounds written as a Pallas kernel over
tiles [E, tokens] (``PERF.md`` section 6, PR 43), so there is no kernel.

Only ``scores -> gates`` is differentiable: the pull-back puts ``dgates`` at
the chosen outputs of a zero [T, E] by a one-hot select, k distinct outputs a
token and so one term each: the bits a scatter-add into zeros gives, and
nothing of [T, E] is kept for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rounds(cur, rounds: int, gates_of=None):
    """``rounds`` times over the last axis of ``cur`` [..., n]: the largest,
    the first index not yet taken that holds it.  ([..., rounds] those
    indices, ``gates_of`` at them, and ``left``: an index where it was never
    taken, ``n`` where it was.)"""
    n = cur.shape[-1]
    index = jnp.arange(n, dtype=jnp.int32)
    left = jnp.broadcast_to(index, cur.shape)
    firsts, gates = [], []
    for _ in range(rounds):
        largest = jnp.max(cur, axis=-1, keepdims=True)
        first = jnp.min(jnp.where(cur == largest, left, n), axis=-1, keepdims=True)
        sel = index == first
        firsts.append(first)
        if gates_of is not None:
            gates.append(jnp.sum(jnp.where(sel, gates_of, 0.0), axis=-1, keepdims=True))
        cur = jnp.where(sel, -jnp.inf, cur)
        left = jnp.where(sel, n, left)
    return (jnp.concatenate(firsts, -1), jnp.concatenate(gates, -1) if gates else None, left)


def _choose(scores, bias, kept, k: int, groups: int, groups_kept: int):
    with jax.named_scope("router_choice"):
        biased = scores + bias
        if groups > 1:
            by_group = biased.reshape(biased.shape[0], groups, -1)
            if kept is None:
                _, top2, _ = _rounds(by_group, 2, by_group)         # a group's two largest
                kept = _rounds(top2[..., 0] + top2[..., 1], groups_kept)[2] == groups
            biased = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(biased.shape)
        chosen, gates, _ = _rounds(biased, k, scores)
        return chosen, gates, kept


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def router_choice(scores, bias, kept, k: int, groups: int = 1, groups_kept: int = 1):
    """(``chosen`` [T, k] int32, raw ``gates`` [T, k] float32, ``kept`` [T,
    groups] bool or None without groups) from float32 ``scores`` [T, E] and
    ``bias`` [E] (module docstring).  ``kept``: None, or the groups a token
    keeps, given."""
    return _choose(scores, bias, kept, k, groups, groups_kept)


def _choice_fwd(scores, bias, kept, k, groups, groups_kept):
    out = _choose(scores, bias, kept, k, groups, groups_kept)
    return out, (out[0], bias)


def _choice_bwd(k, groups, groups_kept, res, cotangents):
    chosen, bias = res
    with jax.named_scope("router_choice"):
        hit = chosen[:, :, None] == jnp.arange(bias.shape[0], dtype=jnp.int32)
        dscores = jnp.sum(jnp.where(hit, cotangents[1][:, :, None], 0.0), axis=1)
    return dscores, jnp.zeros_like(bias), None


router_choice.defvjp(_choice_fwd, _choice_bwd)

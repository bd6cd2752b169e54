"""The router's choice by selection (``ops/router_choice.py``, through
``expert_torso.choose`` / ``route`` / ``groups_kept``) against the choice by
sorting that it replaced, kept here as the oracle: ``jax.lax.top_k`` and
``take_along_axis``.  The same experts in the same order on every row, ties
included; the groups kept; raw and normalised gates and the gradient of the
raw gates with respect to the scores equal bit for bit.

The normalised gates divide by the sum of a token's k gates, and the order of
that sum is the one the TPU gave it while the gates came from a gather (k in
the lanes: halves folded onto each other from the widest down), written out
in ``expert_torso._lane_sum``: the oracle here sums in that order too
(``lanes_sum``), since this CPU's ``jnp.sum`` adds one after another; the
gradient through the normalisation is held to the oracle's within rounding
here and bit for bit on the chip (``chip_smoke.py --ling-kernels``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_torso
from ape_x_dqn_tpu.ops.router_choice import router_choice

# (router outputs, a token's, groups, groups kept, score function, tokens): the four cells' and a small one
SHAPES = {
    "ling3_q_l7": (512, 8, 8, 4, "sigmoid", 300),
    "solar2_q_ep40": (320, 8, 1, 1, "sigmoid", 300),
    "laguna_q_ep32": (256, 10, 1, 1, "softmax", 300),
    "lfm2moe_q_ep8": (64, 4, 1, 1, "sigmoid", 1100),
    "small": (16, 3, 4, 2, "sigmoid", 50),
}


def _spec(outputs, k, groups, kept, score_function, scale=2.5, norm=True):
    return expert_torso.TorsoSpec(
        hidden_size=8, intermediate_size=8, moe_intermediate_size=8, norm_eps=1e-5,
        router_outputs=outputs, num_experts_per_tok=k, experts_held=(0, 2),
        layers=(("op", "moe"),), mixers=(("op", None),), norm_topk_prob=norm,
        routed_scaling_factor=scale, score_function=score_function,
        router_groups=groups, router_groups_kept=kept)


def sorted_groups_kept(biased, spec):
    """The parent's ``groups_kept``, word for word."""
    groups = spec.router_groups
    by_group = biased.reshape(biased.shape[0], groups, -1)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], -1)
    _, kept = jax.lax.top_k(score, spec.router_groups_kept)
    return jnp.any(kept[:, :, None] == jnp.arange(groups), axis=1)


def lanes_sum(x):
    """The sum over the last axis of [T, k] as the TPU sums a row's lanes:
    zeros up to a power of two, then the upper half onto the lower, again
    and again."""
    width = 1 << (x.shape[1] - 1).bit_length()
    x = jnp.pad(x, ((0, 0), (0, width - x.shape[1])))
    while width > 1:
        width //= 2
        x = x[:, :width] + x[:, width:]
    return x


def sorted_route(scores, bias, spec, kept=None, raw=False, total=lanes_sum):
    """The parent's ``route``, word for word but for the sum's order, which
    was the chip's (``raw``: the gates before the normalisation)."""
    biased = scores + bias
    if spec.router_groups > 1:
        kept = sorted_groups_kept(biased, spec) if kept is None else kept
        biased = jnp.where(jnp.repeat(kept, spec.router_outputs // spec.router_groups, axis=-1),
                           biased, -jnp.inf)
    _, chosen = jax.lax.top_k(biased, spec.num_experts_per_tok)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if raw:
        return chosen, gates
    if spec.norm_topk_prob:
        gates = gates / (total(gates) + spec.gate_norm_eps)
    return chosen, gates * spec.routed_scaling_factor


def _scores(name, kind, seed=0):
    outputs, k, groups, kept, score_function, tokens = SHAPES[name]
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = jax.random.normal(key[0], (tokens, outputs))
    scores = jax.nn.sigmoid(logits) if score_function == "sigmoid" else jax.nn.softmax(logits, -1)
    bias = 0.05 * jax.random.normal(key[1], (outputs,))
    if kind == "ties":           # 1/64ths (1/8ths of 16 outputs) and no bias: most rows hold equal scores
        scale = 64.0 * outputs / 8 if score_function == "softmax" else 64.0 if outputs >= 64 else 8.0
        scores, bias = jnp.round(scores * scale) / scale, jnp.zeros_like(bias)
    elif kind in ("just_enough", "too_few"):
        # k finite scores (or two fewer) among the outputs of the first ``kept`` groups, -inf
        # everywhere else: those groups are kept (the earlier of equal ones first)
        allowed = outputs if groups == 1 else kept * (outputs // groups)
        noise = jnp.where(jnp.arange(outputs) < allowed, jax.random.uniform(key[2], scores.shape), 2.0)
        finite = jnp.argsort(jnp.argsort(noise, -1), -1) < (k if kind == "just_enough" else k - 2)
        scores, bias = jnp.where(finite, scores, -jnp.inf), jnp.zeros_like(bias)
    return scores.astype(jnp.float32), bias.astype(jnp.float32)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("kind", ["random", "ties", "just_enough", "too_few"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_the_selection_chooses_what_the_sort_chose(name, kind):
    outputs, k, groups, kept, score_function, tokens = SHAPES[name]
    spec = _spec(outputs, k, groups, kept, score_function)
    scores, bias = _scores(name, kind)
    if kind == "ties":
        first = np.sort(np.asarray(scores), -1)[:, ::-1][:, :k + 1]
        assert (first[:, :-1] == first[:, 1:]).any(-1).mean() > 0.5, "no ties among the largest"
    chosen, raw, groups_out = jax.jit(lambda s, b: router_choice(s, b, None, k, groups, kept))(scores, bias)
    want, want_raw = jax.jit(lambda s, b: sorted_route(s, b, spec, raw=True))(scores, bias)
    assert chosen.dtype == jnp.int32 and raw.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_array_equal(_bits(raw), _bits(want_raw))
    routed, gates = jax.jit(lambda s, b: expert_torso.route(s, b, spec))(scores, bias)
    np.testing.assert_array_equal(np.asarray(routed), np.asarray(want))
    np.testing.assert_array_equal(_bits(gates), _bits(jax.jit(
        lambda s, b: sorted_route(s, b, spec))(scores, bias)[1]))
    if groups > 1:
        want_kept = np.asarray(sorted_groups_kept(scores + bias, spec))
        np.testing.assert_array_equal(np.asarray(groups_out), want_kept)
        np.testing.assert_array_equal(np.asarray(expert_torso.groups_kept(scores + bias, spec)), want_kept)
    else:
        assert groups_out is None
    ranked = np.sort(np.asarray(chosen), -1)
    assert (ranked[:, 1:] != ranked[:, :-1]).all(), "an output taken twice"
    if kind in ("just_enough", "too_few"):    # every finite value taken, and -inf after them
        assert (np.isfinite(np.asarray(raw)).sum(-1) == (k if kind == "just_enough" else k - 2)).all()


@pytest.mark.parametrize("name", ["ling3_q_l7", "small"])
def test_given_groups_are_the_ones_chosen_among(name):
    outputs, k, groups, kept, score_function, tokens = SHAPES[name]
    spec = _spec(outputs, k, groups, kept, score_function)
    scores, bias = _scores(name, "random", seed=3)
    given = jnp.roll(sorted_groups_kept(scores + bias, spec), 1, axis=-1)   # not the ones it would keep
    chosen, gates = expert_torso.route(scores, bias, spec, given)
    want, want_gates = sorted_route(scores, bias, spec, given)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_array_equal(_bits(gates), _bits(want_gates))
    np.testing.assert_array_equal(np.asarray(expert_torso.choose(scores, bias, spec, given)[2]),
                                  np.asarray(given))
    assert np.take_along_axis(np.asarray(given), np.asarray(chosen) // (outputs // groups), -1).all()


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("name", list(SHAPES))
def test_the_gates_gradient_is_the_scatter_adds_bit_for_bit(name, norm):
    outputs, k, groups, kept, score_function, tokens = SHAPES[name]
    spec = _spec(outputs, k, groups, kept, score_function, norm=norm)
    scores, bias = _scores(name, "random", seed=5)
    weight = jax.random.normal(jax.random.PRNGKey(9), (tokens, k))

    def total(route):
        return lambda s, b: jnp.sum(route(s, b, spec)[1] * weight)

    got = jax.jit(jax.grad(total(expert_torso.route), argnums=(0, 1)))(scores, bias)
    want = jax.jit(jax.grad(total(sorted_route), argnums=(0, 1)))(scores, bias)
    if norm:    # the pull-back's own sum over k: the lanes' order against this CPU's
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(want[0]))))
    else:
        np.testing.assert_array_equal(_bits(got[0]), _bits(want[0]))
    assert (np.asarray(got[0]) != 0).sum() == tokens * k
    np.testing.assert_array_equal(np.asarray(got[1]), 0.0)     # the choice carries no gradient
    np.testing.assert_array_equal(np.asarray(want[1]), 0.0)


def test_the_gates_sum_is_written_in_the_lanes_order_forward_and_pulled_back():
    x = jax.random.uniform(jax.random.PRNGKey(0), (500, 10)) + 0.01
    c = [x[:, i] for i in range(10)]
    for k, want in ((4, (c[0] + c[2]) + (c[1] + c[3])),
                    (8, ((c[0] + c[4]) + (c[2] + c[6])) + ((c[1] + c[5]) + (c[3] + c[7]))),
                    (10, (((c[0] + c[8]) + c[4]) + (c[2] + c[6])) + (((c[1] + c[9]) + c[5]) + (c[3] + c[7]))),
                    (1, c[0])):
        got = expert_torso._lane_sum(x[:, :k])
        np.testing.assert_array_equal(_bits(got), _bits(want))
        np.testing.assert_array_equal(_bits(got), _bits(lanes_sum(x[:, :k])[:, 0]))
    assert (np.asarray(expert_torso._lane_sum(x[:, :8])) != np.asarray(jnp.sum(x[:, :8], -1))).mean() > 0.1
    # the normalisation's pull-back: ct / den, and the denominator's part summed in the same order
    eps, ct = 1e-6, jax.random.normal(jax.random.PRNGKey(1), (500, 8))
    g = x[:, :8]
    got = jax.vjp(lambda g: g / expert_torso._over_lanes(expert_torso._lane_sum(g) + eps, 8), g)[1](ct)[0]
    den = (expert_torso._lane_sum(g) + eps)[:, None]
    want = ct / den + (-expert_torso._lane_sum((ct * den ** -2) * g))[:, None]
    np.testing.assert_array_equal(_bits(got), _bits(want))
    text = str(jax.make_jaxpr(jax.grad(lambda g: jnp.sum(g / expert_torso._over_lanes(
        expert_torso._lane_sum(g) + eps, 8))))(g))
    assert "reduce_sum[axes=(1,)" not in text, "a sum over k whose order the layout decides"


def test_the_choice_holds_no_sort_and_no_gather_and_keeps_the_chosen_alone():
    spec = _spec(64, 4, 4, 2, "sigmoid")
    scores, bias = jnp.zeros((49, 64)), jnp.zeros(64)
    text = str(jax.make_jaxpr(jax.grad(lambda s, b: jnp.sum(expert_torso.route(s, b, spec)[1])))(
        scores, bias))
    assert not [op for op in ("top_k", "sort", "gather", "scatter") if op in text], text
    _, kept = jax.vjp(lambda s: expert_torso.route(s, bias, spec)[1], scores)
    wide = [x.shape for x in jax.tree.leaves(kept) if x.shape[-1:] == (64,) and x.ndim > 1]
    assert not wide, f"the pull-back keeps an array of the scores' size: {wide}"

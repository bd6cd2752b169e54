"""The network kind ``ling_hybrid`` in the program: the attention kernels with
a shared key operand against plain attention, the router that keeps groups
against a sort in numpy, the delta-rule scan at the bounded gate's floor, the
two mixers' shares of heads and the expert layer's 32 shares against the uncut
layers, at small widths on the CPU (the attention kernels in Pallas'
interpreter); what every torso is held to (structure,
``benchmark/reference/ling3_q.py`` on seeded weights, the float32 leaves,
scopes, counters, the configuration path, the trainer's loop) is the
contract's, ``tests/torso_contract.py``, on this torso's row."""
import dataclasses
import importlib
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_torso, ling_hybrid, solar_open2
from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta as delta
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from tests import torso_contract as contract
from tests.torso_contract import built, init_of, pulled  # noqa: F401 - built: the module's fixture

TORSO = contract.LING


class TestContract(contract.of("ling_hybrid")):
    """The contract's cases on this torso (``tests/torso_contract.py``)."""


# ------------------------------------------------- the kernels' shared operand

def _plain(qn, kn, v, qs, ks):
    """Causal softmax attention a head with the scores' two parts written out."""
    s = jnp.einsum("bhtd,bhsd->bhts", qn, kn) + jnp.einsum("bhtd,bosd->bhts", qs, ks)
    t = qn.shape[2]
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf), -1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def _operands(tokens, rows=2, heads=3, group=1, width=128, shared=64):
    ks = jax.random.split(jax.random.PRNGKey(tokens), 6)
    scale = 1.0 / math.sqrt(width + shared)
    return (jax.random.normal(ks[0], (rows, heads, tokens, width)) * scale,
            jax.random.normal(ks[1], (rows, heads // group, tokens, width)),
            jax.random.normal(ks[2], (rows, heads // group, tokens, width)),
            jax.random.normal(ks[3], (rows, heads, tokens, shared)) * scale,
            jax.random.normal(ks[4], (rows, 1, tokens, shared))), jax.random.normal(
                ks[5], (rows, heads, tokens, width))


def test_the_kernels_add_the_shared_keys_scores_forward_and_in_all_five_gradients():
    """192 = 128 + 64 against values of 128, one rope key for every head, a
    length that is no multiple of a block (a head has keys of its own, so a
    block is 256 tokens: 700 = 2 query blocks and a rest of 188, one key
    block of 512 and a rest of 188): the output and the
    gradients of both query parts, the keys, the shared key (summed over the
    heads) and the values against autodiff of plain attention."""
    args, cot = _operands(700)
    got, gots = pulled(lambda *a: blocked.blocked_attention(a[0], a[1], a[2], None, a[3], a[4]))(cot, *args)
    want, wanted = pulled(_plain)(cot, *args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for name, a, b in zip(("q", "k", "v", "q_shared", "k_shared"), gots, wanted):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4, err_msg=name)


def test_a_group_of_query_heads_shares_the_shared_key_too():
    """The operand is the kernels', not latent attention's alone: two query
    heads a key-value head, a shared part of 32 beside heads of 64."""
    (q, k, v, qs, ks), cot = _operands(200, heads=4, group=2, width=64, shared=32)
    rep = lambda x: jnp.repeat(x, 2, axis=1)  # noqa: E731
    got, gots = pulled(lambda *a: blocked.blocked_attention(a[0], a[1], a[2], None, a[3], a[4]))(
        cot, q, k, v, qs, ks)
    want, wanted = pulled(lambda q, k, v, qs, ks: _plain(q, rep(k), rep(v), qs, ks))(cot, q, k, v, qs, ks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for a, b in zip(gots, wanted):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4)


def test_without_a_shared_operand_the_traced_program_is_the_older_one():
    """No shared key: the same custom_vjp, kernels and operands as before the
    operand existed (three inputs and the schedule), so the three older
    families' programs do not move; with one, two inputs more."""
    (q, k, v, qs, ks), _ = _operands(130, heads=2)
    plain = str(jax.make_jaxpr(lambda *a: blocked.blocked_attention(*a))(q, k, v))
    assert "shared" not in plain and plain.count("pallas_call") == 1
    grads = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(blocked.blocked_attention(*a)),
                                        argnums=(0, 1, 2)))(q, k, v))
    assert grads.count("pallas_call") == 3 and "f32[2,1,130,64]" not in grads
    both = str(jax.make_jaxpr(lambda *a: blocked.blocked_attention(a[0], a[1], a[2], None, a[3], a[4]))(
        q, k, v, qs, ks))
    assert both.count("pallas_call") == 1 and "f32[2,1,130,64]" in both


@pytest.mark.parametrize("family,layers,heads", [("ling", 1, 8), ("kanana", 6, 32)])
def test_what_the_two_pinned_tests_hold_beside_the_plan(monkeypatch, family, layers, heads):
    """``tests/benchmark/test_benchmark_{ling,kanana}_reference.py::test_published_configuration_builds_abstractly``
    (the benchmark's files; ``tests/conftest.py`` expects them to fail) hold
    the published networks' counters to blocks of 128 tokens.  With the plan
    told a group of 2, as they stand, they pass: the parameter trees, the
    counts and the other counters are held.  The counters themselves are the
    plan's a group of 1 gets: 16 blocks of 256 x 512 of 7 x 4 a head."""
    from ape_x_dqn_tpu.models.dueling import build_network

    tests = os.path.dirname(os.path.abspath(__file__))
    for path in (os.path.join(tests, "benchmark"), os.path.join(os.path.dirname(tests), "benchmark")):
        monkeypatch.syspath_prepend(path)      # as tests/benchmark/conftest.py has them
    pinned = importlib.import_module(f"test_benchmark_{family}_reference")
    cfg = pinned.PUBLISHED
    net = build_network(cfg["network"], cfg["num_actions"], torso=cfg,
                        channels=tuple(cfg["channels"]), hidden=cfg["hidden"])
    assert net.attention_metrics((8, 84, 84, 32)) == {
        "pairs_in_mask_latent": layers * 8 * 1_230_096.0,
        "pairs_computed_latent": layers * 8 * 16 * 256 * 512.0,
        "blocks_visited_latent": layers * 8 * heads * 16.0,
        "blocks_total_latent": layers * 8 * heads * 7 * 4.0}
    whole = blocked.plan
    monkeypatch.setattr(blocked, "plan", lambda tokens, window, group: whole(tokens, window, max(group, 2)))
    pinned.test_published_configuration_builds_abstractly()


# ------------------------------------------------------------------ the router

def _numpy_route(scores, bias, groups, kept, k, scale):
    """By sorting, a token at a time."""
    chosen, gates = [], []
    for s in np.asarray(scores, np.float64):
        b = s + np.asarray(bias, np.float64)
        size = len(b) // groups
        group_score = [np.sort(b[g * size:(g + 1) * size])[-2:].sum() for g in range(groups)]
        keep = sorted(range(groups), key=lambda g: (-group_score[g], g))[:kept]
        allowed = [e for g in sorted(keep) for e in range(g * size, (g + 1) * size)]
        top = sorted(allowed, key=lambda e: (-b[e], e))[:k]
        chosen.append(top)
        gates.append(s[top] / s[top].sum() * scale)
    return np.asarray(chosen), np.asarray(gates)


def test_route_keeps_groups_before_it_chooses_experts():
    spec = ling_hybrid.spec_from_config(dict(TORSO, router_outputs=64, n_group=8, topk_group=4,
                                             num_experts_per_tok=8, experts_held=[0, 2]))
    assert (spec.router_groups, spec.router_groups_kept) == (8, 4)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (200, 64)))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    chosen, gates = expert_torso.route(scores, bias, spec)
    want_chosen, want_gates = _numpy_route(scores, bias, 8, 4, 8, 2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1), np.sort(want_chosen, -1))
    order = np.argsort(np.asarray(chosen), -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(gates), order, -1),
                               np.take_along_axis(want_gates, np.argsort(want_chosen, -1), -1),
                               rtol=1e-5)
    kept = np.asarray(expert_torso.groups_kept(scores + bias, spec))
    assert kept.shape == (200, 8) and (kept.sum(-1) == 4).all()
    assert (np.take_along_axis(kept, np.asarray(chosen) // 8, -1)).all()      # none outside them
    # the plain top 8 of all 64 differs on most tokens: the groups decide
    plain = np.asarray(expert_torso.route(scores, bias, dataclasses.replace(
        spec, router_groups=1, router_groups_kept=1))[0])
    assert (np.sort(plain, -1) != np.sort(np.asarray(chosen), -1)).any(-1).mean() > 0.5


def test_one_group_is_the_plain_top_k_bit_for_bit():
    spec = ling_hybrid.spec_from_config(dict(TORSO, n_group=1, topk_group=1))
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (300, 16)))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    chosen, gates = expert_torso.route(scores, bias, spec)
    _, want = jax.lax.top_k(scores + bias, 2)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    picked = jnp.take_along_axis(scores, want, -1)
    np.testing.assert_array_equal(np.asarray(gates),
                                  np.asarray(picked / jnp.sum(picked, -1, keepdims=True) * 2.5))
    text = str(jax.make_jaxpr(lambda s, b: expert_torso.route(s, b, spec))(scores, bias))
    # two rounds of selection and no operation of the groups' (their two largest, their rounds)
    assert "top_k" not in text and text.count("reduce_max") == 2
    for bad in (dict(n_group=3), dict(topk_group=5), dict(n_group=4, topk_group=1, num_experts_per_tok=5)):
        with pytest.raises(ValueError, match="router_groups"):
            ling_hybrid.spec_from_config(dict(TORSO, **bad))


# ---------------------------- the chip's own numeric check (chip_smoke.py --ling)

@pytest.mark.parametrize("lost", [None, "shared_key", "groups"])
def test_the_chips_numeric_check_passes_here_and_fails_on_a_lost_mechanism(monkeypatch, lost):
    """``chip_smoke.py``'s leg ``ling_kernels``, which on the chip holds the
    kernels' shared key operand and the router's groups to plain attention and
    to a sort (the cell's comparison sees neither over its own rounding), at a
    small size here: it passes on the program as it is, and a kernel call
    that lost ``q_shared k_shared^T`` or a router that forgot its groups
    fails it."""
    import chip_smoke

    sizes = dict(rows=1, heads=2, tokens=300)       # a block of 256 tokens and a rest of 44
    if lost == "shared_key":
        whole = blocked.blocked_attention
        monkeypatch.setattr(blocked, "blocked_attention",
                            lambda q, k, v, window=None, q_shared=None, k_shared=None: whole(q, k, v, window))
        with pytest.raises(AssertionError, match="from plain attention"):
            chip_smoke.latent_kernels_against_plain(**sizes)
        return
    if lost == "groups":
        whole = expert_torso.route
        monkeypatch.setattr(expert_torso, "route", lambda s, b, spec, kept=None: whole(
            s, b, dataclasses.replace(spec, router_groups=1, router_groups_kept=1)))
        with pytest.raises(AssertionError, match="choose other experts than sorting"):
            chip_smoke.route_against_sorting(tokens=512)
        return
    readings = chip_smoke.latent_kernels_against_plain(**sizes)
    assert set(readings) == {"out", "dq", "dk", "dv", "dq_shared", "dk_shared"}
    assert all(near <= chip_smoke.KERNEL_REL < chip_smoke.KERNEL_REL_WITHOUT_SHARED_KEY <= far
               for near, far in readings.values())
    routed = chip_smoke.route_against_sorting(tokens=512)
    assert routed["differing"] == 0 and routed["ungrouped_differs_share"] > 0.5


@pytest.mark.parametrize("lost", [None, "first_index"])
def test_the_chips_check_of_the_choice_against_the_sort_runs_here(monkeypatch, lost):
    """``chip_smoke.choice_against_sorting_on_the_chip`` at small shapes: it
    passes on the selection as it is (with and without groups, ties
    included) and returns its timings; a selection that takes the last of
    equal scores, not the first, fails it."""
    import chip_smoke
    from ape_x_dqn_tpu.ops import router_choice

    shapes = ((300, 64, 4, 4, 2), (200, 32, 3, 1, 1))
    if lost == "first_index":
        whole = router_choice._rounds

        def last_of_equals(cur, rounds, gates_of=None):
            n = cur.shape[-1]
            firsts, gates, left = whole(cur[..., ::-1], rounds,
                                        None if gates_of is None else gates_of[..., ::-1])
            return n - 1 - firsts, gates, jnp.where(left[..., ::-1] == n, n, jnp.arange(n))

        monkeypatch.setattr(router_choice, "_rounds", last_of_equals)
        with pytest.raises(AssertionError, match="differs from the sort's"):
            chip_smoke.choice_against_sorting_on_the_chip(shapes=shapes, walk_tile=(64, 16), repeats=1)
        return
    rows = chip_smoke.choice_against_sorting_on_the_chip(shapes=shapes, walk_tile=(64, 16), repeats=1)
    assert [r.get("outputs") for r in rows] == [64, 32, None] and rows[-1]["scatter_add_us"] > 0
    assert all(r[key] > 0 for r in rows[:2] for key in ("selection_us", "sorting_us", "pairs_argsort_us"))


# ------------------------------------------- the scan at the bounded gate's floor

def test_the_scan_at_a_decay_of_e_to_the_minus_five_a_step_is_the_literal_recurrence():
    """Every ``g`` at ``-5 + 1e-3``, the bounded gate's floor: a sub-block of
    16 rows decays by e^-80 and a chunk of 64 by e^-320, past float32's
    smallest number; the chunked form and its hand-walked backward pass stay
    finite and agree with the recurrence stepped a token at a time, within
    the limits the other family's tests use."""
    from tests.test_solar_open2 import literal, scan_inputs

    (q, k, v, g, beta), cot = scan_inputs(128, beta_scale=1.0)
    g = jnp.full_like(g, -5.0 + 1e-3)
    assert float(jnp.sum(g[0, 0, :64, 0])) < -319.0
    with jax.default_matmul_precision("highest"):
        want, wanted = pulled(literal)(cot, q, k, v, g, beta)
        got, gots = pulled(lambda *z: delta(*z, 64))(cot, q, k, v, g, beta)
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
        for name, a, b in zip(("q", "k", "v", "g", "beta"), gots, wanted):
            assert bool(jnp.all(jnp.isfinite(a))), name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                                       err_msg=name)


def test_the_gate_is_bounded_and_told_to_the_one_delta_module():
    """``DeltaAttention`` is ``solar_open2``'s class, told its rank and rule:
    full-rank ``W_f`` and ``W_g``, no gate bias, log decays in (-5, 0)."""
    assert ling_hybrid.DeltaAttention is solar_open2.DeltaAttention
    spec = ling_hybrid.spec_from_config(TORSO)
    m = spec.arg("linear")
    assert (m.gate_rank, m.gate, m.gate_bound, m.beta_scale, m.chunk) == (None, "bounded", -5.0, 1.0, 16)
    layer = solar_open2.DeltaAttention(spec, "linear_attention", jnp.float32, jnp.float32)
    u = 30.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))     # drives the sigmoid to both ends
    params = init_of(layer, jax.random.PRNGKey(1), u)["params"]
    assert set(params) == {"w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f", "A_log",
                           "dt_bias", "w_b", "w_g", "norm", "w_o"}
    assert params["w_f"].shape == params["w_g"].shape == (64, 64)
    seen = {}
    import ape_x_dqn_tpu.models.solar_open2 as so

    real = so.chunked_delta
    try:
        def spied(q, k, v, g, beta, chunk):      # the values, not the tracers of a remat
            jax.debug.callback(lambda g, b: seen.update(g=np.asarray(g), beta=np.asarray(b)), g, beta)
            return real(q, k, v, g, beta, chunk)

        so.chunked_delta = spied
        out = layer.apply({"params": params}, u)
    finally:
        so.chunked_delta = real
    assert bool(jnp.all(jnp.isfinite(out)))
    jax.effects_barrier()
    assert -5.0 <= seen["g"].min() < -4.0 and -0.5 < seen["g"].max() <= 0.0
    assert 0.0 <= seen["beta"].min() and seen["beta"].max() <= 1.0
    with pytest.raises(ValueError, match="gate rule"):
        bad = dataclasses.replace(spec, mixer_args=(("linear", dataclasses.replace(m, gate="tanh")),))
        solar_open2.DeltaAttention(bad, "linear_attention", jnp.float32, jnp.float32).init(
            jax.random.PRNGKey(1), u)


# ------------------------------------------------------------------ the shares

def _linear_share(params, lo, hi, hd):
    cols = slice(lo * hd, hi * hd)
    out = {}
    for name, w in params.items():
        if name in ("w_q", "w_k", "w_v", "w_f", "w_g"):
            out[name] = w[:, cols]
        elif name in ("conv_q", "conv_k", "conv_v", "dt_bias", "w_o"):
            out[name] = w[cols]
        elif name in ("A_log", "w_b"):
            out[name] = w[..., lo:hi]
        else:                       # the head norm
            out[name] = w
    return out


def _latent_share(params, lo, hi, m):
    qw, kvw = m.nope + m.rope, m.nope + m.v
    return {"w_q": params["w_q"][:, lo * qw:hi * qw], "w_ukv": params["w_ukv"][:, lo * kvw:hi * kvw],
            "w_g": params["w_g"][:, lo:hi], "w_o": params["w_o"][lo * m.v:hi * m.v],
            "w_dkv": params["w_dkv"], "kv_norm": params["kv_norm"]}     # alike on every chip


@pytest.mark.parametrize("op", ling_hybrid.LAYER_TYPES)
def test_the_four_head_shares_add_up_to_the_uncut_mixer(op):
    """Eight heads on four tensor-parallel shares of two: the shares' outputs
    (each the held heads' part of ``W_o``'s sum) add up to the uncut layer's;
    the latent layer's down-projection, norm and shared rope key are alike on
    every share."""
    spec = ling_hybrid.spec_from_config(dict(TORSO, num_attention_heads=8))
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    mixer = dict(spec.mixers)[op]
    whole = mixer(spec, op, jnp.float32, jnp.float32)
    params = init_of(whole, jax.random.PRNGKey(1), u)["params"]
    want = whole.apply({"params": params}, u)
    total = 0.0
    for lo in range(0, 8, 2):
        share = dataclasses.replace(spec, heads_held=(lo, lo + 2))
        part = (_linear_share(params, lo, lo + 2, 16) if op == "linear_attention"
                else _latent_share(params, lo, lo + 2, spec.arg("latent")))
        layer = mixer(share, op, jnp.float32, jnp.float32)
        got = jax.eval_shape(layer.init, jax.random.PRNGKey(1), u)["params"]
        assert {k: v.shape for k, v in got.items()} == {k: v.shape for k, v in part.items()}
        total = total + layer.apply({"params": part}, u)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    assert mixer.divides_heads


def test_the_32_expert_shares_add_up_to_the_uncut_expert_layer():
    """64 routed experts in 8 groups on 32 expert-parallel shares of two (a
    share's experts all in one group): the held experts' parts add up, with
    the shared expert counted once, to the uncut layer's; each share's count
    of the tokens that keep its group is the uncut router's."""
    base = ling_hybrid.spec_from_config(dict(
        TORSO, num_experts=64, router_outputs=64, experts_held=[0, 64], n_group=8, topk_group=4,
        num_experts_per_tok=8))
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 64))
    shared = expert_torso.SwiGLU(base.shared_expert_intermediate_size, jnp.float32, jnp.float32)
    sp = init_of(shared, jax.random.PRNGKey(4), u)
    whole = expert_torso.ExpertShare(base, jnp.float32, jnp.float32)
    params = init_of(whole, jax.random.PRNGKey(5), u)["params"]
    out, sown = whole.apply({"params": params}, u, mutable=["routing"])
    assert int(sown["routing"]["kept"][0]) == 80                 # every token keeps some group
    want = out + shared.apply(sp, u)
    total, kept = shared.apply(sp, u), []                        # counted once
    for lo in range(0, 64, 2):
        share = dataclasses.replace(base, experts_held=(lo, lo + 2))
        part = dict(params, w13=params["w13"][lo:lo + 2], w2=params["w2"][lo:lo + 2])
        y, sown = expert_torso.ExpertShare(share, jnp.float32, jnp.float32).apply(
            {"params": part}, u, mutable=["routing"])
        total = total + y
        kept.append(int(sown["routing"]["kept"][0]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=3e-5)
    # four shares a group see the same count; four groups of eight are kept a token
    assert all(len(set(kept[g * 4:(g + 1) * 4])) == 1 for g in range(8)) and sum(kept[::4]) == 4 * 80


def test_rope_turns_pairs_and_scores_depend_on_the_distance_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 9, 8))
    got = np.asarray(ling_hybrid.rope_pairs(x, 100.0), np.float64)
    pos = np.arange(9)[:, None] * 100.0 ** (-np.arange(0, 8, 2) / 8)[None, :]
    even, odd = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    np.testing.assert_allclose(got[..., 0::2], even * np.cos(pos) - odd * np.sin(pos), atol=1e-6)
    np.testing.assert_allclose(got[..., 1::2], odd * np.cos(pos) + even * np.sin(pos), atol=1e-6)
    same = jnp.broadcast_to(x[:, :, :1], x.shape)                 # one vector at every position
    turned = ling_hybrid.rope_pairs(same, 100.0)
    dots = np.asarray(jnp.einsum("bhtd,bhsd->bhts", turned, turned))
    np.testing.assert_allclose(dots[0, 0, 2, 0], dots[0, 0, 7, 5], rtol=1e-5)

"""The network kind ``ling_hybrid`` in the program: the attention kernels with
a shared key operand against plain attention, the router that keeps groups
against a sort in numpy, the delta-rule scan at the bounded gate's floor, the
two mixers' shares of heads and the expert layer's 32 shares against the uncut
layers, the counters, the configuration path and the trainer's loop, all at
small widths on the CPU (the attention kernels in Pallas' interpreter).  The
network against ``benchmark/reference/ling3_q.py`` on seeded weights is
``tests/test_ling_hybrid_reference.py``: a file of its own, so that another
worker of the test run takes those two and a half minutes."""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "benchmark"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from ape_x_dqn_tpu.config import HISTORY_NETWORKS, TORSO_NETWORKS, ApexConfig, load_config, network_kwargs
from ape_x_dqn_tpu.models import dueling, expert_torso, ling_hybrid, solar_open2
from ape_x_dqn_tpu.models.dueling import build_network
from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta as delta
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.utils import profiling

TORSO = dict(
    model_type="bailing_hybrid", hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_shared_experts=1, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, q_lora_rank=None, rope_theta=6000000, rope_interleave=True,
    short_conv_kernel_size=4, rms_norm_eps=1e-6, layer_group_size=3, num_hidden_layers=4,
    first_k_dense_replace=1, published=dict(num_hidden_layers=12, first_k_dense_replace=2),
    layers_held=[1, 2, 3, 4], no_kda_lora=True, kda_safe_gate=True, kda_lower_bound=-5,
    num_kv_heads_for_linear_attn=0, num_experts=4, router_outputs=16, experts_held=[4, 8],
    n_group=4, topk_group=2, norm_topk_prob=True, routed_scaling_factor=2.5,
    num_experts_per_tok=2, score_function="sigmoid", moe_router_enable_expert_bias=True,
    expert_swiglu_limit_list=[0] * 10 + [4, 4], share_expert_swiglu_limit_list=[0] * 11 + [5],
    kda_chunk_size=16, channels=[8, 8, 8], hidden=32, expert_bias_update_rate=0.05,
)
# what the benchmark's driver adds to the torso's keys for its reference
CFG = dict(TORSO, obs_shape=[44, 60, 5], num_actions=6, batch_size=4, optimizer="rmsprop",
           learning_rate=6.25e-5, rmsprop_decay=0.95, rmsprop_eps=1.5e-7, max_grad_norm=40.0,
           loss="squared")


def small_net(compute=jnp.float32, **over):
    return build_network("ling_hybrid", 6, torso=dict(TORSO, **over), channels=(8, 8, 8),
                         hidden=32, compute_dtype=compute)


def obs(key, rows=2, shape=(44, 60, 5)):   # 5 frames of 2 x 4 positions: 40 tokens
    return jax.random.randint(key, (rows, *shape), 0, 256).astype(jnp.uint8)


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
    length that is no multiple of a block (700 = 5 query blocks of 128 and a
    rest of 60, one key block of 512 and a rest of 188): the output and the
    gradients of both query parts, the keys, the shared key (summed over the
    heads) and the values against autodiff of plain attention."""
    args, cot = _operands(700)
    got, pull = jax.vjp(lambda *a: blocked.blocked_attention(a[0], a[1], a[2], None, a[3], a[4]), *args)
    want, pull_plain = jax.vjp(_plain, *args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for name, a, b in zip(("q", "k", "v", "q_shared", "k_shared"), pull(cot), pull_plain(cot)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4, err_msg=name)


def test_a_group_of_query_heads_shares_the_shared_key_too():
    """The operand is the kernels', not latent attention's alone: two query
    heads a key-value head, a shared part of 32 beside heads of 64."""
    (q, k, v, qs, ks), cot = _operands(200, heads=4, group=2, width=64, shared=32)
    rep = lambda x: jnp.repeat(x, 2, axis=1)  # noqa: E731
    got, pull = jax.vjp(lambda *a: blocked.blocked_attention(a[0], a[1], a[2], None, a[3], a[4]),
                        q, k, v, qs, ks)
    want, pull_plain = jax.vjp(lambda q, k, v, qs, ks: _plain(q, rep(k), rep(v), qs, ks),
                               q, k, v, qs, ks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for a, b in zip(pull(cot), pull_plain(cot)):
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

    sizes = dict(rows=1, heads=2, tokens=200)
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
        want, pull = jax.vjp(literal, q, k, v, g, beta)
        got, pull_chunked = jax.vjp(lambda *z: delta(*z, 64), q, k, v, g, beta)
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
        for name, a, b in zip(("q", "k", "v", "g", "beta"), pull_chunked(cot), pull(cot)):
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
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
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
    params = whole.init(jax.random.PRNGKey(1), u)["params"]
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
    sp = shared.init(jax.random.PRNGKey(4), u)
    whole = expert_torso.ExpertShare(base, jnp.float32, jnp.float32)
    params = whole.init(jax.random.PRNGKey(5), u)["params"]
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


# ----------------------------------------------------------------- the network

def test_the_network_has_the_issues_structure():
    net = small_net()
    x = obs(jax.random.PRNGKey(2))
    assert net.tokens_of(x.shape) == 40
    params = net.init(jax.random.PRNGKey(3), x)["params"]
    assert set(params) >= {"layer_0", "layer_1", "layers_2_3", "w_tok", "final_norm"}
    assert set(params["layer_0"]) == {"operator_norm", "ffn_norm", "linear_attention", "dense"}
    assert params["layer_0"]["dense"]["w1"].shape == (64, 128)      # the leading dense layer
    assert set(params["layer_1"]) == {"operator_norm", "ffn_norm", "latent_attention", "moe",
                                      "shared_expert"}
    assert {k: v.shape for k, v in params["layer_1"]["latent_attention"].items()} == {
        "w_q": (64, 4 * 24), "w_dkv": (64, 24 + 8), "kv_norm": (24,), "w_ukv": (24, 4 * 32),
        "w_g": (64, 4), "w_o": (64, 64)}
    assert params["layers_2_3"]["moe"]["router"].shape == (2, 64, 16)
    assert params["layers_2_3"]["moe"]["w13"].shape == (2, 4, 64, 64)
    assert params["layers_2_3"]["shared_expert"]["w1"].shape == (2, 64, 32)
    spec = net.spec
    assert spec.layers == (("linear_attention", "dense"), ("latent_attention", "moe"),
                           ("linear_attention", "moe"), ("linear_attention", "moe"))
    assert (spec.router_outputs, spec.experts_held, spec.num_experts_per_tok, spec.score_function,
            spec.use_expert_bias, spec.shared_expert_intermediate_size, spec.routed_scaling_factor,
            spec.router_groups, spec.router_groups_kept, spec.norm_eps, spec.frame_history) == (
                16, (4, 8), 2, "sigmoid", True, 32, 2.5, 4, 2, 1e-6, True)
    m = spec.arg("latent")
    assert (m.heads, m.kv_rank, m.nope, m.rope, m.v, m.theta) == (4, 24, 16, 8, 16, 6e6)
    assert ling_hybrid.layer_types(TORSO) == (["linear_attention"] * 2 + ["latent_attention"]) * 4
    out, sown = net.apply({"params": params}, x, mutable=["routing"])
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2])))
    routing = net.routing_metrics(sown)
    assert float(routing["held_pairs"]) > 0 and 0.0 < float(routing["groups_kept_hold_share"]) < 1.0
    # a non-zero swiglu limit on a held layer raises; on a layer not held it does not
    for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        with pytest.raises(ValueError, match="no clamp"):
            ling_hybrid.spec_from_config(dict(TORSO, **{name: [0, 0, 0, 4] + [0] * 8}))
    ling_hybrid.spec_from_config(dict(TORSO, expert_swiglu_limit_list=[4] + [0] * 11))
    for bad in (dict(no_kda_lora=False), dict(kda_safe_gate=False), dict(q_lora_rank=128),
                dict(num_kv_heads_for_linear_attn=2), dict(score_function="softmax"),
                dict(layer_types=["linear_attention"] * 12),
                dict(heads_held=[0, 2], published=dict(TORSO["published"], num_attention_heads=8))):
        with pytest.raises(ValueError):
            ling_hybrid.spec_from_config(dict(TORSO, **bad))


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


def test_the_other_torsos_sow_and_count_as_they_did():
    """One group is no group: the four older families' specs carry the
    default, sow ``load`` alone and count no ``groups_kept_hold_share``."""
    from tests.test_solar_open2 import small_net as solar_net

    net = solar_net()
    assert (net.spec.router_groups, net.spec.router_groups_kept) == (1, 1)
    x = obs(jax.random.PRNGKey(2))
    params = net.init(jax.random.PRNGKey(3), x)
    _, sown = net.apply(params, x, mutable=["routing"])
    names = {p[-2].key for p, _ in jax.tree_util.tree_leaves_with_path(sown["routing"])}
    assert names == {"load"} and set(net.routing_metrics(sown)) == {
        "held_pairs", "load_max", "load_mean", "rows_walked"}
    assert net.attention_metrics(x.shape).keys() == {"pairs_in_mask_full", "pairs_computed_full",
                                                     "blocks_visited_full", "blocks_total_full"}


def test_the_latent_kernels_are_scoped_inside_the_mixer():
    assert profiling.PARTS[-1] == "attn_latent"
    net = small_net()
    x = obs(jax.random.PRNGKey(8))
    params = net.init(jax.random.PRNGKey(9), x)
    text = jax.jit(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2))).lower(params).as_text(
        debug_info=True)
    for part in ("delta_scan", "attn_latent", "mixer", "router", "experts", "shared_expert",
                 "dense_ffn", "stem", "head"):
        assert f"torso:{part}" in text, part
    assert "torso:mixer/latent_attention/torso:attn_latent" in text
    assert "torso:mixer/linear_attention/" in text and "transpose(" in text
    for part in ("ssm_scan", "attn_full", "attn_window"):
        assert f"torso:{part}" not in text, part


def test_config_carries_the_torso_and_the_committed_file_is_the_cells():
    assert TORSO_NETWORKS[-1] == "ling_hybrid" and HISTORY_NETWORKS[-1] == "ling_hybrid"
    assert tuple(dueling.TORSO_KINDS) == TORSO_NETWORKS
    cfg = ApexConfig()
    cfg.network = "ling_hybrid"
    cfg.torso = dict(TORSO)
    with pytest.raises(ValueError, match="frame_stack"):
        cfg.validate()                      # a history needs more than one frame
    cfg.env.frame_stack = 5
    kw = network_kwargs(cfg.validate())
    assert kw["channels"] == (8, 8, 8) and kw["hidden"] == 32
    assert build_network(cfg.network, 6, **kw).spec.num_held == 4
    committed = load_config(os.path.join(ROOT, "configs", "config10_ling3_q_l7.json"))
    spec = build_network(committed.network, 18, **network_kwargs(committed)).spec
    assert committed.env.frame_stack == 32 and spec.frame_history
    assert committed.learner.replay_sample_size == 8 and committed.learner.steps_per_call == 1
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "ling3_q_l7.json")))
    assert spec == ling_hybrid.spec_from_config(cell)
    assert [kinds for kinds in spec.layers] == [("linear_attention", "dense")] + [
        ("linear_attention", "moe")] * 3 + [("latent_attention", "moe")] + [("linear_attention", "moe")] * 2
    m, n = spec.arg("linear"), spec.arg("latent")
    assert (spec.hidden_size, spec.intermediate_size, spec.moe_intermediate_size,
            spec.shared_expert_intermediate_size, m.heads, m.head_dim, m.conv, m.gate_rank, m.chunk,
            m.gate, m.gate_bound) == (2560, 6144, 768, 768, 32, 128, 4, None, 64, "bounded", -5.0)
    assert (n.heads, n.kv_rank, n.nope, n.rope, n.v, n.theta) == (32, 512, 128, 64, 128, 6e6)
    assert (spec.router_outputs, spec.num_experts_per_tok, spec.router_groups, spec.router_groups_kept,
            spec.heads_held, spec.routed_scaling_factor) == (512, 8, 8, 4, (0, 8), 2.5)
    lo, hi = spec.experts_held
    assert lo == 0 and hi in (8, 16) and hi <= 64                  # all in router group 0
    assert expert_torso.tile_rows(12544 * 8, hi, 512) == {16: 4608, 8: 2560}[hi]


def test_the_trainers_loop_runs_the_network():
    """``runtime/single_process.py``'s loop, a few learner steps, through
    ``build_components``: the normal path builds and trains the network on
    histories of ``env.frame_stack`` frames."""
    from ape_x_dqn_tpu.runtime import SingleProcessDriver

    cfg = ApexConfig()
    cfg.env.name = "fake-atari"
    cfg.env.frame_stack = 4
    cfg.network = "ling_hybrid"
    cfg.torso = dict(TORSO)
    cfg.actor.num_actors = 2
    cfg.actor.flush_every = 8
    cfg.learner.min_replay_mem_size = 32
    cfg.learner.replay_sample_size = 4
    cfg.replay.capacity = 256
    driver = SingleProcessDriver(cfg.validate())
    results = driver.run(learner_steps=3)
    assert driver.learner_step >= 3
    learned = [r.loss for r in results if r.learner_step > 0]
    assert len(learned) >= 3 and all(np.isfinite(v) for v in learned), learned
    assert type(driver.network).__name__ == "LingHybridQ"

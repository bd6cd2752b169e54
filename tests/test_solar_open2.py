"""The network kind ``solar_open2`` in the program: the chunked delta-rule scan
and its hand-walked backward pass against the literal recurrence (padding,
chunk sizes, strong decay, a negative eigenvalue), the two mixers' shares of
heads and the expert layer's shares against the uncut layers, the network
against ``benchmark/reference/solar2_q.py`` on seeded weights, the float32
leaves, what the other three torsos keep, the configuration path and the
trainer's loop, all at small widths on the CPU (the attention kernels in
Pallas' interpreter)."""
import dataclasses
import json
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
from ape_x_dqn_tpu.models import dueling, expert_torso, solar_open2
from ape_x_dqn_tpu.models.dueling import build_network
from ape_x_dqn_tpu.ops import chunked_delta
from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta as delta
from ape_x_dqn_tpu.ops.chunked_scan import chunks_of
from ape_x_dqn_tpu.utils import profiling

TORSO = dict(
    model_type="solar_open2", hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16, num_heads=8, num_kv_heads=None),
    rms_norm_eps=1e-5, num_hidden_layers=4, gqa_layers=[0, 4, 8], gqa_interval=3,
    first_k_dense_replace=0, use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, n_routed_experts=4, router_outputs=8, experts_held=[2, 6],
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1, num_experts_per_tok=2,
    kda_chunk_size=16, channels=[8, 8, 8], hidden=32, expert_bias_update_rate=0.05,
)
# what the benchmark's driver adds to the torso's keys for its reference
CFG = dict(TORSO, obs_shape=[44, 60, 5], num_actions=6, batch_size=4, optimizer="rmsprop",
           learning_rate=6.25e-5, rmsprop_decay=0.95, rmsprop_eps=1.5e-7, max_grad_norm=40.0,
           loss="squared")


def small_net(compute=jnp.float32, **over):
    return build_network("solar_open2", 6, torso=dict(TORSO, **over), channels=(8, 8, 8),
                         hidden=32, compute_dtype=compute)


def obs(key, rows=2, shape=(44, 60, 5)):   # 5 frames of 2 x 4 positions: 40 tokens
    return jax.random.randint(key, (rows, *shape), 0, 256).astype(jnp.uint8)


def literal(q, k, v, g, beta):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t =
    S_t^T q_t``, a token a step, heads second: [B, H, T, .]."""
    from reference import solar2_q as ref

    turn = lambda x: jnp.moveaxis(x, 1, 2)  # noqa: E731
    return turn(ref.recurrence(*(turn(x) for x in (q, k, v, g, beta))))


def scan_inputs(tokens, rows=2, heads=3, kw=16, vw=8, decay=0.1, beta_scale=2.0, seed=0):
    """Unit keys with a common direction (a SiLU's outputs have one), queries
    over the root of the width, log decays in ``[-decay, 0]``."""
    ks = jax.random.split(jax.random.PRNGKey(seed + tokens), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, heads, tokens, kw))) / np.sqrt(kw)
    k = unit(jax.random.normal(ks[1], (rows, heads, tokens, kw)) + 0.5)
    v = jax.random.normal(ks[2], (rows, heads, tokens, vw))
    g = -decay * jax.random.uniform(ks[3], (rows, heads, tokens, kw))
    beta = beta_scale * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, heads, tokens)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], v.shape)


def _agrees(tokens, chunk, atol=3e-5, gtol=2e-4, **kw):
    args, cot = scan_inputs(tokens, **kw)
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(literal, *args)
        got, pull_chunked = jax.vjp(lambda *z: delta(*z, chunk), *args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)
        for name, a, b in zip(("q", "k", "v", "g", "beta"), pull_chunked(cot), pull(cot)):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            assert bool(jnp.all(jnp.isfinite(a))), name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=gtol, rtol=gtol,
                                       err_msg=name)
    return args, got


@pytest.mark.parametrize("tokens,chunk", [
    (64, 32),    # a multiple of the chunk
    (40, 32),    # not one: the last chunk is padded
    (40, 64),    # one chunk, of the sequence's own length
    (64, 16),    # another chunk size, the same answer
    (50, 20),    # a chunk that is no multiple of the sub-block's 16 rows: sub-blocks of 10
])
def test_chunked_delta_is_the_literal_recurrence(tokens, chunk):
    """The output and, through the hand-walked backward pass, the gradient of
    every input, against autodiff of the recurrence stepped a token at a time."""
    assert chunks_of(tokens, chunk) == {(64, 32): (2, 64), (40, 32): (2, 64), (40, 64): (1, 40),
                                        (64, 16): (4, 64), (50, 20): (3, 60)}[tokens, chunk]
    assert chunked_delta.sub_rows(min(chunk, tokens)) == {32: 16, 40: 10, 16: 16, 20: 10}[
        min(chunk, tokens)]
    _agrees(tokens, chunk)


def test_two_chunk_sizes_give_one_answer():
    args, _ = scan_inputs(64)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(delta(*args, 16)), np.asarray(delta(*args, 64)),
                                   atol=3e-5)


def test_a_chunk_whose_decay_passes_e_to_the_minus_100_stays_finite_and_right():
    """Log decays down to -5 a token: a chunk of 64 sums to about -160 a
    channel (a sub-block of 16 to -40), past where ``exp(-G)`` alone overflows
    float32; every decay formed is of a difference ``G_i - G_j <= 0``."""
    args, got = _agrees(128, 64, decay=5.0, gtol=5e-4)
    run = jnp.cumsum(args[3][:, :, :64], axis=2)
    assert float(jnp.min(run[:, :, -1])) < -100.0 and float(jnp.mean(run[:, :, -1])) < -120.0
    assert not bool(jnp.isfinite(jnp.exp(-run[:, :, -1])).all())       # the naive factor: inf
    assert bool(jnp.all(jnp.isfinite(got)))


def test_a_negative_eigenvalue_agrees():
    """``beta`` between 1 and 2: ``I - beta k k^T`` reflects along the key."""
    args, _ = scan_inputs(48)
    q, k, v, g, beta = args
    beta = 1.0 + 0.99 * jax.nn.sigmoid(beta)
    assert float(jnp.min(beta)) > 1.0
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(delta(q, k, v, g, beta, 16)),
                                   np.asarray(literal(q, k, v, g, beta)), atol=5e-5)


def test_padded_rows_write_nothing_and_get_no_gradient():
    """40 tokens in chunks of 32: the 24 rows past the end carry ``beta = 0``
    and ``g = 0``; the sequence's own rows read as the first 40 of a longer
    one, and a sequence already cut scans as the uncut one."""
    args, _ = scan_inputs(64)
    with jax.default_matmul_precision("highest"):
        whole = delta(*args, 32)
        short = delta(*(x[:, :, :40] for x in args), 32)
    np.testing.assert_allclose(np.asarray(short), np.asarray(whole[:, :, :40]), atol=2e-5)


def test_the_backward_pass_keeps_the_chunks_incoming_states_alone():
    """The residuals of the ``custom_vjp``: the five inputs, cut, and ``[chunks,
    B, H, K, V]`` float32; nothing of ``[chunk, chunk]`` a head."""
    args, _ = scan_inputs(40)
    cut = [jnp.zeros((3, 2, 3, 16, *x.shape[3:]), x.dtype) for x in args]
    saved = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda *z: jax.vjp(chunked_delta.delta_chunks, *z)[1], *cut))
    shapes = sorted(tuple(s.shape) for s in saved)
    assert (3, 2, 3, 16, 8) in shapes                     # three chunks' incoming states [K, V]
    assert not any(s[-2:] == (16, 16) and len(s) == 5 and s != (3, 2, 3, 16, 16) for s in shapes)
    assert sum(int(np.prod(s)) for s in shapes) == sum(int(np.prod(c.shape)) for c in cut) + 3 * 2 * 3 * 16 * 8


@pytest.mark.parametrize("entry", [0.8, 1.8])
def test_the_inverse_is_made_by_blocks(entry):
    """``(I + A)^-1`` of a strictly lower triangular ``A`` whose entries are all
    ``entry`` (keys that share a direction, ``beta`` up to 2): the plain
    doubling product over 64 rows cancels powers of 1e9 and more in float32;
    by blocks of 8 merged two and two it is right to 1e-4 of its largest entry."""
    a = entry * jnp.tril(jnp.ones((64, 64)), -1)
    want = np.linalg.inv(np.eye(64) + np.asarray(a, np.float64))
    got = np.asarray(chunked_delta._unit_lower_inverse(a[None])[0])
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
    assert np.allclose(np.triu(got, 1), 0) and np.allclose(np.diag(got), 1)
    # a count of blocks that is not a power of two: five of 8, three of 20 (two of 10 merged)
    for rows in (40, 60):
        got = np.asarray(chunked_delta._unit_lower_inverse(a[None, :rows, :rows])[0])
        np.testing.assert_allclose(got, want[:rows, :rows], atol=1e-4 * np.abs(want).max())


# ------------------------------------------------------------------ the shares

def _linear_share(params, lo, hi, hd):
    """The parameters of the delta-rule layer's share that holds heads ``[lo,
    hi)``: by columns, by rows, a head each, or alike on every chip."""
    cols = slice(lo * hd, hi * hd)
    out = {}
    for name, w in params.items():
        if name in ("w_q", "w_k", "w_v", "w_f2", "w_g2"):
            out[name] = w[:, cols]
        elif name in ("conv_q", "conv_k", "conv_v", "dt_bias", "b_g", "w_o"):
            out[name] = w[cols]
        elif name in ("A_log", "w_b"):
            out[name] = w[..., lo:hi]
        else:                       # w_f1, w_g1, the head norm
            out[name] = w
    return out


def test_the_four_head_shares_add_up_to_the_uncut_mixers():
    """Eight heads on four tensor-parallel shares of two: the shares' outputs
    (each the held heads' part of ``W_o``'s sum) add up to the uncut layer's,
    for both mixers; the softmax layer's share holds the key-value head its
    two query heads... here four query heads a key-value head, so shares of
    four."""
    spec = solar_open2.spec_from_config(TORSO)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    # the linear layer: shares of two heads
    whole = solar_open2.DeltaAttention(spec, "linear_attention", jnp.float32, jnp.float32)
    params = whole.init(jax.random.PRNGKey(1), u)["params"]
    want = whole.apply({"params": params}, u)
    total = 0.0
    for lo in range(0, 8, 2):
        share = dataclasses.replace(spec, heads_held=(lo, lo + 2))
        part = _linear_share(params, lo, lo + 2, 16)
        layer = solar_open2.DeltaAttention(share, "linear_attention", jnp.float32, jnp.float32)
        got = jax.eval_shape(layer.init, jax.random.PRNGKey(1), u)["params"]
        assert {k: v.shape for k, v in got.items()} == {k: v.shape for k, v in part.items()}
        total = total + layer.apply({"params": part}, u)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    # the softmax layer: 8 query heads on 2 key-value heads, shares of four
    whole = solar_open2.GatedNopeAttention(spec, "full_attention", jnp.float32, jnp.float32)
    params = whole.init(jax.random.PRNGKey(2), u)["params"]
    want = whole.apply({"params": params}, u)
    total = 0.0
    for lo in (0, 4):
        share = dataclasses.replace(spec, heads_held=(lo, lo + 4))
        kv = lo // 4
        part = {"w_q": params["w_q"][:, lo * 16:(lo + 4) * 16], "w_g": params["w_g"][:, lo * 16:(lo + 4) * 16],
                "w_k": params["w_k"][:, kv * 16:(kv + 1) * 16], "w_v": params["w_v"][:, kv * 16:(kv + 1) * 16],
                "w_o": params["w_o"][lo * 16:(lo + 4) * 16]}
        layer = solar_open2.GatedNopeAttention(share, "full_attention", jnp.float32, jnp.float32)
        assert layer.held(share) == (4, 1)
        total = total + layer.apply({"params": part}, u)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="cuts a group"):
        solar_open2.GatedNopeAttention.held(dataclasses.replace(spec, heads_held=(2, 6)))
    with pytest.raises(ValueError, match="no range"):
        solar_open2.held_heads(dataclasses.replace(spec, heads_held=(4, 12)), 8)


def test_the_expert_shares_add_up_to_the_uncut_expert_layer():
    """Eight routed experts on four expert-parallel shares of two: the held
    experts' parts add up, with the shared expert counted once, to the uncut
    layer's (every expert held)."""
    base = solar_open2.spec_from_config(dict(TORSO, n_routed_experts=8, experts_held=[0, 8]))
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 64))
    shared = expert_torso.SwiGLU(base.shared_expert_intermediate_size, jnp.float32, jnp.float32)
    sp = shared.init(jax.random.PRNGKey(4), u)
    whole = expert_torso.ExpertShare(base, jnp.float32, jnp.float32)
    params = whole.init(jax.random.PRNGKey(5), u)["params"]
    assert params["router"].shape == (64, 8) and params["expert_bias"].shape == (8,)
    want = whole.apply({"params": params}, u, mutable=["routing"])[0] + shared.apply(sp, u)
    total = shared.apply(sp, u)                                    # counted once
    for lo in range(0, 8, 2):
        share = dataclasses.replace(base, experts_held=(lo, lo + 2))
        part = dict(params, w13=params["w13"][lo:lo + 2], w2=params["w2"][lo:lo + 2])
        total = total + expert_torso.ExpertShare(share, jnp.float32, jnp.float32).apply(
            {"params": part}, u, mutable=["routing"])[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


# ----------------------------------------------------------------- the network

def test_the_network_has_the_issues_structure():
    net = small_net()
    x = obs(jax.random.PRNGKey(2))
    assert net.tokens_of(x.shape) == 40
    params = net.init(jax.random.PRNGKey(3), x)["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layer_0", "layers_1_3", "w_tok", "final_norm"}
    linear = params["layers_1_3"]["linear_attention"]
    assert {k: v.shape[1:] for k, v in linear.items()} == {
        "w_q": (64, 128), "w_k": (64, 128), "w_v": (64, 128), "conv_q": (128, 4),
        "conv_k": (128, 4), "conv_v": (128, 4), "w_f1": (64, 16), "w_f2": (16, 128),
        "A_log": (8,), "dt_bias": (128,), "w_b": (64, 8), "w_g1": (64, 16), "w_g2": (16, 128),
        "b_g": (128,), "norm": (16,), "w_o": (128, 64)}
    assert {k: v.shape for k, v in params["layer_0"]["full_attention"].items()} == {
        "w_q": (64, 128), "w_k": (64, 32), "w_v": (64, 32), "w_g": (64, 128), "w_o": (128, 64)}
    for run in ("layer_0", "layers_1_3"):                        # every layer routes
        assert set(params[run]) == {"operator_norm", "ffn_norm", "moe", "shared_expert",
                                    "full_attention" if run == "layer_0" else "linear_attention"}
        assert params[run]["moe"]["router"].shape[-2:] == (64, 8)
        assert params[run]["moe"]["w13"].shape[-3:] == (4, 64, 64)
        assert params[run]["shared_expert"]["w1"].shape[-2:] == (64, 32)
    a, dt = np.exp(np.asarray(linear["A_log"])), np.asarray(jax.nn.softplus(linear["dt_bias"]))
    assert (1 <= a).all() and (a <= 16).all() and (1e-3 <= dt).all() and (dt <= 1e-1 + 1e-6).all()
    out, sown = net.apply({"params": params}, x, mutable=["routing"])
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2])))
    assert float(net.routing_metrics(sown)["held_pairs"]) > 0
    spec = net.spec
    assert [op for op, _ in spec.layers] == ["full_attention"] + ["linear_attention"] * 3
    assert all(ffn == "moe" for _, ffn in spec.layers) and spec.frame_history
    assert (spec.router_outputs, spec.experts_held, spec.num_experts_per_tok, spec.score_function,
            spec.use_expert_bias, spec.shared_expert_intermediate_size, spec.heads_held) == (
                8, (2, 6), 2, "sigmoid", True, 32, None)
    m = spec.arg("linear")
    assert (m.heads, m.head_dim, m.conv, m.gate_rank, m.beta_scale, m.chunk) == (8, 16, 4, 16, 2.0, 16)
    assert dict(spec.mixers) == {"full_attention": solar_open2.GatedNopeAttention,
                                 "linear_attention": solar_open2.DeltaAttention}
    assert solar_open2.layer_types(TORSO) == ["full_attention"] + ["linear_attention"] * 3
    assert solar_open2.spec_from_config(dict(TORSO, kda_allow_neg_eigval=False)).arg(
        "linear").beta_scale == 1.0
    for bad in (dict(use_rope=True), dict(kda_use_full_proj=True), dict(first_k_dense_replace=1),
                dict(layer_types=["linear_attention"] * 4), dict(num_key_value_heads=3),
                dict(linear_attn_config=dict(TORSO["linear_attn_config"], num_heads=4))):
        with pytest.raises(ValueError):
            solar_open2.spec_from_config(dict(TORSO, **bad))


def test_a_cut_states_the_heads_it_holds():
    """The benchmark's way: the head counts are the held ones, ``published``
    keeps the model's, ``heads_held`` the range; all the heads is no cut."""
    cut = dict(TORSO, num_attention_heads=4, num_key_value_heads=1, heads_held=[4, 8],
               linear_attn_config=dict(TORSO["linear_attn_config"], num_heads=4),
               published=dict(num_hidden_layers=48, num_attention_heads=8, num_key_value_heads=2,
                              linear_attn_config=dict(num_heads=8)))
    spec = solar_open2.spec_from_config(cut)
    assert spec.heads_held == (4, 8) and spec.arg("num_attention_heads") == 8
    assert spec.arg("linear").heads == 8 and len(solar_open2.layer_types(cut)) == 48
    net = build_network("solar_open2", 6, torso=cut, channels=(8, 8, 8), hidden=32,
                        compute_dtype=jnp.float32)
    x = obs(jax.random.PRNGKey(2))
    params = net.init(jax.random.PRNGKey(3), x)["params"]
    assert params["layers_1_3"]["linear_attention"]["w_q"].shape == (3, 64, 64)
    assert params["layers_1_3"]["linear_attention"]["A_log"].shape == (3, 4)
    assert params["layer_0"]["full_attention"]["w_k"].shape == (64, 16)
    assert float(net.attention_metrics(x.shape)["blocks_total_full"]) == 2 * 4.0   # the held heads'
    with pytest.raises(ValueError, match="heads_held"):
        solar_open2.spec_from_config(dict(cut, heads_held=[0, 2]))
    # every head held is the uncut layer: the same tree, the same numbers
    whole, named = small_net(), small_net(heads_held=[0, 8])
    p = whole.init(jax.random.PRNGKey(3), x)
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(
        named.init(jax.random.PRNGKey(3), x))
    np.testing.assert_array_equal(np.asarray(whole.apply(p, x)[2]), np.asarray(named.apply(p, x)[2]))


def test_the_state_crosses_chunks_and_nothing_sees_the_future():
    spec = solar_open2.spec_from_config(TORSO)
    layer = solar_open2.DeltaAttention(spec, "linear_attention", jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    params = layer.init(jax.random.PRNGKey(1), u)
    base = layer.apply(params, u)
    moved = lambda out, t: float(jnp.max(jnp.abs(out[:, t] - base[:, t])))  # noqa: E731
    assert moved(layer.apply(params, u.at[:, 0].add(1.0)), 39) > 1e-6     # two chunk boundaries on
    later = layer.apply(params, u.at[:, 20:].add(1.0))
    assert moved(later, 19) == 0.0 and moved(later, 20) > 1e-4


def test_the_network_is_the_reference():
    """Forward in float32 (1e-4 of |Q|: sums in another order, the scan in
    chunks against a token a step) and at the stated precision; the gradients
    of sum(Q^2) leaf by leaf, 1e-3 of each leaf's norm; both mechanism flags
    move Q."""
    from reference import solar2_q as ref

    weights = ref.make_weights(jax.random.PRNGKey(11), CFG)
    x = obs(jax.random.PRNGKey(5), rows=4)
    with jax.default_matmul_precision("highest"):
        want, loads = ref.forward(weights, x, CFG)
        assert loads.shape == (4, 8) and float(jnp.sum(loads)) == 4 * 4 * 40 * 2
        scale = float(jnp.std(want)) + float(jnp.mean(jnp.abs(want)))
        for compute, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 0.5)):
            got = small_net(compute).apply(ref.to_program_params(weights, CFG), x)[2]
            assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, compute
        for flag in ("reference_resets_state", "reference_drops_delta"):
            other, _ = ref.forward(weights, x, dict(CFG, **{flag: True}))
            assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * scale, flag
        net = small_net()
        wanted = jax.grad(lambda w: jnp.sum(ref.forward(w, x, CFG)[0] ** 2))(weights)
        got = ref.from_program_params(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2))(
            ref.to_program_params(weights, CFG)), CFG)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(wanted)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * float(jnp.linalg.norm(b)) + 1e-7, name
        assert float(jnp.linalg.norm(b)) > 0 or "expert_bias" in name, name


def _batch(x):
    from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch

    n = x.shape[0]
    return PrioritizedBatch(
        transition=NStepTransition(obs=x, action=jnp.arange(n) % 6, reward=jnp.ones(n),
                                   discount=jnp.full((n,), 0.9), next_obs=x[::-1]),
        indices=jnp.arange(n), is_weights=jnp.linspace(0.4, 1.0, n))


def test_one_learner_step_is_the_references_and_counts_the_delta_rule():
    """Loss, priorities and the parameters after one RMSProp step of the
    program's train step, float32 compute, against ``learner_step`` (the
    balancing rule's move of the bias among them); the step's counters."""
    from ape_x_dqn_tpu.learner.train_step import StepMetrics, build_train_step, make_optimizer
    from ape_x_dqn_tpu.types import TrainState
    from reference import solar2_q as ref

    weights = ref.make_weights(jax.random.PRNGKey(12), CFG)
    k = jax.random.PRNGKey(21)
    target = jax.tree_util.tree_map(
        lambda w: w + 0.05 * jnp.std(w) * jax.random.normal(k, w.shape), weights)
    x = obs(jax.random.fold_in(k, 1), rows=4)
    batch = _batch(x)
    net = small_net()
    opt = make_optimizer("rmsprop", learning_rate=CFG["learning_rate"], rmsprop_decay=0.95,
                         rmsprop_eps=1.5e-7, max_grad_norm=40.0, second_moment_dtype=jnp.float32)
    own = lambda t: jax.tree_util.tree_map(lambda v: jnp.array(v, copy=True), t)  # noqa: E731
    params = own(ref.to_program_params(weights, CFG))
    nu0 = 1e-4
    opt_state = jax.tree_util.tree_map_with_path(
        lambda path, v: jnp.full_like(v, nu0) if any("nu" in str(p) for p in path) else v,
        opt.init(params))
    state = TrainState(params=params, target_params=own(ref.to_program_params(target, CFG)),
                       opt_state=opt_state, step=jnp.zeros((), jnp.int32), rng=jax.random.PRNGKey(0))
    step = build_train_step(net, opt, loss_kind="squared", sync_in_step=False, jit=True)
    t = batch.transition
    with jax.default_matmul_precision("highest"):
        new_state, metrics = step(state, batch)
        want_w, _, _, want_prio, want_loss = ref.learner_step(
            weights, target, jax.tree_util.tree_map(lambda w: jnp.full(w.shape, nu0), weights),
            dict(obs=t.obs, next_obs=t.next_obs, action=t.action, reward=t.reward,
                 discount=t.discount, is_weights=batch.is_weights), CFG)
    assert float(metrics.loss) == pytest.approx(float(want_loss), rel=1e-4)
    np.testing.assert_allclose(np.asarray(metrics.priorities), np.asarray(want_prio), rtol=2e-4)
    got_w = ref.from_program_params(new_state.params, CFG)
    num = den = 0.0
    for a, b, old in zip(*(jax.tree_util.tree_leaves(tree) for tree in (got_w, want_w, weights))):
        num += float(jnp.sum(jnp.square((a - old) - (b - old))))
        den += float(jnp.sum(jnp.square(b - old)))
    assert den > 0 and np.sqrt(num / den) < 2e-3
    for i in range(4):                                   # the balancing rule moved every layer's bias
        moved = got_w[f"layer_{i}"]["expert_bias"] - weights[f"layer_{i}"]["expert_bias"]
        assert float(jnp.max(jnp.abs(moved))) > 1e-3
        np.testing.assert_allclose(np.asarray(got_w[f"layer_{i}"]["expert_bias"]),
                                   np.asarray(want_w[f"layer_{i}"]["expert_bias"]), atol=1e-6)
    # 40 tokens in chunks of 16: 3 chunks, 48 tokens walked, three layers, 4 rows, 3 forwards
    assert {k: float(v) for k, v in metrics.delta.items()} == {
        "chunks": 3 * 3 * 4 * 3.0, "tokens_padded": 3 * 3 * 4 * 48.0, "tokens": 3 * 3 * 4 * 40.0}
    assert float(metrics.attention["pairs_in_mask_full"]) == 3 * 4 * (40 * 41 // 2)
    assert float(metrics.routing["held_pairs"]) > 0 and metrics.scan is None
    assert net.delta_metrics(x.shape) == {"chunks": 36.0, "tokens_padded": 576.0, "tokens": 480.0}
    assert net.scan_metrics(x.shape) is None
    assert StepMetrics(loss=0, mean_abs_td=0, max_abs_td=0, priorities=0, mean_q=0).delta is None


def test_a_lower_target_keeps_the_decays_float32():
    from ape_x_dqn_tpu.learner.train_step import init_train_state, make_optimizer

    net = small_net(jnp.bfloat16)
    assert net.float32_leaves == ("router", "expert_bias", "A_log", "dt_bias")
    state = init_train_state(net, make_optimizer("rmsprop", learning_rate=1e-4),
                             jax.random.PRNGKey(0), obs(jax.random.PRNGKey(1), rows=1),
                             target_dtype=jnp.bfloat16)
    kept = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.target_params):
        name = path[-1].key
        if name in ("A_log", "dt_bias", "router", "expert_bias"):
            kept.add(name)
            assert leaf.dtype == jnp.float32, jax.tree_util.keystr(path)
        else:
            assert leaf.dtype == jnp.bfloat16, jax.tree_util.keystr(path)
    assert kept == {"A_log", "dt_bias", "router", "expert_bias"}


def test_the_other_torsos_hold_every_head_as_they_did():
    """``heads_held`` defaults to every head and only a family whose mixers
    divide may state one: the three older families' specs carry none, their
    trees and outputs are what they were (their own tests hold the numbers),
    and a share of heads on them is refused."""
    from tests.test_granite_hybrid import TORSO as GRANITE
    from tests.test_granite_hybrid import LFM2
    from tests.test_laguna_moe import TORSO as LAGUNA

    nets = {"lfm2_moe": build_network("lfm2_moe", 6, torso=LFM2, compute_dtype=jnp.float32),
            "laguna_moe": build_network("laguna_moe", 6, torso=dict(LAGUNA), channels=(8, 8, 8),
                                        hidden=32, compute_dtype=jnp.float32),
            "granite_hybrid": build_network("granite_hybrid", 6, torso=dict(GRANITE),
                                            channels=(8, 8, 8), hidden=32, compute_dtype=jnp.float32)}
    for kind, net in nets.items():
        assert net.spec.heads_held is None and net.delta_metrics((2, 52, 52, 4)) is None, kind
        with pytest.raises(ValueError, match="hold every head"):
            dataclasses.replace(net.spec, heads_held=(0, 2))
        assert "heads_held" not in str(jax.tree_util.tree_structure(
            jax.eval_shape(net.init, jax.random.PRNGKey(0), obs(jax.random.PRNGKey(1)))))
    assert not hasattr(nets["laguna_moe"].spec.mixers[0][1], "divides_heads")
    assert nets["granite_hybrid"].scan_metrics((2, 44, 60, 5)) is not None


def test_the_delta_scan_is_scoped_inside_the_mixer():
    assert profiling.PARTS[9:11] == ("ssm_scan", "delta_scan")
    net = small_net()
    x = obs(jax.random.PRNGKey(8))
    params = net.init(jax.random.PRNGKey(9), x)
    text = jax.jit(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2))).lower(params).as_text(
        debug_info=True)
    for part in ("delta_scan", "attn_full", "mixer", "router", "experts", "shared_expert",
                 "stem", "head"):
        assert f"torso:{part}" in text, part
    assert "torso:mixer/linear_attention/" in text and "torso:delta_scan" in text
    assert "torso:mixer/full_attention/torso:attn_full" in text
    assert "transpose(" in text and text.count("torso:delta_scan") > 10      # the backward walk too
    for part in ("ssm_scan", "dense_ffn", "attn_window"):
        assert f"torso:{part}" not in text, part
    parts = profiling.hlo_parts(jax.jit(lambda p: net.apply(p, x)[2]).lower(params).compile().as_text())
    assert "delta_scan" in set(parts.values())


def test_config_carries_the_torso_and_the_committed_file_is_the_cells():
    assert TORSO_NETWORKS[3] == "solar_open2" and HISTORY_NETWORKS[2] == "solar_open2"
    assert tuple(dueling.TORSO_KINDS) == TORSO_NETWORKS
    cfg = ApexConfig()
    cfg.network = "solar_open2"
    cfg.torso = dict(TORSO)
    with pytest.raises(ValueError, match="frame_stack"):
        cfg.validate()                      # a history needs more than one frame
    cfg.env.frame_stack = 5
    kw = network_kwargs(cfg.validate())
    assert kw["channels"] == (8, 8, 8) and kw["hidden"] == 32
    assert build_network(cfg.network, 6, **kw).spec.num_held == 4
    committed = load_config(os.path.join(ROOT, "configs", "config9_solar2_q_ep40.json"))
    spec = build_network(committed.network, 18, **network_kwargs(committed)).spec
    assert committed.env.frame_stack == 32 and spec.frame_history
    assert committed.learner.replay_sample_size == 8 and committed.learner.steps_per_call == 1
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "solar2_q_ep40.json")))
    assert spec == solar_open2.spec_from_config(cell)
    assert [op for op, _ in spec.layers] == ["full_attention"] + ["linear_attention"] * 3
    m = spec.arg("linear")
    assert (spec.hidden_size, spec.moe_intermediate_size, spec.shared_expert_intermediate_size,
            m.heads, m.head_dim, m.conv, m.gate_rank, m.chunk) == (4096, 1280, 1280, 64, 128, 4, 128, 64)
    assert (spec.arg("num_attention_heads"), spec.arg("num_key_value_heads"), spec.arg("head_dim"),
            spec.router_outputs, spec.num_experts_per_tok, spec.experts_held, spec.heads_held) == (
                64, 8, 128, 320, 8, (0, 8), (0, 16))
    assert solar_open2.GatedNopeAttention.held(spec) == (16, 2)
    assert expert_torso.tile_rows(12544 * 8, 8, 320) == 3584      # the walk's tile at 320 outputs


def test_the_trainers_loop_runs_the_network():
    """``runtime/single_process.py``'s loop, a few learner steps, through
    ``build_components``: the normal path builds and trains the network on
    histories of ``env.frame_stack`` frames."""
    from ape_x_dqn_tpu.runtime import SingleProcessDriver

    cfg = ApexConfig()
    cfg.env.name = "fake-atari"
    cfg.env.frame_stack = 4
    cfg.network = "solar_open2"
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
    assert type(driver.network).__name__ == "SolarOpen2Q"

"""The network kind ``solar_open2`` in the program: the chunked delta-rule scan
and its hand-walked backward pass against the literal recurrence (padding,
chunk sizes, strong decay, a negative eigenvalue), the two mixers' shares of
heads and the expert layer's shares against the uncut layers, a cut that states
the heads it holds, at small widths on the CPU (the attention kernels in
Pallas' interpreter); what every torso is held to (structure,
``benchmark/reference/solar2_q.py`` on seeded weights, the float32 leaves,
scopes, counters, the configuration path, the trainer's loop) is the
contract's, ``tests/torso_contract.py``, on this torso's row."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_torso, solar_open2
from ape_x_dqn_tpu.models.dueling import build_network
from ape_x_dqn_tpu.ops import chunked_delta
from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta as delta
from ape_x_dqn_tpu.ops.chunked_scan import chunks_of
from tests import torso_contract as contract
from tests.torso_contract import built, init_of, pulled  # noqa: F401 - built: the module's fixture

TORSO = contract.SOLAR


class TestContract(contract.of("solar_open2")):
    """The contract's cases on this torso (``tests/torso_contract.py``)."""


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
        want, wanted = pulled(literal)(cot, *args)
        got, gots = pulled(lambda *z: delta(*z, chunk))(cot, *args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)
        for name, a, b in zip(("q", "k", "v", "g", "beta"), gots, wanted):
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
    (150, 64),   # the cells' chunk: eight inverse blocks merged three times, four sub-blocks
])
def test_chunked_delta_is_the_literal_recurrence(tokens, chunk):
    """The output and, through the hand-walked backward pass, the gradient of
    every input, against autodiff of the recurrence stepped a token at a time."""
    assert chunks_of(tokens, chunk) == {(64, 32): (2, 64), (40, 32): (2, 64), (40, 64): (1, 40),
                                        (64, 16): (4, 64), (50, 20): (3, 60),
                                        (150, 64): (3, 192)}[tokens, chunk]
    assert chunked_delta.sub_rows(min(chunk, tokens)) == {32: 16, 40: 10, 16: 16, 20: 10, 64: 16}[
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
    inverse = jax.jit(chunked_delta._unit_lower_inverse_of)
    got = np.asarray(inverse(a[None])[0])
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
    assert np.allclose(np.triu(got, 1), 0) and np.allclose(np.diag(got), 1)
    # a count of blocks that is not a power of two: five of 8, three of 20 (two of 10 merged)
    for rows in (40, 60):
        got = np.asarray(inverse(a[None, :rows, :rows])[0])
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
    params = init_of(whole, jax.random.PRNGKey(1), u)["params"]
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
    params = init_of(whole, jax.random.PRNGKey(2), u)["params"]
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
    sp = init_of(shared, jax.random.PRNGKey(4), u)
    whole = expert_torso.ExpertShare(base, jnp.float32, jnp.float32)
    params = init_of(whole, jax.random.PRNGKey(5), u)["params"]
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

def test_a_cut_states_the_heads_it_holds(built):
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
    x = built.x
    params = jax.eval_shape(net.init, jax.random.PRNGKey(3), x)["params"]     # the shapes alone
    assert params["layers_1_3"]["linear_attention"]["w_q"].shape == (3, 64, 64)
    assert params["layers_1_3"]["linear_attention"]["A_log"].shape == (3, 4)
    assert params["layer_0"]["full_attention"]["w_k"].shape == (64, 16)
    assert float(net.attention_metrics(x.shape)["blocks_total_full"]) == 2 * 4.0   # the held heads'
    with pytest.raises(ValueError, match="heads_held"):
        solar_open2.spec_from_config(dict(cut, heads_held=[0, 2]))
    # every head held is the uncut layer: the same tree, the same numbers
    named, p = contract.network("solar_open2", heads_held=[0, 8]), built.params
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(
        jax.eval_shape(named.init, jax.random.PRNGKey(3), x))
    np.testing.assert_array_equal(np.asarray(built.apply()(p, x)[2]), np.asarray(jax.jit(named.apply)(p, x)[2]))


def test_the_state_crosses_chunks_and_nothing_sees_the_future():
    spec = solar_open2.spec_from_config(TORSO)
    layer = solar_open2.DeltaAttention(spec, "linear_attention", jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    params = init_of(layer, jax.random.PRNGKey(1), u)
    apply = jax.jit(layer.apply)
    base = apply(params, u)
    moved = lambda out, t: float(jnp.max(jnp.abs(out[:, t] - base[:, t])))  # noqa: E731
    assert moved(apply(params, u.at[:, 0].add(1.0)), 39) > 1e-6     # two chunk boundaries on
    later = apply(params, u.at[:, 20:].add(1.0))
    assert moved(later, 19) == 0.0 and moved(later, 20) > 1e-4

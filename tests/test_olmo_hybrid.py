"""The network kind ``olmo_hybrid`` in the program: the scalar-gate form of the
chunked delta-rule scan and its hand-walked backward pass against the literal
recurrence (keys and values of two widths, padding, chunk sizes, strong
decay, a negative eigenvalue) and against the per-channel form fed the same
decay on every channel; the post-norm block written out; the full layer's
norm over the whole width, which a norm a head fails; thirty heads through
the causal kernels; the ``scan_path`` span; at small widths on the CPU (the
attention kernels in Pallas' interpreter).  What every torso is held to
(structure, ``benchmark/reference/olmoh_q.py`` on seeded weights, the float32
leaves, scopes, counters, the configuration path, the trainer's loop) is the
contract's, ``tests/torso_contract.py``, on this torso's row."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_torso, olmo_hybrid
from ape_x_dqn_tpu.ops import chunked_delta
from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta as delta
from ape_x_dqn_tpu.ops.chunked_scan import chunks_of
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.utils import profiling
from tests import torso_contract as contract
from tests.torso_contract import built, init_of, pulled  # noqa: F401 - built: the module's fixture

TORSO = contract.OLMO


class TestContract(contract.of("olmo_hybrid")):
    """The contract's cases on this torso (``tests/torso_contract.py``)."""


def literal(q, k, v, g, beta):
    """``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T
    q_t``, a token a step (the reference's), heads second: [B, H, T, .], ``g`` [B, H, T]."""
    from reference import olmoh_q as ref

    turn = lambda x: jnp.moveaxis(x, 1, 2)  # noqa: E731
    return turn(ref.recurrence(*(turn(x) for x in (q, k, v, g, beta))))


def scan_inputs(tokens, rows=2, heads=3, kw=12, vw=24, decay=0.1, beta_scale=2.0, seed=0):
    """Unit keys with a common direction (a SiLU's outputs have one), queries
    over the root of the width, one log decay a head and token in ``[-decay,
    0]``; keys of ``kw`` and values of ``vw``, as 96 and 192."""
    ks = jax.random.split(jax.random.PRNGKey(seed + tokens), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, heads, tokens, kw))) / np.sqrt(kw)
    k = unit(jax.random.normal(ks[1], (rows, heads, tokens, kw)) + 0.5)
    v = jax.random.normal(ks[2], (rows, heads, tokens, vw))
    g = -decay * jax.random.uniform(ks[3], (rows, heads, tokens))
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
    (40, 16),    # not one: the last chunk is half padding, as 1,568 in chunks of 64
    (40, 64),    # one chunk, of the sequence's own length
    (37, 8),     # a prime count, one inverse block a chunk
    (50, 20),    # a chunk whose inverse blocks are of 5 rows
])
def test_the_scalar_gate_form_is_the_literal_recurrence(tokens, chunk):
    """The output and, through the hand-walked backward pass, the gradient of
    every input (the scalar ``g`` among them), against autodiff of the
    recurrence stepped a token at a time; keys of 12, values of 24."""
    assert chunks_of(tokens, chunk) == {(64, 32): (2, 64), (40, 16): (3, 48), (40, 64): (1, 40),
                                        (37, 8): (5, 40), (50, 20): (3, 60)}[tokens, chunk]
    _agrees(tokens, chunk)


@pytest.mark.parametrize("kw,vw", [(12, 24), (24, 12), (16, 16), (5, 7)])
def test_keys_and_values_of_two_widths(kw, vw):
    """The state is [K, V], whatever the two are: values wider than keys (the
    published 96 and 192), narrower, equal, and two odd widths."""
    args, got = _agrees(40, 16, kw=kw, vw=vw)
    assert got.shape == (2, 3, 40, vw)


@pytest.mark.parametrize("tokens,chunk", [(40, 16), (64, 64)])
def test_the_per_channel_form_fed_one_decay_a_head_gives_the_same(tokens, chunk):
    """``g`` broadcast over a head's key channels through the per-channel
    walk (sub-blocks of 16 rows, the decays inside the sums) against the
    scalar walk: the same recurrence, output and gradients."""
    (q, k, v, g, beta), cot = scan_inputs(tokens)
    wide = lambda g: jnp.broadcast_to(g[..., None], q.shape)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, wanted = pulled(lambda q, k, v, g, b: delta(q, k, v, wide(g), b, chunk))(
            cot, q, k, v, g, beta)
        got, gots = pulled(lambda *z: delta(*z, chunk))(cot, q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), gots, wanted):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4, err_msg=name)


def test_the_two_forms_are_told_apart_by_the_decays_rank_and_traced_apart():
    """A decay a head and token takes ``_chunk_scalar``, whose program names
    ``scalar_gate`` inside ``torso:delta_scan`` and makes no sub-block; a
    decay a key channel takes ``_chunk``, whose program does not name it."""
    (q, k, v, g, beta), _ = scan_inputs(40)
    scalar = jax.jit(lambda *z: delta(*z, 16)).lower(q, k, v, g, beta).as_text(debug_info=True)
    wide = jax.jit(lambda *z: delta(*z, 16)).lower(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta).as_text(debug_info=True)
    assert "scalar_gate" in scalar and "scalar_gate" not in wide
    assert "torso:delta_scan" in wide and "torso:delta_scan" in scalar


def test_two_chunk_sizes_give_one_answer():
    args, _ = scan_inputs(64)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(delta(*args, 16)), np.asarray(delta(*args, 64)),
                                   atol=3e-5)


def test_a_chunk_whose_decay_passes_e_to_the_minus_100_stays_finite_and_right():
    """Log decays down to -5 a token: a chunk of 64 sums to about -160, past
    where ``exp(-G)`` alone overflows float32; every decay formed is of a
    difference ``G_i - G_j <= 0`` under the mask."""
    args, got = _agrees(128, 64, decay=5.0, gtol=5e-4)
    run = jnp.cumsum(args[3][:, :, :64], axis=2)
    assert float(jnp.min(run[:, :, -1])) < -100.0 and float(jnp.mean(run[:, :, -1])) < -120.0
    assert not bool(jnp.isfinite(jnp.exp(-run[:, :, -1])).all())       # the naive factor: inf
    assert bool(jnp.all(jnp.isfinite(got)))


def test_a_negative_eigenvalue_agrees_and_a_write_strength_held_to_one_does_not():
    """``beta`` between 1 and 2: ``I - beta k k^T`` reflects along the key
    (``linear_allow_neg_eigval``); the same gates clipped at 1 give another
    output."""
    (q, k, v, g, beta), _ = scan_inputs(48)
    beta = 1.0 + 0.99 * jax.nn.sigmoid(beta)
    assert float(jnp.min(beta)) > 1.0
    with jax.default_matmul_precision("highest"):
        want = literal(q, k, v, g, beta)
        np.testing.assert_allclose(np.asarray(delta(q, k, v, g, beta, 16)), np.asarray(want),
                                   atol=5e-5)
        held = delta(q, k, v, g, jnp.minimum(beta, 1.0), 16)
    assert float(jnp.max(jnp.abs(held - want))) > 1e-2


def test_padded_rows_write_nothing_and_get_no_gradient():
    """40 tokens in chunks of 32: the 24 rows past the end carry ``beta = 0``
    and ``g = 0``; the sequence's own rows read as the first 40 of a longer
    one."""
    args, _ = scan_inputs(64)
    with jax.default_matmul_precision("highest"):
        whole = delta(*args, 32)
        short = delta(*(x[:, :, :40] for x in args), 32)
    np.testing.assert_allclose(np.asarray(short), np.asarray(whole[:, :, :40]), atol=2e-5)


def test_the_backward_pass_keeps_the_chunks_incoming_states_alone():
    """The residuals of the ``custom_vjp``: the five inputs, cut (``g`` a
    scalar a token and head), and ``[chunks, B, H, K, V]`` float32; nothing
    of ``[chunk, chunk]`` a head."""
    args, _ = scan_inputs(40)
    cut = [jnp.zeros((3, 2, 3, 16, *x.shape[3:]), x.dtype) for x in args]
    saved = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda *z: jax.vjp(chunked_delta.delta_chunks, *z)[1], *cut))
    shapes = sorted(tuple(x.shape) for x in saved)
    assert shapes == sorted([(3, 2, 3, 16, 12)] * 2 + [(3, 2, 3, 16, 24)] + [(3, 2, 3, 16)] * 2
                            + [(3, 2, 3, 12, 24)])


def test_bfloat16_operands_float32_sums():
    """At the stated precision: bfloat16 q, k, v; float32 decays, inverse and
    state; the output in q's type, within bfloat16's rounding of float32's."""
    (q, k, v, g, beta), _ = scan_inputs(40)
    with jax.default_matmul_precision("highest"):
        want = delta(q, k, v, g, beta, 16)
        got = delta(*(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta, 16)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.05 * float(jnp.max(jnp.abs(want)))


# ------------------------------------------------ the inverse where it lies

def _strictly_lower(c, seed=0, scale=0.5):
    """[2, 3, C, C] strictly lower triangular, entries near ``beta k.k``'s."""
    return jnp.tril(scale * jax.random.normal(jax.random.PRNGKey(seed + c), (2, 3, c, c)), -1)


@pytest.mark.parametrize("c", [
    64,    # eight blocks of 8 merged two and two three times: the cell's chunk
    16,    # one merge
    8,     # one block: the doubling product alone
    40,    # five blocks of 8: block substitution, no merge
    48,    # six blocks: one merge, then three blocks by substitution
    50,    # blocks of 5 rows, ten of them
    20,    # blocks of 5, four of them
    11,    # a prime count: blocks of one row, substitution alone
    7,     # one block of 7 rows
])
def test_the_inverse_in_place_is_the_inverse_by_blocks(c):
    """Both forms' inverse on whole ``[C, C]`` matrices under block masks
    against numpy's in float64, over every way a chunk divides (the doubling
    product alone, merges, block substitution, blocks of 5 and of one row):
    right to 2e-6 of the largest entry, unit lower triangular to the bit."""
    a = _strictly_lower(c)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(chunked_delta._unit_lower_inverse_of)(a)
    exact = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    top = float(np.max(np.abs(exact)))
    assert float(np.max(np.abs(np.asarray(got, np.float64) - exact))) / top < 2e-6
    assert not np.triu(np.asarray(got), 1).any() and (np.diagonal(got, axis1=-2, axis2=-1) == 1).all()


@pytest.mark.parametrize("c", [64, 40, 20, 11])
def test_the_inverse_is_pulled_back_through_itself_alone(c):
    """``dA = -T^T dT T^T`` against autodiff through the plain
    ``_inverse_in_place``'s products, on the strictly lower part (all of
    ``a`` that a chunk makes); the backward pass keeps ``T`` and nothing of
    its making."""
    a, dt = _strictly_lower(c), jax.random.normal(jax.random.PRNGKey(c), (2, 3, c, c))
    with jax.default_matmul_precision("highest"):
        (want,), (got,) = (pulled(fn)(dt, a)[1] for fn in (
            chunked_delta._inverse_in_place, chunked_delta._unit_lower_inverse_of))
    np.testing.assert_allclose(np.asarray(jnp.tril(got, -1)), np.asarray(jnp.tril(want, -1)),
                               atol=2e-6 * float(jnp.max(jnp.abs(want))))
    kept = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda a: jax.vjp(chunked_delta._unit_lower_inverse_of, a)[1], a))
    assert [x.shape for x in kept] == [(2, 3, c, c)]


def _loops(fn, *args):
    """[(reverse, the body's equations, every primitive under the body)] of
    each ``scan`` in ``fn``'s jaxpr whose carry is the walk's state."""
    def under(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for value in eqn.params.values():
                for inner in (value if isinstance(value, (list, tuple)) else [value]):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from under(inner)

    found = []
    for eqn in under(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            found.append((eqn.params["reverse"], len(body.eqns), list(under(body))))
    return found


def _highest(eqns):
    """The products among ``eqns`` at the highest precision: the inverse's."""
    return [e for e in eqns if e.primitive.name == "dot_general" and e.params["precision"] is not None
            and jax.lax.Precision.HIGHEST in tuple(e.params["precision"])]


def _both_ways(chunk):
    def run(*args):
        out, pull = jax.vjp(lambda *z: delta(*z, chunk), *args)
        return pull(out)
    return run


def test_the_scalar_walks_loop_multiplies_whole_chunks_and_gathers_nothing():
    """The chunk loop of the scalar-gate walk: no ``gather``, ``scatter-add``
    or ``pad`` (the gathered blocks' inverse made nine a chunk), every product
    at the highest precision is ``[C, C]`` by ``[C, C]`` (the inverse where it
    lies), four products read the state (``_carry``), and the backward's loop
    holds 183 equations where the gathered blocks' autodiff held 231: what
    keeps a later edit from putting the blocks back in the loop."""
    (q, k, v, g, beta), _ = scan_inputs(150)
    (reverse, top, eqns), = _loops(lambda *z: delta(*z, 16), q, k, v, g, beta)
    names = [e.primitive.name for e in eqns]
    assert not reverse and top <= 42 and not {"gather", "scatter-add", "pad"} & set(names)
    products, highest = [e for e in eqns if e.primitive.name == "dot_general"], _highest(eqns)
    assert len(products) == 12 and len(highest) == 6            # 16 rows: a doubling of 8, one merge
    assert all(tuple(x.aval.shape[-2:]) == (16, 16) for e in highest for x in e.invars)
    carry = jax.make_jaxpr(chunked_delta._carry)(
        jnp.zeros((2, 3, 12, 24)), *chunked_delta._state_free(*(x[:, :, :16] for x in (q, k, v, g, beta))))
    assert [e.primitive.name for e in carry.jaxpr.eqns].count("dot_general") == 4
    forward, backward = _loops(_both_ways(16), q, k, v, g, beta)
    assert not forward[0] and backward[0] and backward[1] <= 183
    assert not {"gather", "scatter-add"} & {e.primitive.name for e in backward[2]}


def test_the_per_channel_walks_loop_multiplies_whole_chunks_too():
    """A decay a key channel walks ``_chunk`` with the same inverse: every
    product at the highest precision in either loop is ``[C, C]`` by ``[C,
    C]`` (six a chunk of 16 forward; those and the pull-back's two in the
    backward's loop), and the loops hold at most 85 and 293 equations where
    the gathered blocks' made 140 and 356 (94 and 308 with the inverse alone
    swapped: a sub-block's own pairs are laid beside the earlier blocks' by a
    join and two selects, no product with an identity).  The sub-blocks' own
    indexing keeps a ``gather`` forward and a ``scatter-add`` and ``pad`` in
    its pull-back: they are not the inverse's."""
    (q, k, v, g, beta), _ = scan_inputs(150)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    forward, backward = _loops(_both_ways(16), q, k, v, wide, beta)
    assert (forward[0], backward[0]) == (False, True) and forward[1] <= 85 and backward[1] <= 293
    for (_, _, eqns), count in ((forward, 6), (backward, 8)):
        highest = _highest(eqns)
        assert len(highest) == count
        assert all(tuple(x.aval.shape[-2:]) == (16, 16) for e in highest for x in e.invars)


def test_each_form_leaves_its_scan_path_in_the_launch_log(monkeypatch, capsys, tmp_path):
    """A trace of the walk records one ``scan_path`` span: ``scalar`` with
    the two head sizes, ``per_channel`` for a decay a key channel, the
    inverse made where it lies on both; and ``tools/launch_report.py``
    prints them."""
    log = profiling.LaunchLog()
    monkeypatch.setattr(chunked_delta, "launch", log)
    (q, k, v, g, beta), _ = scan_inputs(40)
    jax.make_jaxpr(lambda *z: delta(*z, 16))(q, k, v, g, beta)
    jax.make_jaxpr(lambda *z: delta(*z, 16))(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)
    assert log.attrs_of("scan_path") == [
        {"path": "scalar", "heads": 3, "key": 12, "value": 24, "inverse": "in_place"},
        {"path": "per_channel", "heads": 3, "key": 12, "value": 24, "inverse": "in_place"}]
    from tools import launch_report

    log.write(str(tmp_path / "l.json"))
    assert launch_report.main([str(tmp_path / "l.json")]) == 0
    out = capsys.readouterr().out
    assert "scan_path: scalar, 3 heads, keys of 12, values of 24, the inverse in_place" in out
    assert "scan_path: per_channel, 3 heads, keys of 12, values of 24, the inverse in_place" in out


# ------------------------------------------------------------------ the block

def _rms(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


@pytest.mark.parametrize("op", ["linear_attention", "full_attention"])
def test_the_block_norms_a_sublayers_output(op):
    """``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(SwiGLU(h))`` with the
    modules applied one by one, against ``expert_torso.Block`` under the
    spec's ``post_norm``; the same parameters under the pre-norm order give
    another output."""
    import dataclasses

    spec = olmo_hybrid.spec_from_config(TORSO)
    f32 = jnp.float32
    block = expert_torso.Block(spec, op, "dense", f32, f32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 48))
    params = init_of(block, jax.random.PRNGKey(1), x)
    p = jax.tree_util.tree_map(        # norm weights that are not one
        lambda w: w + 0.1 * jax.random.normal(jax.random.PRNGKey(2), w.shape), params["params"])
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(block.apply)({"params": p}, x)
        mixer = dict(spec.mixers)[op](spec, op, f32, f32)
        h = x + _rms(mixer.apply({"params": p[op]}, x), p["operator_norm"]["weight"])
        y = expert_torso.SwiGLU(spec.intermediate_size, f32, f32).apply({"params": p["dense"]}, h)
        want = h + _rms(y, p["ffn_norm"]["weight"])
        pre = expert_torso.Block(dataclasses.replace(spec, post_norm=False), op, "dense", f32, f32)
        other, _ = jax.jit(pre.apply)({"params": p}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.max(jnp.abs(other - want))) > 0.1


def _plain_attention(q, k, v):
    """Causal softmax attention, [B, H, T, D], float32, scores unscaled."""
    t = q.shape[2]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), jnp.einsum("bhsd,bhtd->bhst", q, k), -jnp.inf)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(s, -1), v)


def test_queries_and_keys_are_normed_over_the_whole_width():
    """``q = RMSNorm_d(W_q x)`` over all heads' width, then the split: the
    layer against the formulas written out; a norm a head at a time (each
    head with its slice of the weight) gives another output."""
    spec = olmo_hybrid.spec_from_config(TORSO)
    layer = olmo_hybrid.QkNormAttention(spec, "full_attention", jnp.float32, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 48))
    p = init_of(layer, jax.random.PRNGKey(1), x)["params"]
    p = dict(p, q_norm=p["q_norm"] + 0.2 * jax.random.normal(jax.random.PRNGKey(3), (48,)),
             k_norm=p["k_norm"] + 0.2 * jax.random.normal(jax.random.PRNGKey(4), (48,)))
    heads = lambda y: jnp.moveaxis(y.reshape(2, 40, 3, 16), 2, 1)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = jax.jit(layer.apply)({"params": p}, x)

        def written_out(norm):
            q, k = norm(x @ p["w_q"], p["q_norm"]) / math.sqrt(16), norm(x @ p["w_k"], p["k_norm"])
            a = _plain_attention(heads(q), heads(k), heads(x @ p["w_v"]))
            return jnp.moveaxis(a, 1, 2).reshape(2, 40, 48) @ p["w_o"]

        whole = written_out(_rms)
        by_head = written_out(lambda y, w: _rms(y.reshape(2, 40, 3, 16), w.reshape(3, 16)).reshape(
            2, 40, 48))
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), atol=2e-5)
    assert float(jnp.max(jnp.abs(by_head - whole))) > 1e-2


def test_thirty_heads_of_128_go_through_the_causal_kernels():
    """30 heads, no power of two and no multiple of 8, a key-value head each:
    the blocked kernels, forward and the three gradients, against plain
    attention (Pallas' interpreter)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, cot = (0.3 * jax.random.normal(key, (1, 30, 40, 128)) for key in ks)
    with jax.default_matmul_precision("highest"):
        want, wanted = pulled(_plain_attention)(cot, q, k, v)
        got, gots = pulled(blocked.blocked_attention)(cot, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for name, a, b in zip("qkv", gots, wanted):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name)
    assert olmo_hybrid.QkNormAttention.count(
        olmo_hybrid.spec_from_config(TORSO), "full_attention", 4, 1568)["blocks_total_full"] == (
            4 * 3 * blocked.blocks_visited(1568, None, 1)[1])

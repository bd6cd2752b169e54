"""The network kind ``granite_hybrid`` in the program: the chunked scan and its
hand-walked backward pass against the literal recurrence, the layout kernels
around it, one mixer against the layer written out and what a torso without
experts leaves out, at small widths on the CPU; what every torso is held to
(structure, ``benchmark/reference/granite_h_q.py`` on seeded weights, scopes,
counters, the configuration path, the trainer's loop) is the contract's,
``tests/torso_contract.py``, on this torso's row."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_torso, granite_hybrid
from ape_x_dqn_tpu.ops.chunked_scan import chunks_of, cut, join, scan_chunks
from ape_x_dqn_tpu.ops.pallas.scan_layout import conv_to_chunks, gated_norm
from tests import torso_contract as contract
from tests.torso_contract import built  # noqa: F401 - the module's fixture

TORSO = contract.GRANITE


class TestContract(contract.of("granite_hybrid")):
    """The contract's cases on this torso (``tests/torso_contract.py``)."""


def chunked(x, dt, a, b, c, d, chunk):
    """``scan_chunks`` for a caller that holds ``[B, T, H, P]``: cut, turned, walked and joined."""
    y = scan_chunks(cut(x, chunk, True), cut(dt, chunk, True), a, cut(b, chunk), cut(c, chunk), d)
    return join(y, x.shape[1], True)


def literal(x, dt, a, b, c, d):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``, a token a step."""
    def step(state, token):
        xt, dtt, bt, ct = token
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, ct) + d[:, None] * xt

    first = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[-1]))
    _, ys = jax.lax.scan(step, first, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1)


def scan_inputs(tokens, rows=2, heads=3, head=4, state=5):
    ks = jax.random.split(jax.random.PRNGKey(tokens), 7)
    x = jax.random.normal(ks[0], (rows, tokens, heads, head))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, tokens, heads)))
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    b, c = (jax.random.normal(k, (rows, tokens, state)) for k in ks[3:5])
    return (x, dt, a, b, c, jax.random.normal(ks[5], (heads,))), jax.random.normal(ks[6], x.shape)


@pytest.mark.parametrize("tokens,chunk", [
    (48, 16),    # a multiple of the chunk
    (40, 16),    # not one: the last chunk is padded
    (40, 64),    # one chunk, of the sequence's own length
    (40, 8),     # another chunk size, the same answer
])
def test_chunked_scan_is_the_literal_recurrence(tokens, chunk):
    """The output and, through the hand-walked backward pass, the gradient of
    every input, against autodiff of the recurrence stepped a token at a time."""
    args, cot = scan_inputs(tokens)
    assert chunks_of(tokens, chunk) == {(48, 16): (3, 48), (40, 16): (3, 48), (40, 64): (1, 40),
                                        (40, 8): (5, 40)}[tokens, chunk]
    with jax.default_matmul_precision("highest"):
        want, wanted = contract.pulled(literal)(cot, *args)
        got, gots = contract.pulled(lambda *z: chunked(*z, chunk))(cot, *args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
        for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), gots, wanted):
            assert g.shape == w.shape and g.dtype == w.dtype, name
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=2e-4,
                                       err_msg=name)


def test_the_backward_pass_keeps_the_chunks_incoming_states_alone():
    """The residuals of the ``custom_vjp``: the six inputs, cut, and ``[chunks,
    B, H, P, N]`` float32; nothing of ``[chunk, chunk]`` a head."""
    (x, dt, a, b, c, d), _ = scan_inputs(40)
    args = (cut(x, 16, True), cut(dt, 16, True), a, cut(b, 16), cut(c, 16), d)
    saved = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda *z: jax.vjp(scan_chunks, *z)[1], *args))
    shapes = sorted(tuple(s.shape) for s in saved)
    assert (3, 2, 3, 4, 5) in shapes                      # three chunks' incoming states
    assert not any(s[-2:] == (16, 16) for s in shapes if len(s) >= 2), shapes
    assert sum(int(np.prod(s)) for s in shapes) == sum(int(np.prod(a.shape)) for a in args) + 3 * 2 * 3 * 4 * 5


# tokens and chunk: a multiple, not one (the last chunk padded), the cell's 1,568 in 256, a
# sequence shorter than a chunk
CUTS = [(48, 16), (40, 16), (1568, 256), (10, 16)]


def _close(got, want, name, tol=2e-5):
    assert got.shape == want.shape and got.dtype == want.dtype, name
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, (name, scale)


@pytest.mark.parametrize("turned", [True, False])
@pytest.mark.parametrize("tokens,chunk", CUTS)
def test_the_convolution_writes_the_scans_layout(tokens, chunk, turned):
    """``conv_to_chunks`` (Pallas' interpreter here) against the literal formula,
    ``silu(bias + sum_j kernel[:, j] v[t - 3 + j])`` cut in chunks: the values,
    zeros past T, and the gradients of the input, the kernel and the bias from
    a cotangent that is anything at the padded tokens."""
    ks = jax.random.split(jax.random.PRNGKey(tokens + turned), 4)
    v, kernel, bias = (jax.random.normal(k, shape) for k, shape in zip(
        ks, ((2, tokens, 128), (128, 4), (128,))))

    def literal(v, kernel, bias):
        padded = jnp.pad(v, ((0, 0), (3, 0), (0, 0)))
        act = jax.nn.silu(bias + sum(padded[:, j:j + tokens] * kernel[:, j] for j in range(4)))
        return cut(act, chunk, turned)

    want, pull = jax.vjp(literal, v, kernel, bias)
    got, pull_kernel = jax.vjp(lambda *a: conv_to_chunks(*a, chunk, turned), v, kernel, bias)
    n, padded = chunks_of(tokens, chunk)
    assert got.shape == ((n, 2, 128, padded // n) if turned else (n, 2, padded // n, 128))
    _close(got, want, "values")
    own = cut(jnp.ones((2, tokens, 1)), chunk, turned) > 0
    assert not np.asarray(jnp.where(own, 0.0, got)).any()
    cot = jax.random.normal(ks[3], want.shape)
    for name, g, w in zip(("input", "kernel", "bias"), pull_kernel(cot), pull(cot)):
        _close(g, w, name, 1e-4)


@pytest.mark.parametrize("tokens,chunk", CUTS)
def test_the_gate_and_norm_read_the_scans_layout(tokens, chunk):
    """``gated_norm`` against ``rmsnorm(y silu(z)) w`` in float32 with ``y`` cut
    and turned as the scan writes it: values and the gradients of ``y`` (zeros
    at the padded tokens), ``z`` and the weight."""
    ks = jax.random.split(jax.random.PRNGKey(tokens), 4)
    y, z = (jax.random.normal(k, (2, tokens, 128)) for k in ks[:2])
    w, eps = 1.0 + 0.1 * jax.random.normal(ks[2], (128,)), 1e-5

    def literal(y, z, w):
        g = y * jax.nn.silu(z)
        return g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps) * w

    want, pull = jax.vjp(literal, y, z, w)
    cut_y = cut(y, chunk, True)
    got, pull_kernel = jax.vjp(lambda *a: gated_norm(*a, eps), cut_y, z, w)
    _close(got, want, "values")
    cot = jax.random.normal(ks[3], want.shape)
    (dy, dz, dw), (dy_w, dz_w, dw_w) = pull_kernel(cot), pull(cot)
    _close(dy, cut(dy_w, chunk, True), "y", 1e-4)                # zeros where the cut pads
    _close(dz, dz_w, "z", 1e-4)
    _close(dw, dw_w, "weight", 1e-4)
    # in the compute type the answer is the float32 one rounded once
    low = gated_norm(cut_y.astype(jnp.bfloat16), z.astype(jnp.bfloat16), w, eps)
    assert low.dtype == jnp.bfloat16
    _close(low.astype(jnp.float32), literal(y.astype(jnp.bfloat16).astype(jnp.float32),
                                            z.astype(jnp.bfloat16).astype(jnp.float32), w), "bf16", 1e-2)


@pytest.mark.parametrize("tokens,chunk", [(40, 16), (10, 16), (1568, 256)])
def test_the_mixer_is_the_literal_layer(tokens, chunk):
    """One ``Mamba2`` layer in float32 against the layer written out a token at
    a time (projection, convolution, SiLU, the recurrence, gate, norm,
    projection): the output and the gradient of every parameter and of the
    input, at tokens that do not divide the chunk."""
    spec = granite_hybrid.spec_from_config(dict(TORSO, mamba_chunk_size=chunk))
    layer = granite_hybrid.Mamba2(spec=spec, op="mamba", compute_dtype=jnp.float32,
                                  param_dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(tokens), (2, tokens, TORSO["hidden_size"]))
    params = contract.init_of(layer, jax.random.PRNGKey(1), u)
    params = jax.tree_util.tree_map(                             # off the initial ones and zeros
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(x.size), x.shape), params)
    heads, head, state = TORSO["mamba_n_heads"], TORSO["mamba_d_head"], TORSO["mamba_d_state"]
    inner = heads * head

    def literal_layer(params, u):
        p = params["params"]
        z, xbc, dt = jnp.split(u @ p["w_in"], (inner, 2 * inner + 2 * state), axis=-1)
        padded = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
        xbc = jax.nn.silu(p["conv_bias"] + sum(
            padded[:, j:j + tokens] * p["conv_kernel"][:, j] for j in range(4)))
        x, b, c = jnp.split(xbc, (inner, inner + state), axis=-1)
        y = literal(x.reshape(2, tokens, heads, head), jax.nn.softplus(dt + p["dt_bias"]),
                    -jnp.exp(p["A_log"]), b, c, p["D"]).reshape(x.shape)
        g = y * jax.nn.silu(z)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + TORSO["rms_norm_eps"])
        return (g * p["norm"]) @ p["w_out"]

    cot = jax.random.normal(jax.random.PRNGKey(2), u.shape)
    with jax.default_matmul_precision("highest"):
        want, (dp_w, du_w) = contract.pulled(literal_layer)(cot, params, u)
        got, (dp, du) = contract.pulled(layer.apply)(cot, params, u)
        _close(got, want, "output", 1e-4)
    _close(du, du_w, "input", 1e-3)
    for name in dp_w["params"]:
        _close(dp["params"][name], dp_w["params"][name], name, 1e-3)


def test_the_state_crosses_chunks_and_nothing_sees_the_future():
    """A change to the oldest token moves the newest token's layer output,
    32 tokens and two chunk boundaries later; a change to a later token moves
    nothing before it."""
    spec = granite_hybrid.spec_from_config(TORSO)
    layer = granite_hybrid.Mamba2(spec, "mamba", jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    params = contract.init_of(layer, jax.random.PRNGKey(1), u)
    apply = jax.jit(layer.apply)
    base = apply(params, u)
    moved = lambda out, t: float(jnp.max(jnp.abs(out[:, t] - base[:, t])))  # noqa: E731
    assert moved(apply(params, u.at[:, 0].add(1.0)), 39) > 1e-5
    later = apply(params, u.at[:, 20:].add(1.0))
    assert moved(later, 19) == 0.0 and moved(later, 20) > 1e-4


def test_a_spec_without_experts(built):
    held, outputs, experts = expert_torso.cut_from_config(TORSO)
    assert (held, outputs, experts) == ([0, 1, 2, 3, 4], 0, (0, 0))
    spec = granite_hybrid.spec_from_config(TORSO)
    with pytest.raises(ValueError, match="no moe layer"):
        dataclasses.replace(spec, layers=(("mamba", "moe"),))
    with pytest.raises(ValueError, match="holds no expert"):
        dataclasses.replace(spec, experts_held=(0, 1))
    net, params = built.net(), built.params
    _, sown = built.applied
    assert net.routing_metrics(sown) is None and net.rebalanced(params, sown) is params

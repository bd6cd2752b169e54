"""The network kind ``granite_hybrid`` in the program: the chunked scan and its
hand-walked backward pass against the literal recurrence, the network against
``benchmark/reference/granite_h_q.py`` on seeded weights, what a torso
without experts leaves out, what the other two torsos keep, the
configuration path and the trainer's loop, all at small widths on the CPU
(the attention kernels in Pallas' interpreter)."""
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
from ape_x_dqn_tpu.models import dueling, expert_torso, granite_hybrid, laguna_moe, lfm2_moe
from ape_x_dqn_tpu.models.dueling import build_network
from ape_x_dqn_tpu.ops.chunked_scan import chunked_scan, chunks_of, cut, join, scan_chunks
from ape_x_dqn_tpu.ops.pallas.scan_layout import conv_to_chunks, gated_norm
from ape_x_dqn_tpu.utils import profiling

TORSO = dict(
    hidden_size=64, shared_intermediate_size=128, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, rms_norm_eps=1e-5, attention_multiplier=0.0625, embedding_multiplier=12,
    residual_multiplier=0.22, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=8, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=16, num_local_experts=0,
    num_experts_per_tok=0, position_embedding_type="nope", logits_scaling=8, vocab_size=100352,
    layer_types=["mamba"] * 2 + ["attention"] + ["mamba"] * 2 + ["mamba"] * 5, num_hidden_layers=5,
    layers_held=[0, 1, 2, 3, 4], channels=[8, 8, 8], hidden=32,
)
# what the benchmark's driver adds to the torso's keys for its reference
CFG = dict(TORSO, obs_shape=[44, 60, 5], num_actions=6, batch_size=4, optimizer="rmsprop",
           learning_rate=6.25e-5, rmsprop_decay=0.95, rmsprop_eps=1.5e-7, max_grad_norm=40.0,
           loss="squared")


def small_net(compute=jnp.float32, **over):
    return build_network("granite_hybrid", 6, torso=dict(TORSO, **over), channels=(8, 8, 8),
                         hidden=32, compute_dtype=compute)


def obs(key, rows=2, shape=(44, 60, 5)):   # 5 frames of 2 x 4 positions: 40 tokens
    return jax.random.randint(key, (rows, *shape), 0, 256).astype(jnp.uint8)


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
        want, pull = jax.vjp(literal, *args)
        got, pull_chunked = jax.vjp(lambda *z: chunked_scan(*z, chunk), *args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
        for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), pull_chunked(cot), pull(cot)):
            assert g.shape == w.shape and g.dtype == w.dtype, name
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=2e-4,
                                       err_msg=name)


def test_the_backward_pass_keeps_the_chunks_incoming_states_alone():
    """The residuals of the ``custom_vjp``: the six inputs and ``[chunks, B, H,
    P, N]`` float32; nothing of ``[chunk, chunk]`` a head."""
    args, _ = scan_inputs(40)
    saved = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda *z: jax.vjp(lambda *w: chunked_scan(*w, 16), *z)[1], *args))
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
    params = layer.init(jax.random.PRNGKey(1), u)
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

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(literal_layer, params, u)
        got, pull_layer = jax.vjp(layer.apply, params, u)
        _close(got, want, "output", 1e-4)
        cot = jax.random.normal(jax.random.PRNGKey(2), want.shape)
        (dp, du), (dp_w, du_w) = pull_layer(cot), pull(cot)
    _close(du, du_w, "input", 1e-3)
    for name in dp_w["params"]:
        _close(dp["params"][name], dp_w["params"][name], name, 1e-3)


def test_a_sequence_already_cut_scans_as_the_uncut_one():
    """``scan_chunks`` on ``cut`` inputs and ``join`` are ``chunked_scan``."""
    (x, dt, a, b, c, d), _ = scan_inputs(40)
    y = scan_chunks(cut(x, 16, True), cut(dt, 16, True), a, cut(b, 16), cut(c, 16), d)
    assert y.shape == (3, 2, 3, 4, 16)
    np.testing.assert_allclose(np.asarray(join(y, 40, True)), np.asarray(chunked_scan(x, dt, a, b, c, d, 16)),
                               atol=1e-6)


def test_the_network_has_the_issues_structure():
    net = small_net()
    x = obs(jax.random.PRNGKey(2))
    assert net.tokens_of(x.shape) == 40
    params = net.init(jax.random.PRNGKey(3), x)["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layers_0_1", "layer_2", "layers_3_4", "w_tok", "final_norm"}
    mamba = params["layers_0_1"]["mamba"]
    assert {k: v.shape[1:] for k, v in mamba.items()} == {
        "w_in": (64, 128 + 144 + 8), "conv_kernel": (144, 4), "conv_bias": (144,), "A_log": (8,),
        "dt_bias": (8,), "D": (8,), "norm": (128,), "w_out": (128, 64)}
    assert set(params["layer_2"]["attention"]) == {"w_q", "w_k", "w_v", "w_o"}   # no gate, no bias
    assert all("dense" in params[k] and "moe" not in params[k]
               for k in ("layers_0_1", "layer_2", "layers_3_4"))
    # Mamba-2's initialisation: -A in [1, 16], softplus(dt_bias) in [1e-3, 1e-1], D = 1
    a, dt = np.exp(np.asarray(mamba["A_log"])), np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (1 <= a).all() and (a <= 16).all() and (1e-3 <= dt).all() and (dt <= 1e-1 + 1e-6).all()
    assert (np.asarray(mamba["D"]) == 1).all()
    out, sown = net.apply({"params": params}, x, mutable=["routing"])
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2]))) and not sown
    spec = net.spec
    assert (spec.residual_multiplier, spec.token_multiplier) == (0.22, 12.0)
    assert spec.router_outputs == 0 and spec.num_held == 0 and spec.frame_history
    assert dict(spec.mixers) == {"attention": granite_hybrid.NopeAttention,
                                 "mamba": granite_hybrid.Mamba2}
    for bad in (dict(mamba_n_groups=2), dict(num_local_experts=4),
                dict(position_embedding_type="rope"), dict(mamba_expand=3)):
        with pytest.raises(ValueError):
            granite_hybrid.spec_from_config(dict(TORSO, **bad))


def test_the_state_crosses_chunks_and_nothing_sees_the_future():
    """A change to the oldest token moves the newest token's layer output,
    32 tokens and two chunk boundaries later; a change to a later token moves
    nothing before it."""
    spec = granite_hybrid.spec_from_config(TORSO)
    layer = granite_hybrid.Mamba2(spec, "mamba", jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    params = layer.init(jax.random.PRNGKey(1), u)
    base = layer.apply(params, u)
    moved = lambda out, t: float(jnp.max(jnp.abs(out[:, t] - base[:, t])))  # noqa: E731
    assert moved(layer.apply(params, u.at[:, 0].add(1.0)), 39) > 1e-5
    later = layer.apply(params, u.at[:, 20:].add(1.0))
    assert moved(later, 19) == 0.0 and moved(later, 20) > 1e-4


def test_the_network_is_the_reference():
    """Forward in float32 (1e-4 of |Q|: sums in another order, the scan in
    chunks against a token a step) and at the stated precision; the gradients
    of sum(Q^2) leaf by leaf, 1e-3 of each leaf's norm."""
    from reference import granite_h_q as ref

    weights = ref.make_weights(jax.random.PRNGKey(11), CFG)
    x = obs(jax.random.PRNGKey(5), rows=4)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.forward(weights, x, CFG)
        scale = float(jnp.std(want)) + float(jnp.mean(jnp.abs(want)))
        for compute, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 0.15)):
            got = small_net(compute).apply(ref.to_program_params(weights, CFG), x)[2]
            assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, compute
        net = small_net()
        wanted = jax.grad(lambda w: jnp.sum(ref.forward(w, x, CFG)[0] ** 2))(weights)
        got = ref.from_program_params(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2))(
            ref.to_program_params(weights, CFG)), CFG)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(wanted)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * float(jnp.linalg.norm(b)) + 1e-7, name
        assert float(jnp.linalg.norm(b)) > 0, name


def _batch(x):
    from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch

    n = x.shape[0]
    return PrioritizedBatch(
        transition=NStepTransition(obs=x, action=jnp.arange(n) % 6, reward=jnp.ones(n),
                                   discount=jnp.full((n,), 0.9), next_obs=x[::-1]),
        indices=jnp.arange(n), is_weights=jnp.linspace(0.4, 1.0, n))


def test_one_learner_step_is_the_references_and_counts_the_scan():
    """Loss, priorities and the parameters after one RMSProp step of the
    program's train step, float32 compute, against ``learner_step``; the
    step's counters: the scan's and the attention layer's from the shapes,
    no routing."""
    from ape_x_dqn_tpu.learner.train_step import StepMetrics, build_train_step, make_optimizer
    from ape_x_dqn_tpu.types import TrainState
    from reference import granite_h_q as ref

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
    # 40 tokens in chunks of 16: 3 chunks, 48 tokens walked, four layers, 4 rows, 3 forwards
    assert metrics.routing is None
    assert {k: float(v) for k, v in metrics.scan.items()} == {
        "chunks": 3 * 4 * 4 * 3.0, "tokens_padded": 3 * 4 * 4 * 48.0, "tokens": 3 * 4 * 4 * 40.0}
    assert float(metrics.attention["pairs_in_mask_full"]) == 3 * 4 * (40 * 41 // 2)
    assert net.scan_metrics(x.shape) == {"chunks": 48.0, "tokens_padded": 768.0, "tokens": 640.0}
    assert StepMetrics(loss=0, mean_abs_td=0, max_abs_td=0, priorities=0, mean_q=0).scan is None


def test_a_lower_target_keeps_the_decays_float32():
    from ape_x_dqn_tpu.learner.train_step import init_train_state, make_optimizer

    net = small_net(jnp.bfloat16)
    assert net.float32_leaves == ("router", "expert_bias", "A_log", "dt_bias", "['D']")
    state = init_train_state(net, make_optimizer("rmsprop", learning_rate=1e-4),
                             jax.random.PRNGKey(0), obs(jax.random.PRNGKey(1), rows=1),
                             target_dtype=jnp.bfloat16)
    kept = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.target_params):
        name = path[-1].key
        if name in ("A_log", "dt_bias", "D"):
            kept.add(name)
            assert leaf.dtype == jnp.float32, jax.tree_util.keystr(path)
        else:   # Dense_0's kernel too: "D" alone would have matched it
            assert leaf.dtype == jnp.bfloat16, jax.tree_util.keystr(path)
    assert kept == {"A_log", "dt_bias", "D"}


LFM2 = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, conv_L_cache=3, norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
            layer_types=["conv", "full_attention"], num_dense_layers=1, num_experts=2,
            router_outputs=4, num_experts_per_tok=2)


def test_the_other_torsos_are_as_they_were():
    """The new spec fields default to what the two expert torsos had: no
    multiplier and no multiplication, the router's float32 leaves alone, the
    parameter trees by name, the counters of a step."""
    from tests.test_laguna_moe import TORSO as LAGUNA

    h, y = jnp.ones((2, 3), jnp.bfloat16), jnp.full((2, 3), 0.5, jnp.bfloat16)
    eqns = jax.make_jaxpr(lambda h, y: expert_torso._added(h, y, 1.0))(h, y).eqns
    assert [e.primitive.name for e in eqns] == ["add"]
    assert "mul" in [e.primitive.name for e in
                     jax.make_jaxpr(lambda h, y: expert_torso._added(h, y, 0.22))(h, y).eqns]
    lfm2 = build_network("lfm2_moe", 6, torso=LFM2, compute_dtype=jnp.float32)
    laguna = build_network("laguna_moe", 6, torso=dict(LAGUNA), channels=(8, 8, 8), hidden=32,
                           compute_dtype=jnp.float32)
    for net in (lfm2, laguna):
        sp = net.spec
        assert (sp.residual_multiplier, sp.token_multiplier, sp.float32_leaves) == (1.0, 1.0, ())
        assert net.float32_leaves == ("router", "expert_bias") and sp.num_held > 0
        assert net.scan_metrics((2, 52, 52, 4)) is None
    x = obs(jax.random.PRNGKey(4))
    params = laguna.init(jax.random.PRNGKey(5), x)
    assert sorted(params["params"]) == sorted(
        ["Conv_0", "Conv_1", "Conv_2", "Dense_0", "Dense_1", "Dense_2", "Dense_3", "final_norm",
         "layer_0", "layers_1_3", "layer_4", "w_tok"])
    text = str(jax.make_jaxpr(lambda p: laguna.apply(p, x)[2])(params))
    assert "0.22" not in text and " 12.0" not in text
    _, sown = laguna.apply(params, x, mutable=["routing"])
    assert float(laguna.routing_metrics(sown)["held_pairs"]) > 0
    with pytest.raises(KeyError):      # a config with experts still has to name them
        lfm2_moe.spec_from_config({k: v for k, v in LFM2.items() if k != "num_experts_per_tok"})


def test_a_spec_without_experts():
    held, outputs, experts = expert_torso.cut_from_config(TORSO)
    assert (held, outputs, experts) == ([0, 1, 2, 3, 4], 0, (0, 0))
    spec = granite_hybrid.spec_from_config(TORSO)
    import dataclasses
    with pytest.raises(ValueError, match="no moe layer"):
        dataclasses.replace(spec, layers=(("mamba", "moe"),))
    with pytest.raises(ValueError, match="holds no expert"):
        dataclasses.replace(spec, experts_held=(0, 1))
    net = small_net()
    x = obs(jax.random.PRNGKey(6))
    params = net.init(jax.random.PRNGKey(7), x)
    _, sown = net.apply(params, x, mutable=["routing"])
    assert net.routing_metrics(sown) is None and net.rebalanced(params, sown) is params


def test_the_scan_is_scoped_inside_the_mixer():
    assert profiling.PARTS[9] == "ssm_scan"
    net = small_net()
    x = obs(jax.random.PRNGKey(8))
    params = net.init(jax.random.PRNGKey(9), x)
    text = jax.jit(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2))).lower(params).as_text(
        debug_info=True)
    for part in ("ssm_scan", "attn_full", "mixer", "dense_ffn", "stem", "head"):
        assert f"torso:{part}" in text, part
    assert "torso:mixer/mamba/torso:ssm_scan" in text
    assert "torso:mixer/attention/torso:attn_full" in text
    assert "transpose(" in text and text.count("torso:ssm_scan") > 10      # the backward walk too
    for part in ("router", "experts", "shared_expert", "attn_window"):
        assert f"torso:{part}" not in text, part
    parts = profiling.hlo_parts(jax.jit(lambda p: net.apply(p, x)[2]).lower(params).compile().as_text())
    assert "ssm_scan" in set(parts.values())


def test_config_carries_the_torso_and_the_committed_file_is_the_cells():
    assert TORSO_NETWORKS[2] == "granite_hybrid" and HISTORY_NETWORKS[:2] == ("laguna_moe", "granite_hybrid")
    assert tuple(dueling.TORSO_KINDS) == TORSO_NETWORKS
    cfg = ApexConfig()
    cfg.network = "granite_hybrid"
    cfg.torso = dict(TORSO)
    with pytest.raises(ValueError, match="frame_stack"):
        cfg.validate()                      # a history needs more than one frame
    cfg.env.frame_stack = 5
    kw = network_kwargs(cfg.validate())
    assert kw["channels"] == (8, 8, 8) and kw["hidden"] == 32
    assert build_network(cfg.network, 6, **kw).spec.num_held == 0
    committed = load_config(os.path.join(ROOT, "configs", "config8_granite4h_q_l10.json"))
    spec = build_network(committed.network, 18, **network_kwargs(committed)).spec
    assert committed.env.frame_stack == 32 and spec.frame_history
    assert committed.learner.replay_sample_size == 8 and committed.learner.steps_per_call == 1
    import json
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "granite4h_q_l10.json")))
    assert spec == granite_hybrid.spec_from_config(cell)
    assert [op for op, _ in spec.layers] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    m = spec.arg("mamba")
    assert (spec.hidden_size, spec.intermediate_size, m.heads, m.head_dim, m.state, m.conv,
            m.chunk, m.inner) == (2048, 8192, 64, 64, 128, 4, 256, 4096)
    assert (spec.arg("num_attention_heads"), spec.arg("num_key_value_heads"), spec.arg("head_dim"),
            spec.arg("attention_multiplier")) == (32, 8, 64, 0.015625)


def test_the_trainers_loop_runs_the_network():
    """``runtime/single_process.py``'s loop, a few learner steps, through
    ``build_components``: the normal path builds and trains the network on
    histories of ``env.frame_stack`` frames."""
    from ape_x_dqn_tpu.runtime import SingleProcessDriver

    cfg = ApexConfig()
    cfg.env.name = "fake-atari"
    cfg.env.frame_stack = 4
    cfg.network = "granite_hybrid"
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
    assert type(driver.network).__name__ == "GraniteHybridQ"

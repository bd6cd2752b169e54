"""``reference/kanana2_q.py``: the router's rounds against a sort in numpy,
the rotary pairs and the latent layer against a head-at-a-time numpy softmax,
its three mechanism flags, the parameter maps, the program against it on
seeded weights (forward, loss, gradients, one learner step, each tolerance with
its reason, and the reference held in bfloat16 failing them), the controls of
the comparison at a toy size on the CPU, and the configuration built
abstractly."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest as mf
import ops_count_kanana2_q as ops
from reference import kanana2_q as ref
from test_benchmark_kanana_cell import TOY_LIMITS, _toy_config, _toy_traffic

PUBLISHED = mf.load_json(os.path.join(mf.HERE, "configs", "kanana2_q_ep8.json"))
CFG = dict(_toy_config(), obs_shape=[44, 60, 5], batch_size=4)


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda k: ref.make_weights(k, CFG))(jax.random.PRNGKey(11))


def test_the_router_takes_the_largest_biased_scores_and_weighs_by_the_scores():
    """16 outputs, 3 chosen, against a stable sort a token in numpy: the bias
    chooses and does not weigh, the gates are the chosen scores over their
    sum plus 1e-20, times 2.448; ``reference_unscaled_gates`` leaves the
    factor off; two equal biased scores go to the earlier output."""
    cfg = dict(num_experts_per_tok=3, routed_scaling_factor=2.448, norm_topk_prob=True)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (3, 50, 16)))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    chosen, gates = ref.route(scores, bias, cfg)
    s, b = np.asarray(scores, np.float64).reshape(-1, 16), np.asarray(bias, np.float64)
    for row, (got, gate) in enumerate(zip(np.asarray(chosen).reshape(-1, 3),
                                          np.asarray(gates).reshape(-1, 3))):
        want = np.argsort(-(s[row] + b), kind="stable")[:3]
        np.testing.assert_array_equal(got, want)
        np.testing.assert_allclose(gate, s[row][want] / (s[row][want].sum() + 1e-20) * 2.448, rtol=1e-5)
    assert (np.asarray(chosen) != np.asarray(jax.lax.top_k(scores, 3)[1])).any(-1).mean() > 0.3
    _, plain = ref.route(scores, bias, dict(cfg, reference_unscaled_gates=True))
    np.testing.assert_allclose(np.asarray(plain) * 2.448, np.asarray(gates), rtol=1e-6)
    tied = jnp.full((1, 16), 0.5).at[0, 9].set(0.7)
    assert np.asarray(ref.route(tied, jnp.zeros(16), cfg)[0]).tolist() == [[9, 0, 1]]
    zero = ref.route(jnp.zeros((1, 16)), jnp.zeros(16), cfg)[1]       # a sum of 0 is held off by 1e-20
    assert bool(jnp.all(jnp.isfinite(zero)))


def test_the_rotary_pairs_turn_by_the_tokens_index():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 8))
    got = np.asarray(ref.turned(x, 100.0), np.float64)
    z = np.asarray(x)[..., 0::2] + 1j * np.asarray(x)[..., 1::2]
    ang = np.arange(9)[:, None] * 100.0 ** (-np.arange(0, 8, 2) / 8)[None, :]
    z = z * np.exp(1j * ang)[None, :, None, :]
    np.testing.assert_allclose(got[..., 0::2], z.real, atol=1e-6)
    np.testing.assert_allclose(got[..., 1::2], z.imag, atol=1e-6)
    flat = np.asarray(ref.turned(x[:, :, 0], 100.0))                   # [B, T, R]: the one shared key
    np.testing.assert_allclose(flat, got[:, :, 0], atol=1e-6)


def test_the_latent_layer_is_the_issues_equations(weights):
    """One row through ``latent_attention`` against numpy: a head at a time,
    the whole [T, T] score matrix, RoPE by complex rotation of the pairs, no
    gate; each of the layer's two flags moves it."""
    p = {k: np.asarray(v, np.float64) for k, v in weights["layer_1"].items()}
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (1, 40, CFG["hidden_size"])), np.float64)
    h, r = CFG["num_attention_heads"], CFG["kv_lora_rank"]
    dn, dr, dv = CFG["qk_nope_head_dim"], CFG["qk_rope_head_dim"], CFG["v_head_dim"]

    def turn(x):                                  # [T, R]: the pairs (2j, 2j + 1) as complex numbers
        z = x[:, 0::2] + 1j * x[:, 1::2]
        ang = np.arange(x.shape[0])[:, None] * CFG["rope_theta"] ** (-np.arange(0, dr, 2) / dr)
        z = z * np.exp(1j * ang)
        return np.stack([z.real, z.imag], -1).reshape(x.shape)

    q = (u[0] @ p["w_q"]).reshape(40, h, dn + dr)
    down = u[0] @ p["w_dkv"]
    c = down[:, :r] / np.sqrt((down[:, :r] ** 2).mean(-1, keepdims=True) + CFG["rms_norm_eps"])
    kv = ((c * p["kv_norm"]) @ p["w_ukv"]).reshape(40, h, dn + dv)
    k_rope = turn(down[:, r:])
    heads = []
    for i in range(h):
        scores = (q[:, i, :dn] @ kv[:, i, :dn].T + turn(q[:, i, dn:]) @ k_rope.T) / np.sqrt(dn + dr)
        scores = np.where(np.tril(np.ones((40, 40), bool)), scores, -np.inf)
        prob = np.exp(scores - scores.max(-1, keepdims=True))
        heads.append((prob / prob.sum(-1, keepdims=True)) @ kv[:, i, dn:])
    want = np.concatenate(heads, -1) @ p["w_o"]
    u32 = jnp.asarray(u, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ref.latent_attention(u32, weights["layer_1"], CFG, jnp.float32, lambda x: x)
        np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-5)
        for flag in ("reference_drops_shared_key", "reference_skips_latent_norm"):
            other = ref.latent_attention(u32, weights["layer_1"], dict(CFG, **{flag: True}),
                                         jnp.float32, lambda x: x)
            assert float(jnp.max(jnp.abs(other - got))) > 1e-3, flag
    assert "w_g" not in weights["layer_1"]


@pytest.mark.parametrize("flag", ref.FLAGS)
def test_each_control_of_this_configuration_moves_q(weights, flag):
    obs = jax.random.randint(jax.random.PRNGKey(5), (4, *CFG["obs_shape"]), 0, 256).astype(jnp.uint8)
    with jax.default_matmul_precision("highest"):
        q, loads = ref.forward(weights, obs, CFG)
        other, _ = ref.forward(weights, obs, dict(CFG, **{flag: True}))
    assert q.shape == (4, 6) and loads.shape == (3, CFG["router_outputs"])
    assert float(jnp.sum(loads[0])) == 0.0                       # the leading dense layer routes nothing
    assert [float(v) for v in jnp.sum(loads[1:], -1)] == [4 * 40 * 3.0] * 2
    assert float(jnp.max(jnp.abs(other - q))) > 1e-2 * float(jnp.std(q))


def test_parameter_maps_are_inverse(weights):
    program = ref.to_program_params(weights, CFG, jnp.bfloat16)
    for path, leaf in jax.tree_util.tree_leaves_with_path(program):
        always = path[-1].key in ("router", "expert_bias")
        assert leaf.dtype == (jnp.float32 if always else jnp.bfloat16), jax.tree_util.keystr(path)
    back = ref.from_program_params(ref.to_program_params(weights, CFG), CFG)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(weights)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(weights)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref.layer_runs(PUBLISHED) == [(0, 1), (1, 5)]          # five like expert layers, one run
    assert ref.layer_kinds(PUBLISHED) == [("latent_attention", "dense")] + [("latent_attention", "moe")] * 5
    assert ref.param_count(CFG) == sum(x.size for x in jax.tree_util.tree_leaves(weights))
    assert "expert_bias" not in weights["layer_0"] and "router" in weights["layer_1"]
    assert weights["layer_1"]["shared_w1"].shape == (64, 2 * 32)  # the two shared experts, one SwiGLU


# ------------------- the program against the reference, on seeded weights

@pytest.fixture(scope="module")
def both(weights):
    """The program's float32 network and train step beside the reference's
    ``learner_step`` on one seeded batch: forward, loss, gradients, one step."""
    from ape_x_dqn_tpu.learner.train_step import build_train_step, make_optimizer
    from ape_x_dqn_tpu.models.dueling import build_network
    from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch, TrainState

    net = build_network("kanana_moe", 6, torso=CFG, channels=(8, 8, 8), hidden=32,
                        compute_dtype=jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(21), 3)
    obs, nxt = (jax.random.randint(kk, (4, *CFG["obs_shape"]), 0, 256).astype(jnp.uint8) for kk in k[:2])
    rows = dict(obs=obs, next_obs=nxt, action=jnp.arange(4) % 6, reward=jnp.ones(4),
                discount=jnp.full((4,), 0.9), is_weights=jnp.linspace(0.4, 1.0, 4))
    target = jax.tree_util.tree_map(
        lambda w: w + 0.05 * jnp.std(w) * jax.random.normal(k[2], w.shape), weights)
    cfg = dict(CFG, optimizer="rmsprop", learning_rate=6.25e-5, rmsprop_decay=0.95,
               rmsprop_eps=1.5e-7, max_grad_norm=40.0, loss="squared")
    opt = make_optimizer("rmsprop", learning_rate=6.25e-5, rmsprop_decay=0.95, rmsprop_eps=1.5e-7,
                         max_grad_norm=40.0, second_moment_dtype=jnp.float32)
    own = lambda t: jax.tree_util.tree_map(lambda v: jnp.array(v, copy=True), t)  # noqa: E731
    params = own(ref.to_program_params(weights, cfg))
    opt_state = jax.tree_util.tree_map_with_path(
        lambda path, v: jnp.full_like(v, 1e-4) if any("nu" in str(p) for p in path) else v,
        opt.init(params))
    state = TrainState(params=params, target_params=own(ref.to_program_params(target, cfg)),
                       opt_state=opt_state, step=jnp.zeros((), jnp.int32), rng=jax.random.PRNGKey(0))
    batch = PrioritizedBatch(
        transition=NStepTransition(obs=obs, action=rows["action"], reward=rows["reward"],
                                   discount=rows["discount"], next_obs=nxt),
        indices=jnp.arange(4), is_weights=rows["is_weights"])
    nu = jax.tree_util.tree_map(lambda w: jnp.full(w.shape, 1e-4), weights)
    step = build_train_step(net, opt, loss_kind="squared", sync_in_step=False, jit=True)
    with jax.default_matmul_precision("highest"):
        new_state, metrics = step(state, batch)
        stepped = {name: jax.jit(lambda w, t, v, r, name=name: ref.learner_step(w, t, v, r, cfg, name))(
            weights, target, nu, rows) for name in ("stated", "bf16_held")}
        q = jax.jit(net.apply)(ref.to_program_params(weights, cfg), obs)[2]
        want_q = jax.jit(lambda w: ref.forward(w, obs, cfg)[0])(weights)
        held_q = jax.jit(lambda w: ref.forward(ref._hold(w, jnp.bfloat16), obs, cfg, jnp.bfloat16)[0])(weights)
    return dict(cfg=cfg, net=net, q=q, want_q=want_q, held_q=held_q.astype(jnp.float32), metrics=metrics,
                got_w=ref.from_program_params(new_state.params, cfg), stepped=stepped,
                weights=weights)


def _update_rel(got, want, old):
    num = den = 0.0
    for a, b, o in zip(*(jax.tree_util.tree_leaves(t) for t in (got, want, old))):
        num += float(jnp.sum(jnp.square((a - o) - (b - o))))
        den += float(jnp.sum(jnp.square(b - o)))
    return float(np.sqrt(num / den))


def test_the_programs_forward_is_the_references_and_bfloat16_is_not(both):
    """Q in float32 within 1e-4 of |Q| (sums in another order: blocked kernels
    against whole score rows, a grouped walk against an expert at a time); the
    reference held in bfloat16 is a hundred times further."""
    scale = float(jnp.std(both["want_q"]) + jnp.mean(jnp.abs(both["want_q"])))
    assert float(jnp.max(jnp.abs(both["q"] - both["want_q"]))) <= 1e-4 * scale
    assert float(jnp.max(jnp.abs(both["held_q"] - both["want_q"]))) > 1e-2 * scale


def test_one_learner_step_is_the_references_and_bfloat16_fails_every_tolerance(both):
    """Loss within 1e-4 and priorities within 2e-4 (a TD error is a difference
    of Q values of order one, each within 1e-4), the parameters' change within
    2e-3 of its norm (a clipped gradient through RMSProp at a second moment of
    1e-4: every leaf's rounding is a thousandth of its move); the reference
    held in bfloat16, the nearest precision below the stated one, fails all
    three."""
    s, m = both["stepped"]["stated"], both["metrics"]
    want_w, _, _, want_prio, want_loss = s
    assert float(m.loss) == pytest.approx(float(want_loss), rel=1e-4)
    np.testing.assert_allclose(np.asarray(m.priorities), np.asarray(want_prio), rtol=2e-4)
    assert _update_rel(both["got_w"], want_w, both["weights"]) < 2e-3
    for i in (1, 2):                     # the balancing rule moved both routing layers' bias
        moved = both["got_w"][f"layer_{i}"]["expert_bias"] - both["weights"][f"layer_{i}"]["expert_bias"]
        assert float(jnp.max(jnp.abs(moved))) > 1e-3
        np.testing.assert_allclose(np.asarray(both["got_w"][f"layer_{i}"]["expert_bias"]),
                                   np.asarray(want_w[f"layer_{i}"]["expert_bias"]), atol=1e-6)
    held_w, _, _, held_prio, held_loss = both["stepped"]["bf16_held"]
    assert abs(float(held_loss) / float(want_loss) - 1) > 1e-4
    assert float(jnp.max(jnp.abs(held_prio / want_prio - 1))) > 2e-4
    assert _update_rel(held_w, want_w, both["weights"]) > 2e-3


def test_the_gradients_are_the_references_leaf_by_leaf(both, weights):
    """The gradients of sum(Q^2), each leaf within 1e-3 of its norm; no leaf
    but the expert bias, a buffer, is without one."""
    cfg, net = both["cfg"], both["net"]
    obs = jax.random.randint(jax.random.PRNGKey(5), (4, *CFG["obs_shape"]), 0, 256).astype(jnp.uint8)
    with jax.default_matmul_precision("highest"):
        wanted = jax.jit(jax.grad(lambda w: jnp.sum(ref.forward(w, obs, cfg)[0] ** 2)))(weights)
        got = ref.from_program_params(jax.jit(jax.grad(
            lambda p: jnp.sum(net.apply(p, obs)[2] ** 2)))(ref.to_program_params(weights, cfg)), cfg)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(wanted)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * float(jnp.linalg.norm(b)) + 1e-7, name
        assert float(jnp.linalg.norm(b)) > 0 or "expert_bias" in name, name


# ------------------------------------ the comparison's controls, at the toy size

@pytest.fixture(scope="module")
def toy_run():
    cfg, traffic = _toy_config(), _toy_traffic()
    drv = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    inputs, shots = drv.check_shots(cfg, traffic, 2**31 + 9)
    counts, got, reference = drv.base.program_numbers(cfg, float(traffic["beta"]), inputs, shots)
    return drv, cfg, float(traffic["beta"]), inputs, shots, counts, got, reference


def test_the_program_passes_at_the_toy_size(toy_run):
    _, _, _, _, shots, counts, got, _ = toy_run
    assert counts == dict.fromkeys(counts, 0) and shots["routing"]["held_pairs"] > 0
    assert all(got[name] <= limit for name, limit in TOY_LIMITS.items()), got


# (control, the number it is the control of at the toy size)
@pytest.mark.parametrize("control,number", [
    ("gather_one_row_on", "fused_priority_rel"),
    ("fp8_activations", "fused_priority_median_rel"),
    ("bf16_held", "fused_update_rel"),
])
def test_each_control_moves_the_three_numbers(toy_run, control, number):
    drv, cfg, beta, inputs, shots, _, got, reference = toy_run
    precision, shift = drv.base.CONTROLS[control]
    numbers = drv.base.control_numbers(cfg, beta, inputs, shots, reference, precision, shift)
    assert numbers[number] > TOY_LIMITS[number] and numbers[number] > 2.5 * got[number], numbers
    assert all(v > 0 for v in numbers.values())


def test_the_flags_ride_as_controls_of_the_collecting_driver(toy_run):
    """``check_kanana_controls.py`` rebinds ``check_flag_control.FLAGS`` to this
    reference's three, and each then stands among the driver's controls as a
    precision of its own name; ``reference_drops_shared_key`` in the program's
    place moves all three numbers at the toy size."""
    import check_flag_control
    import check_kanana_controls

    assert check_kanana_controls.FLAGS == ref.FLAGS
    drv, cfg, beta, inputs, shots, _, got, reference = toy_run
    before_flags, before = check_flag_control.FLAGS, dict(drv.base.CONTROLS)
    check_flag_control.FLAGS = check_kanana_controls.FLAGS
    try:
        with check_flag_control.flags_as_controls(drv.base, list(ref.FLAGS)) as base:
            assert list(base.CONTROLS) == list(ref.FLAGS)
            numbers = base.control_numbers(cfg, beta, inputs, shots, reference,
                                           *base.CONTROLS["reference_drops_shared_key"])
            assert all(numbers[name] > 2 * got[name] for name in got), (numbers, got)
    finally:
        check_flag_control.FLAGS = before_flags
    assert drv.base.CONTROLS == before


def test_published_configuration_builds_abstractly():
    """The cell's network at its published widths and its share of the experts:
    the program's parameter tree, made abstractly, holds the reference's and
    the count's parameters."""
    from ape_x_dqn_tpu.models.dueling import build_network

    cfg = PUBLISHED
    net = build_network(cfg["network"], cfg["num_actions"], torso=cfg,
                        channels=tuple(cfg["channels"]), hidden=cfg["hidden"])
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == ref.param_count(cfg) == ops.param_count(cfg) == 624_146_739
    assert set(shapes["params"]) >= {"layer_0", "layers_1_5"}
    latent = shapes["params"]["layers_1_5"]["latent_attention"]
    assert {k: v.shape for k, v in latent.items()} == {
        "w_q": (5, 2048, 32 * 192), "w_dkv": (5, 2048, 576), "kv_norm": (5, 512),
        "w_ukv": (5, 512, 32 * 256), "w_o": (5, 4096, 2048)}
    assert shapes["params"]["layer_0"]["dense"]["w1"].shape == (2048, 6144)
    moe = shapes["params"]["layers_1_5"]["moe"]
    assert moe["router"].shape == (5, 2048, 128) and moe["w13"].shape == (5, 16, 2048, 1536)
    assert shapes["params"]["layers_1_5"]["shared_expert"]["w2"].shape == (5, 1536, 2048)
    assert net.tokens_of((1, 84, 84, 32)) == 1568 == ops.tokens_per_sample(cfg)
    assert net.delta_metrics((8, 84, 84, 32)) is None and net.scan_metrics((8, 84, 84, 32)) is None
    assert net.attention_metrics((8, 84, 84, 32)) == {
        "pairs_in_mask_latent": 6 * 8 * 1_230_096.0, "pairs_computed_latent": 6 * 8 * 28 * 128 * 512.0,
        "blocks_visited_latent": 6 * 8 * 32 * 28.0, "blocks_total_latent": 6 * 8 * 32 * 13 * 4.0}

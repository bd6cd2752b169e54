"""``reference/ling3_q.py``: the router that keeps groups against a loop in
numpy, RoPE in pairs and the latent layer against a head-at-a-time numpy
softmax, its four mechanism flags, the parameter maps, the controls of the
comparison at a toy size on the CPU, and the configuration built abstractly."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest as mf
import ops_count_ling3_q as ops
from reference import ling3_q as ref
from test_benchmark_ling_cell import TOY_LIMITS, _toy_config, _toy_traffic

PUBLISHED = mf.load_json(os.path.join(mf.HERE, "configs", "ling3_q_l7.json"))
CFG = dict(_toy_config(), obs_shape=[44, 60, 5], batch_size=4)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(jax.random.PRNGKey(11), CFG)


def test_the_router_keeps_groups_then_chooses_by_sorting():
    """64 outputs in 8 groups, 4 kept, 8 chosen, against a loop over tokens
    and groups in numpy; ``reference_ungrouped_router`` is the plain top 8."""
    cfg = dict(n_group=8, topk_group=4, num_experts_per_tok=8, routed_scaling_factor=2.5)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (3, 50, 64)))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    chosen, gates = ref.route(scores, bias, cfg)
    s, b = np.asarray(scores, np.float64).reshape(-1, 64), np.asarray(bias, np.float64)
    for row, (got, gate) in enumerate(zip(np.asarray(chosen).reshape(-1, 8),
                                          np.asarray(gates).reshape(-1, 8))):
        biased = s[row] + b
        group_score = np.sort(biased.reshape(8, 8), -1)[:, -2:].sum(-1)
        kept = np.argsort(-group_score, kind="stable")[:4]
        allowed = np.isin(np.arange(64) // 8, kept)
        want = np.argsort(-np.where(allowed, biased, -np.inf), kind="stable")[:8]
        np.testing.assert_array_equal(got, want)
        np.testing.assert_allclose(gate, s[row][want] / s[row][want].sum() * 2.5, rtol=1e-5)
    plain, _ = ref.route(scores, bias, dict(cfg, reference_ungrouped_router=True))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(jax.lax.top_k(scores + bias, 8)[1]))
    assert (np.sort(np.asarray(plain), -1) != np.sort(np.asarray(chosen), -1)).any(-1).mean() > 0.5


def test_the_latent_layer_is_the_issues_equations(weights):
    """One row through ``latent_attention`` against numpy: a head at a time,
    the whole [T, T] score matrix, RoPE by complex rotation of the pairs."""
    p = {k: np.asarray(v, np.float64) for k, v in weights["layer_4"].items()}
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
        a = (prob / prob.sum(-1, keepdims=True)) @ kv[:, i, dn:]
        heads.append(a / (1 + np.exp(-(u[0] @ p["w_g"][:, i])))[:, None])
    want = np.concatenate(heads, -1) @ p["w_o"]
    with jax.default_matmul_precision("highest"):
        got = ref.latent_attention(jnp.asarray(u, jnp.float32), weights["layer_4"], CFG,
                                   jnp.float32, lambda x: x)
        dropped = ref.latent_attention(jnp.asarray(u, jnp.float32), weights["layer_4"],
                                       dict(CFG, reference_drops_shared_key=True), jnp.float32,
                                       lambda x: x)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-5)
    assert float(jnp.max(jnp.abs(dropped - got))) > 1e-3


@pytest.mark.parametrize("flag", ref.FLAGS)
def test_each_control_of_this_configuration_moves_q(weights, flag):
    obs = jax.random.randint(jax.random.PRNGKey(5), (4, *CFG["obs_shape"]), 0, 256).astype(jnp.uint8)
    with jax.default_matmul_precision("highest"):
        q, loads = ref.forward(weights, obs, CFG)
        other, _ = ref.forward(weights, obs, dict(CFG, **{flag: True}))
    assert q.shape == (4, 6) and loads.shape == (7, CFG["router_outputs"])
    assert float(jnp.sum(loads[0])) == 0.0                       # the leading dense layer routes nothing
    assert float(jnp.max(jnp.abs(other - q))) > 1e-2 * float(jnp.std(q))


def test_the_bounded_gate_stays_above_its_bound(weights):
    """The log decay of the reference's linear layer, driven to both ends."""
    p = weights["layer_0"]
    u = 50.0 * jax.random.normal(jax.random.PRNGKey(4), (2, 40, CFG["hidden_size"]))
    f = (u @ p["w_f"]).reshape(2, 40, 4, 16) + p["dt_bias"].reshape(4, 16)
    g = CFG["kda_lower_bound"] * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f)
    assert -5.0 <= float(jnp.min(g)) < -4.5 and -0.01 < float(jnp.max(g)) <= 0.0
    with jax.default_matmul_precision("highest"):
        bounded = ref.linear_attention(u, p, CFG, jnp.float32, lambda x: x)
        other = ref.linear_attention(u, p, dict(CFG, reference_unbounded_gate=True), jnp.float32,
                                     lambda x: x)
    assert bool(jnp.all(jnp.isfinite(bounded))) and float(jnp.max(jnp.abs(bounded - other))) > 1e-3


def test_parameter_maps_are_inverse(weights):
    program = ref.to_program_params(weights, CFG, jnp.bfloat16)
    for path, leaf in jax.tree_util.tree_leaves_with_path(program):
        always = path[-1].key in ref.FLOAT32_ALWAYS + ("router", "expert_bias")
        assert leaf.dtype == (jnp.float32 if always else jnp.bfloat16), jax.tree_util.keystr(path)
    back = ref.from_program_params(ref.to_program_params(weights, CFG), CFG)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(weights)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(weights)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref.layer_runs(PUBLISHED) == [(0, 1), (1, 3), (4, 1), (5, 2)]
    assert ref.layer_kinds(PUBLISHED) == [("linear_attention", "dense")] + [
        ("linear_attention", "moe")] * 3 + [("latent_attention", "moe")] + [
        ("linear_attention", "moe")] * 2
    assert ref.param_count(CFG) == sum(x.size for x in jax.tree_util.tree_leaves(weights))
    assert "expert_bias" not in weights["layer_0"] and "router" in weights["layer_1"]


@pytest.fixture(scope="module")
def toy_run():
    cfg, traffic = _toy_config(), _toy_traffic()
    drv = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    inputs, shots = drv.check_shots(cfg, traffic, 2**31 + 9)
    counts, got, reference = drv.base.program_numbers(cfg, float(traffic["beta"]), inputs, shots)
    return drv, cfg, float(traffic["beta"]), inputs, shots, counts, got, reference


def test_the_program_passes_at_the_toy_size(toy_run):
    _, _, _, _, _, counts, got, _ = toy_run
    assert counts == dict.fromkeys(counts, 0)
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


def test_published_configuration_builds_abstractly():
    """The cell's network at its published widths and its share of heads and
    experts: the program's parameter tree, made abstractly, holds the
    reference's and the count's parameters."""
    from ape_x_dqn_tpu.models.dueling import build_network

    cfg = PUBLISHED
    net = build_network(cfg["network"], cfg["num_actions"], torso=cfg,
                        channels=tuple(cfg["channels"]), hidden=cfg["hidden"])
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    held = cfg["experts_held"][1]
    assert n == ref.param_count(cfg) == ops.param_count(cfg) == {16: 763_253_219, 8: 480_138_723}[held]
    linear = shapes["params"]["layers_1_3"]["linear_attention"]
    assert linear["w_q"].shape == (3, 2560, 1024) and linear["conv_k"].shape == (3, 1024, 4)
    assert linear["w_f"].shape == linear["w_g"].shape == (3, 2560, 1024)
    assert linear["A_log"].shape == (3, 8) and "w_f1" not in linear and "b_g" not in linear
    latent = shapes["params"]["layer_4"]["latent_attention"]
    assert {k: v.shape for k, v in latent.items()} == {
        "w_q": (2560, 8 * 192), "w_dkv": (2560, 576), "kv_norm": (512,), "w_ukv": (512, 8 * 256),
        "w_g": (2560, 8), "w_o": (1024, 2560)}
    assert shapes["params"]["layer_0"]["dense"]["w1"].shape == (2560, 6144)
    moe = shapes["params"]["layers_5_6"]["moe"]
    assert moe["router"].shape == (2, 2560, 512) and moe["w13"].shape == (2, held, 2560, 1536)
    assert shapes["params"]["layer_4"]["shared_expert"]["w2"].shape == (768, 2560)
    assert net.tokens_of((1, 84, 84, 32)) == 1568 == ops.tokens_per_sample(cfg)
    assert net.delta_metrics((8, 84, 84, 32)) == {
        "chunks": 8 * 6 * 25.0, "tokens_padded": 8 * 6 * 1600.0, "tokens": 8 * 6 * 1568.0}
    assert net.attention_metrics((8, 84, 84, 32)) == {
        "pairs_in_mask_latent": 8 * 1_230_096.0, "pairs_computed_latent": 8 * 28 * 128 * 512.0,
        "blocks_visited_latent": 8 * 8 * 28.0, "blocks_total_latent": 8 * 8 * 13 * 4.0}

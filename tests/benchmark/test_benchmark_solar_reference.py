"""``reference/solar2_q.py``: the literal delta-rule recurrence and its two
controls, the parameter maps, the controls of the comparison at a toy size on
the CPU, and the configuration built abstractly."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest as mf
import ops_count_solar2_q as ops
from reference import solar2_q as ref
from test_benchmark_solar_cell import TOY_LIMITS, _toy_config, _toy_traffic

PUBLISHED = mf.load_json(os.path.join(mf.HERE, "configs", "solar2_q_ep40.json"))
CFG = dict(_toy_config(), obs_shape=[44, 60, 5], batch_size=4)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(jax.random.PRNGKey(11), CFG)


def _inputs(tokens=12, rows=2, heads=3, kw=4, vw=5):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, tokens, heads, kw)))
    k = unit(jax.random.normal(ks[1], (rows, tokens, heads, kw)))
    v = jax.random.normal(ks[2], (rows, tokens, heads, vw))
    g = -jax.random.uniform(ks[3], (rows, tokens, heads, kw))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, tokens, heads)))
    return q, k, v, g, beta


def test_the_recurrence_is_stepped_a_token_at_a_time_and_the_controls_differ():
    """Against a Python loop over the tokens with the transition written as a
    matrix, ``(I - beta k k^T) Diag(exp(g))``; with ``reset_every`` the output
    is that of each stretch run alone from a zero state; with ``drop_delta``
    that of plain gated linear attention."""
    q, k, v, g, beta = _inputs()
    state, want, plain_state, plain = np.zeros((2, 3, 4, 5)), [], np.zeros((2, 3, 4, 5)), []
    for t in range(12):
        kt, bt = np.asarray(k[:, t], np.float64), np.asarray(beta[:, t], np.float64)
        decay = np.exp(np.asarray(g[:, t], np.float64))
        move = np.eye(4) - bt[..., None, None] * kt[..., :, None] * kt[..., None, :]
        write = (bt[..., None] * kt)[..., None] * np.asarray(v[:, t], np.float64)[..., None, :]
        state = np.einsum("bhij,bhjv->bhiv", move, decay[..., None] * state) + write
        plain_state = decay[..., None] * plain_state + write
        want.append(np.einsum("bhk,bhkv->bhv", np.asarray(q[:, t]), state))
        plain.append(np.einsum("bhk,bhkv->bhv", np.asarray(q[:, t]), plain_state))
    got = ref.recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(got), np.stack(want, 1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.recurrence(q, k, v, g, beta, drop_delta=True)),
                               np.stack(plain, 1), atol=1e-5)
    forgot = ref.recurrence(q, k, v, g, beta, reset_every=4)
    apart = jnp.concatenate([ref.recurrence(*(x[:, s:s + 4] for x in (q, k, v, g, beta)))
                             for s in (0, 4, 8)], axis=1)
    np.testing.assert_allclose(np.asarray(forgot), np.asarray(apart), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(forgot[:, :4]), np.asarray(got[:, :4]))
    assert float(jnp.max(jnp.abs(forgot[:, 4:] - got[:, 4:]))) > 1e-3
    assert ref._segment(1568) == 224 and ref._segment(40) == 40 and ref._segment(257) == 1


@pytest.mark.parametrize("flag", ["reference_resets_state", "reference_drops_delta"])
def test_each_control_of_this_mechanism_moves_q(weights, flag):
    obs = jax.random.randint(jax.random.PRNGKey(5), (4, *CFG["obs_shape"]), 0, 256).astype(jnp.uint8)
    with jax.default_matmul_precision("highest"):
        q, loads = ref.forward(weights, obs, CFG)
        other, _ = ref.forward(weights, obs, dict(CFG, **{flag: True}))
        rows, _ = ref.forward(weights, obs, CFG, row_block=4)      # all rows at once: the same
    assert q.shape == (4, 6) and loads.shape == (4, CFG["router_outputs"])
    np.testing.assert_allclose(np.asarray(rows), np.asarray(q), atol=1e-5)
    assert float(jnp.max(jnp.abs(other - q))) > 1e-2 * float(jnp.std(q))


def test_parameter_maps_are_inverse(weights):
    program = ref.to_program_params(weights, CFG, jnp.bfloat16)
    for path, leaf in jax.tree_util.tree_leaves_with_path(program):
        always = path[-1].key in ref.FLOAT32_ALWAYS + ("router", "expert_bias")
        assert leaf.dtype == (jnp.float32 if always else jnp.bfloat16), jax.tree_util.keystr(path)
    back = ref.from_program_params(ref.to_program_params(weights, CFG), CFG)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(weights)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(weights)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref.layer_runs(PUBLISHED) == [(0, 1), (1, 3)]
    assert ref.layer_kinds(PUBLISHED) == ["full_attention"] + ["linear_attention"] * 3
    assert ref.param_count(CFG) == sum(x.size for x in jax.tree_util.tree_leaves(weights))


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


# (control, the number it is the control of in the limits file)
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
    # every control moves all three numbers off a replay of the reference itself
    same = drv.base.control_numbers(cfg, beta, inputs, shots, reference)
    assert all(v == 0.0 for v in same.values()) and all(v > 0 for v in numbers.values())


def test_published_configuration_builds_abstractly():
    """The cell's network at its published widths and its share of heads and
    experts: the program's parameter tree, made abstractly, holds the
    reference's and the count's 708,979,043."""
    from ape_x_dqn_tpu.models.dueling import build_network

    cfg = PUBLISHED
    net = build_network(cfg["network"], cfg["num_actions"], torso=cfg,
                        channels=tuple(cfg["channels"]), hidden=cfg["hidden"])
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == ref.param_count(cfg) == ops.param_count(cfg) == 708_979_043
    linear = shapes["params"]["layers_1_3"]["linear_attention"]
    assert linear["w_q"].shape == (3, 4096, 2048) and linear["conv_k"].shape == (3, 2048, 4)
    assert linear["w_f1"].shape == (3, 4096, 128) and linear["A_log"].shape == (3, 16)
    full = shapes["params"]["layer_0"]["full_attention"]
    assert full["w_q"].shape == (4096, 2048) and full["w_k"].shape == (4096, 256)
    assert full["w_g"].shape == (4096, 2048)
    moe = shapes["params"]["layers_1_3"]["moe"]
    assert moe["router"].shape == (3, 4096, 320) and moe["w13"].shape == (3, 8, 4096, 2560)
    assert net.tokens_of((1, 84, 84, 32)) == 1568 == ops.tokens_per_sample(cfg)
    assert net.delta_metrics((8, 84, 84, 32)) == {
        "chunks": 8 * 3 * 25.0, "tokens_padded": 8 * 3 * 1600.0, "tokens": 8 * 3 * 1568.0}

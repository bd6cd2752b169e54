"""``reference/nemotron3s_q.py``: the router's choice of 22 against a stable
sort in numpy, the recurrence in groups against a head at a time in numpy, the
gated norm a group, the experts' rule, the parameter maps, the controls of the
comparison at a toy size on the CPU under the collecting driver (the program
passes, each control moves its number, the five flags ride as controls), and
the configuration built abstractly at its 699 M parameters.  The program
against the reference on seeded weights (forward, loss, gradients, priorities,
one learner step, every flag moving Q) is the contract's,
``tests/test_nemotron_h.py``."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest as mf
import ops_count_nemotron3s_q as ops
from reference import nemotron3s_q as ref
from test_benchmark_nemotron_cell import TOY_LIMITS, _toy_config, _toy_traffic

PUBLISHED = mf.load_json(os.path.join(mf.HERE, "configs", "nemotron3s_q_ep32.json"))
CFG = dict(_toy_config(), obs_shape=[44, 60, 5], batch_size=4)


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda k: ref.make_weights(k, CFG))(jax.random.PRNGKey(11))


def test_the_router_takes_the_largest_biased_scores_and_weighs_by_the_scores():
    """64 outputs, 22 chosen, against a stable sort a token in numpy: the bias
    chooses and does not weigh, the gates are 5 s / (the chosen s' sum), and of
    two equal biased scores the earlier output is taken."""
    cfg = dict(num_experts_per_tok=22, routed_scaling_factor=5.0)
    rng = np.random.default_rng(0)
    scores = rng.uniform(0.05, 0.95, (7, 64)).astype(np.float32)
    bias = rng.normal(0, 0.3, 64).astype(np.float32)
    scores[0, 9] = scores[0, 40] = 0.97                                # a tie on token 0, among the chosen
    bias[9] = bias[40] = 0.0
    chosen, gates = ref.route(jnp.asarray(scores), jnp.asarray(bias), cfg)
    for t in range(7):
        want = np.argsort(-(scores[t] + bias), kind="stable")[:22]
        assert list(np.asarray(chosen[t])) == list(want)
        np.testing.assert_allclose(np.asarray(gates[t]), 5.0 * scores[t, want] / scores[t, want].sum(),
                                   rtol=1e-6)
    both = list(np.asarray(chosen[0]))
    assert 9 in both and 40 in both and both.index(9) < both.index(40)
    plain = ref.route(jnp.asarray(scores), jnp.asarray(bias), dict(cfg, reference_unscaled_gates=True))[1]
    np.testing.assert_allclose(np.asarray(gates), 5.0 * np.asarray(plain), rtol=1e-6)


def test_the_recurrence_reads_b_and_c_by_group():
    """Four heads in two groups against the recurrence of each head alone in
    numpy, float64: head h reads group h // 2."""
    rng = np.random.default_rng(1)
    t, heads, p, n = 12, 4, 3, 5
    x, b, c = rng.normal(size=(1, t, heads, p)), rng.normal(size=(1, t, 2, n)), rng.normal(size=(1, t, 2, n))
    dt, a, d = rng.uniform(0.01, 0.3, (1, t, heads)), -rng.uniform(1, 4, heads), rng.normal(size=heads)
    got = ref.recurrence(*(jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c, d)))
    for h in range(heads):
        state, g = np.zeros((p, n)), h // 2
        for i in range(t):
            state = np.exp(dt[0, i, h] * a[h]) * state + dt[0, i, h] * np.outer(x[0, i, h], b[0, i, g])
            np.testing.assert_allclose(np.asarray(got[0, i, h]), state @ c[0, i, g] + d[h] * x[0, i, h],
                                       rtol=2e-4, atol=2e-5)


def test_the_mamba_layer_norms_a_group_and_the_experts_square_a_relu(weights):
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
    ident = lambda x: x  # noqa: E731
    with jax.default_matmul_precision("highest"):
        p = weights["layer_0"]
        # the held heads' and groups' counts are the weights': two groups of two heads of 16
        assert ref.sizes(CFG) == dict(heads=4, head_dim=16, inner=64, groups=2, state=16, mixed=128,
                                      shared=32)
        # with W_out the identity and the norm's weight one, the output's mean square is 1 a group
        eye = dict(p, w_out=jnp.eye(64), norm=jnp.ones(64))
        y = ref.mamba(u, eye, CFG, jnp.float32, ident).reshape(2, 40, 2, 32)
        np.testing.assert_allclose(np.asarray(jnp.mean(y * y, -1)), 1.0, rtol=2e-2)   # eps 1e-5 under the root
        whole = ref.mamba(u, eye, dict(CFG, reference_norms_all_channels=True), jnp.float32, ident)
        np.testing.assert_allclose(np.asarray(jnp.mean(whole * whole, -1)), 1.0, rtol=2e-2)
        assert float(jnp.max(jnp.abs(whole.reshape(y.shape) - y))) > 0.05
        # an expert: two matrices, relu squared between them, in the latent
        e = weights["layer_1"]
        v = u @ e["w_down"]
        want = jnp.square(jnp.maximum(v @ e["w1"][0], 0.0)) @ e["w2"][0]
        only = dict(CFG, num_experts_per_tok=1, routed_scaling_factor=1.0, experts_held=[4, 5])
        bias = jnp.full((16,), -10.0).at[4].set(10.0)              # every token takes expert 4 alone
        got, load = ref.routed(u, dict(e, expert_bias=bias, w1=e["w1"][:1], w2=e["w2"][:1]), only,
                               jnp.float32, ident)
        assert float(load[4]) == 80.0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want @ e["w_up"]), atol=1e-5)


def test_parameter_maps_are_inverse(weights):
    program = ref.to_program_params(weights, CFG)
    back = ref.from_program_params(program, CFG)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(weights)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(weights)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    p = program["params"]
    assert set(p) >= {"layers_0_1", "layer_2", "layer_3"}                 # seven layers as four blocks
    assert ref.blocks(CFG) == [(0, "mamba", 1), (2, "mamba", 3), (4, "mamba", None), (5, "attention", 6)]
    assert ref.block_runs(CFG) == [(0, 2), (2, 1), (3, 1)]
    low = ref.to_program_params(weights, CFG, jnp.bfloat16)["params"]
    for name in ref.FLOAT32_ALWAYS:
        assert low["layer_2"]["mamba"][name].dtype == jnp.float32
    assert low["layer_3"]["moe"]["router"].dtype == jnp.float32
    assert low["layer_3"]["moe"]["w1"].dtype == jnp.bfloat16


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
def test_each_control_moves_its_number(toy_run, control, number):
    drv, cfg, beta, inputs, shots, _, got, reference = toy_run
    precision, shift = drv.base.CONTROLS[control]
    numbers = drv.base.control_numbers(cfg, beta, inputs, shots, reference, precision, shift)
    assert numbers[number] > TOY_LIMITS[number] and numbers[number] > 2.5 * got[number], numbers
    assert all(v > 0 for v in numbers.values())


def test_the_flags_ride_as_controls_of_the_collecting_driver(toy_run):
    """``check_nemotron_controls.py`` rebinds ``check_flag_control.FLAGS`` to
    this reference's five, and each then stands among the driver's controls as
    a precision of its own name; the router reading the latent, in the
    program's place, moves all three numbers at the toy size."""
    import check_flag_control
    import check_nemotron_controls

    assert check_nemotron_controls.FLAGS == ref.FLAGS
    drv, cfg, beta, inputs, shots, _, got, reference = toy_run
    before_flags, before = check_flag_control.FLAGS, dict(drv.base.CONTROLS)
    check_flag_control.FLAGS = check_nemotron_controls.FLAGS
    try:
        with check_flag_control.flags_as_controls(drv.base, list(ref.FLAGS)) as base:
            assert list(base.CONTROLS) == list(ref.FLAGS)
            numbers = base.control_numbers(cfg, beta, inputs, shots, reference,
                                           *base.CONTROLS["reference_router_reads_latent"])
            assert all(numbers[name] > 2 * got[name] for name in got), (numbers, got)
    finally:
        check_flag_control.FLAGS = before_flags
    assert drv.base.CONTROLS == before


def test_published_configuration_builds_abstractly():
    """The cell's network at its published widths and its share of heads,
    columns and experts: the program's parameter tree, made abstractly, holds
    the reference's and the count's parameters."""
    from ape_x_dqn_tpu.models.dueling import build_network

    cfg = PUBLISHED
    net = build_network(cfg["network"], cfg["num_actions"], torso=cfg,
                        channels=tuple(cfg["channels"]), hidden=cfg["hidden"])
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == ref.param_count(cfg) == ops.param_count(cfg) == 698_953_875
    p = shapes["params"]
    assert set(p) >= {"layers_0_3", "layer_4", "layer_5"}
    assert {k: v.shape for k, v in p["layers_0_3"]["mamba"].items()} == {
        "w_in": (4, 4096, 2048 + 2560 + 32), "conv_kernel": (4, 2560, 4), "conv_bias": (4, 2560),
        "A_log": (4, 32), "dt_bias": (4, 32), "D": (4, 32), "norm": (4, 2048), "w_out": (4, 2048, 4096)}
    assert {k: v.shape for k, v in p["layers_0_3"]["moe"].items()} == {
        "router": (4, 4096, 512), "expert_bias": (4, 512), "w1": (4, 16, 1024, 2688),
        "w2": (4, 16, 2688, 1024), "w_down": (4, 4096, 1024), "w_up": (4, 1024, 4096)}
    assert {k: v.shape for k, v in p["layers_0_3"]["shared_expert"].items()} == {
        "w1": (4, 4096, 1344), "w2": (4, 1344, 4096)}
    assert set(p["layer_4"]) == {"operator_norm", "mamba"}
    assert {k: v.shape for k, v in p["layer_5"]["attention"].items()} == {
        "w_q": (4096, 1024), "w_k": (4096, 128), "w_v": (4096, 128), "w_o": (1024, 4096)}
    assert net.tokens_of((1, 84, 84, 32)) == 1568 == ops.tokens_per_sample(cfg)
    # 13 chunks of 128, 1,664 tokens walked for 1,568, in each of the five Mamba-2 layers
    assert net.scan_metrics((8, 84, 84, 32)) == {
        "chunks": 5 * 8 * 13.0, "tokens_padded": 5 * 8 * 1664.0, "tokens": 5 * 8 * 1568.0}
    assert net.delta_metrics((8, 84, 84, 32)) is None
    attention = net.attention_metrics((8, 84, 84, 32))
    assert attention["pairs_in_mask_full"] == 8 * 1_230_096.0
    assert attention["blocks_total_full"] % (8 * 8) == 0     # eight rows of eight held query heads

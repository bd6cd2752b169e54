"""``reference/granite_h_q.py`` and ``ops_count_granite_h_q.py``: the literal
recurrence and its control, the parameter maps, the controls of the
comparison at a toy size on the CPU, the configuration built abstractly, and
the operation count against hand counts."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest as mf
import ops_count_granite_h_q as ops
from reference import granite_h_q as ref
from test_benchmark_granite_cell import TOY_LIMITS, _toy_config, _toy_traffic

PUBLISHED = mf.load_json(os.path.join(mf.HERE, "configs", "granite4h_q_l10.json"))
CFG = dict(_toy_config(), obs_shape=[44, 60, 5], batch_size=4)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(jax.random.PRNGKey(11), CFG)


def test_the_recurrence_is_stepped_a_token_at_a_time_and_the_control_forgets():
    """Against a Python loop over the tokens; with ``reset_every`` the output
    is that of each stretch run alone from a zero state."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (2, 12, 3, 4))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 12, 3)))
    a = -jnp.exp(jax.random.normal(ks[2], (3,)))
    b, c = (jax.random.normal(k, (2, 12, 5)) for k in ks[3:5])
    d = jax.random.normal(ks[5], (3,))
    state, want = np.zeros((2, 3, 4, 5)), []
    for t in range(12):
        state = (np.exp(np.asarray(dt[:, t] * a))[..., None, None] * state
                 + np.asarray(dt[:, t, :, None] * x[:, t])[..., None] * np.asarray(b[:, t])[:, None, None])
        want.append(np.einsum("bhpn,bn->bhp", state, np.asarray(c[:, t])) + np.asarray(d[:, None] * x[:, t]))
    got = ref.recurrence(x, dt, a, b, c, d)
    np.testing.assert_allclose(np.asarray(got), np.stack(want, 1), atol=1e-5)
    forgot = ref.recurrence(x, dt, a, b, c, d, reset_every=4)
    apart = jnp.concatenate([ref.recurrence(*(v[:, s:s + 4] for v in (x, dt)), a,
                                            b[:, s:s + 4], c[:, s:s + 4], d)
                             for s in (0, 4, 8)], axis=1)
    np.testing.assert_allclose(np.asarray(forgot), np.asarray(apart), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(forgot[:, :4]), np.asarray(got[:, :4]))
    assert float(jnp.max(jnp.abs(forgot[:, 4:] - got[:, 4:]))) > 1e-3
    assert ref._segment(1568) == 224 and ref._segment(40) == 40 and ref._segment(257) == 1


def test_the_control_of_this_mechanism_moves_q(weights):
    obs = jax.random.randint(jax.random.PRNGKey(5), (4, *CFG["obs_shape"]), 0, 256).astype(jnp.uint8)
    with jax.default_matmul_precision("highest"):
        q, none = ref.forward(weights, obs, CFG)
        lost, _ = ref.forward(weights, obs, dict(CFG, reference_resets_state=True))
        rows, _ = ref.forward(weights, obs, CFG, row_block=4)      # all rows at once: the same
    assert none is None and q.shape == (4, 6)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(q), atol=1e-5)
    assert float(jnp.max(jnp.abs(lost - q))) > 1e-3 * float(jnp.std(q))


def test_parameter_maps_are_inverse(weights):
    program = ref.to_program_params(weights, CFG, jnp.bfloat16)
    for path, leaf in jax.tree_util.tree_leaves_with_path(program):
        always = path[-1].key in ref.FLOAT32_ALWAYS
        assert leaf.dtype == (jnp.float32 if always else jnp.bfloat16), jax.tree_util.keystr(path)
    back = ref.from_program_params(ref.to_program_params(weights, CFG), CFG)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(weights)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(weights)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref.layer_runs(PUBLISHED) == [(0, 5), (5, 1), (6, 4)]
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


# (control, the number it is the control of in the limits file, what else it moves)
@pytest.mark.parametrize("control,number", [
    ("gather_one_row_on", "fused_priority_rel"),
    ("fp8_activations", "fused_priority_median_rel"),
    ("bf16_held", "fused_update_rel"),
])
def test_each_control_reads_over_its_limit(toy_run, control, number):
    drv, cfg, beta, inputs, shots, _, got, reference = toy_run
    precision, shift = drv.base.CONTROLS[control]
    numbers = drv.base.control_numbers(cfg, beta, inputs, shots, reference, precision, shift)
    assert numbers[number] > TOY_LIMITS[number] and numbers[number] > 2.5 * got[number], numbers
    # every control moves all three numbers off a replay of the reference itself
    same = drv.base.control_numbers(cfg, beta, inputs, shots, reference)
    assert all(v == 0.0 for v in same.values()) and all(v > 0 for v in numbers.values())


def test_published_configuration_builds_abstractly():
    """The cell's network at its published widths: the program's parameter
    tree, made abstractly, holds the reference's and the count's 748,781,171."""
    from ape_x_dqn_tpu.models.dueling import build_network

    cfg = PUBLISHED
    net = build_network(cfg["network"], cfg["num_actions"], torso=cfg,
                        channels=tuple(cfg["channels"]), hidden=cfg["hidden"])
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == ref.param_count(cfg) == ops.param_count(cfg) == 748_781_171
    mamba = shapes["params"]["layers_0_4"]["mamba"]
    assert mamba["w_in"].shape == (5, 2048, 8512) and mamba["conv_kernel"].shape == (5, 4352, 4)
    assert shapes["params"]["layers_6_9"]["mamba"]["w_out"].shape == (4, 4096, 2048)
    assert shapes["params"]["layer_5"]["attention"]["w_k"].shape == (2048, 512)
    assert net.tokens_of((1, 84, 84, 32)) == 1568 == ops.tokens_per_sample(cfg)
    assert net.scan_metrics((8, 84, 84, 32)) == {
        "chunks": 8 * 9 * 7.0, "tokens_padded": 8 * 9 * 1792.0, "tokens": 8 * 9 * 1568.0}


def test_operation_count_against_hand_counts():
    cfg = PUBLISHED
    # ISSUE 34's arithmetic: in_proj 2048 x 8512, conv 4352 x 4 + 4352, A_log, D, dt_bias 192,
    # gated norm 4096, out_proj 4096 x 2048, SwiGLU 3 x 2048 x 8192, two norms 4096
    mamba = 17_432_576 + 21_760 + 192 + 4_096 + 8_388_608 + 50_331_648 + 4_096
    attention = 2 * 4_194_304 + 2 * 1_048_576 + 50_331_648 + 4_096
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert ops.layer_param_count(cfg, "mamba") == mamba
    assert ops.layer_param_count(cfg, "attention") == attention
    assert sum(ops.layer_param_count(cfg, op) for op in ops.layer_kinds(cfg)) == 746_468_288
    assert ops.pairs_in_mask(cfg) == 1_230_096
    assert ops.pairs_in_chunks(cfg) == 6 * (256 * 257 // 2) + 32 * 33 // 2 == 197_904
    per_token = ops.macs_per_token(cfg)
    assert per_token["mixer"] == 9 * (17_432_576 + 8_388_608) + 10_485_760
    assert per_token["dense_ffn"] == 10 * 50_331_648 and per_token["tokens"] == 64 * 2048
    # the scan a layer: scores over 128 and 64 heads x 64 over the in-chunk pairs, C S and x B^T a token
    assert ops.scan_macs_per_sample(cfg) == 9 * (197_904 * (128 + 4096) + 1568 * 2 * 4096 * 128)
    assert ops.attention_macs_per_sample(cfg) == 2 * 64 * 32 * 1_230_096
    forward = ops.forward_flops_per_sample(cfg)
    assert forward / 1568 == pytest.approx(1527.5e6, rel=2e-3)        # the issue's 1,527.5 MFLOP a token
    assert ops.step_flops(cfg) == pytest.approx(95.8e12, rel=1e-3)
    assert ops.flops_per_sample(cfg) == 5 * forward - ops.stem_and_head_flops(cfg)[2]
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    floor, bound = ops.scan_floor_s(cfg, peaks)
    a_pass = 9 * 1568 * ((2 * 4096 + 2 * 128) * 2 + 64 * 4)
    assert bound == "bandwidth" and floor == pytest.approx(5 * 8 * a_pass / 819e9)
    assert 5 * 2 * ops.scan_macs_per_sample(cfg) * 8 / 197e12 == pytest.approx(0.0091, rel=0.01)
    floor, bound = ops.attention_floor_s(cfg, peaks, "full")
    assert bound == "compute" and floor == pytest.approx(
        5 * 4 * 64 * 32 * 1_230_096 * 8 / 197e12)
    with pytest.raises(ValueError):
        ops.attention_floor_s(cfg, peaks, "window")

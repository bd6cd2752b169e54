"""The program's Laguna Q-network against ``reference/laguna_q.py`` on seeded
float32 weights at a small size (hidden 64, 6 sliding and 4 full heads over 2
key-value heads of 16, window 8, 40 tokens over 5 frames of 8 positions, 16
experts of which 4 held, 3 a token, a shared expert), the configuration
built abstractly, and the operation count against hand counts."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest as mf
import ops_count_laguna_q as ops
from correctness import NU0
from reference import laguna_q as ref

PUBLISHED = mf.load_json(os.path.join(mf.HERE, "configs", "laguna_q_ep32.json"))
CFG = dict(
    PUBLISHED,
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_key_value_heads=2, head_dim=16, sliding_window=8,
    num_attention_heads_per_layer=[4, 6, 6, 6] * 12, num_experts=4, router_outputs=16,
    experts_held=[0, 4], num_experts_per_tok=3, obs_shape=[44, 60, 5], hidden=32,
    channels=[8, 8, 8], batch_size=4, num_actions=6,
)


def program_net(cfg, compute=jnp.float32):
    from ape_x_dqn_tpu.models.dueling import build_network

    return build_network("laguna_moe", cfg["num_actions"], torso=cfg,
                         channels=tuple(cfg["channels"]), hidden=cfg["hidden"],
                         compute_dtype=compute, param_dtype=jnp.float32)


def observations(key, cfg, rows=4):
    return jax.random.randint(key, (rows, *cfg["obs_shape"]), 0, 256).astype(jnp.uint8)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(jax.random.PRNGKey(11), CFG)


def test_the_small_size_is_the_issues():
    net = program_net(CFG)
    assert net.tokens_of((1, *CFG["obs_shape"])) == 40 == ops.tokens_per_sample(
        dict(CFG, obs_shape=[44, 44, 10]))  # 4 positions x 10 frames: the count is square-only
    kinds = ref.layer_kinds(CFG)
    assert [k[0] for k in kinds] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert [k[1] for k in kinds] == ["dense", "moe", "moe", "moe", "moe"]
    assert [k[2] for k in kinds] == [4, 6, 6, 6, 4]


# float32: both sides compute the same sums in another order (the program's
# attention in blocks with a running softmax); 1e-4 of |Q| is float32 rounding
# through 5 layers.  Stated precision: bfloat16 activations against float32,
# and a token whose router scores tie to within that flips an expert of 16.
@pytest.mark.parametrize("compute,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 0.3)])
def test_forward_q_matches_the_reference(weights, compute, tol):
    obs = observations(jax.random.PRNGKey(5), CFG)
    with jax.default_matmul_precision("highest"):
        want, loads = ref.forward(weights, obs, CFG)
        got, sown = program_net(CFG, compute).apply(
            ref.to_program_params(weights, CFG), obs, mutable=["routing"])
    scale = float(jnp.std(want)) + float(jnp.mean(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got[2] - want))) <= tol * scale
    if compute == jnp.float32:  # the same pairs on every one of the router's outputs
        counted = np.concatenate([np.asarray(v).reshape(-1, 16)
                                  for v in jax.tree_util.tree_leaves(sown["routing"])])
        assert counted.shape == (4, 16) and counted.sum() == 4 * 4 * 40 * 3
        # the program holds layers 1-3 stacked and layer 4 apart; sorted by name
        np.testing.assert_array_equal(np.sort(counted, 0), np.sort(np.asarray(loads), 0))


def test_loss_gradients_match_the_reference(weights):
    """Gradients of sum(Q^2) in float32, leaf by leaf in the reference's
    names: 1e-3 relative to each leaf's norm."""
    obs = observations(jax.random.PRNGKey(6), CFG)
    net = program_net(CFG)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda w: jnp.sum(ref.forward(w, obs, CFG)[0] ** 2))(weights)
        got = jax.grad(lambda p: jnp.sum(net.apply(p, obs)[2] ** 2))(
            ref.to_program_params(weights, CFG))
    got = ref.from_program_params(got, CFG)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * float(jnp.linalg.norm(b)) + 1e-7, name
        assert float(jnp.linalg.norm(b)) > 0, name


def test_one_learner_step_matches_the_reference(weights):
    """Loss, TD errors, priorities and the parameters after one RMSProp step
    of the program's train step, float32 compute, against ``learner_step``."""
    from ape_x_dqn_tpu.learner.train_step import build_train_step, make_optimizer
    from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch, TrainState

    drv = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed_by_name.py"),
                         "bench_driver_learner_feed_by_name")
    cfg, k = CFG, jax.random.PRNGKey(21)
    target = jax.tree_util.tree_map(
        lambda w: w + 0.05 * jnp.std(w) * jax.random.normal(k, w.shape), weights)
    batch = dict(obs=observations(jax.random.fold_in(k, 1), cfg),
                 next_obs=observations(jax.random.fold_in(k, 2), cfg),
                 action=jnp.array([0, 5, 2, 3]), reward=jnp.array([1.0, -0.5, 0.0, 2.0]),
                 discount=jnp.full((4,), 0.97), is_weights=jnp.array([1.0, 0.7, 0.4, 0.9]))
    net = program_net(cfg)
    opt = make_optimizer(cfg["optimizer"], learning_rate=cfg["learning_rate"],
                         rmsprop_decay=cfg["rmsprop_decay"], rmsprop_eps=cfg["rmsprop_eps"],
                         max_grad_norm=cfg["max_grad_norm"], second_moment_dtype=jnp.float32)
    own = lambda t: jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), t)  # noqa: E731
    params = own(ref.to_program_params(weights, cfg))    # the step donates its state
    state = TrainState(params=params, target_params=own(ref.to_program_params(target, cfg)),
                       opt_state=drv.warm_second_moment(opt.init(params), NU0),
                       step=jnp.zeros((), jnp.int32), rng=jax.random.PRNGKey(0))
    step = build_train_step(net, opt, loss_kind=cfg["loss"], sync_in_step=False, jit=True)
    nu = jax.tree_util.tree_map(lambda w: jnp.full(w.shape, NU0, jnp.float32), weights)
    with jax.default_matmul_precision("highest"):
        new_state, metrics = step(state, PrioritizedBatch(
            transition=NStepTransition(obs=batch["obs"], action=batch["action"],
                                       reward=batch["reward"], discount=batch["discount"],
                                       next_obs=batch["next_obs"]),
            indices=jnp.arange(4), is_weights=batch["is_weights"]))
        want_w, _nu, _delta, want_prio, want_loss = ref.learner_step(
            weights, target, nu, batch, dict(cfg, precision="stated"))
    assert float(metrics.loss) == pytest.approx(float(want_loss), rel=1e-4)
    np.testing.assert_allclose(np.asarray(metrics.priorities), np.asarray(want_prio), rtol=2e-4)
    got_w = ref.from_program_params(new_state.params, cfg)
    num = den = 0.0
    for a, b, old in zip(*(jax.tree_util.tree_leaves(t) for t in (got_w, want_w, weights))):
        num += float(jnp.sum(jnp.square((a - old) - (b - old))))
        den += float(jnp.sum(jnp.square(b - old)))
    assert den > 0 and np.sqrt(num / den) < 2e-3
    # the counters ride with the step: routing from what the layers sowed,
    # attention from the shapes, both over the three forwards
    assert float(metrics.routing["held_pairs"]) > 0
    assert float(metrics.attention["pairs_in_mask_full"]) == 3 * 4 * 2 * (40 * 41 // 2)
    assert float(metrics.attention["pairs_in_mask_window"]) == 3 * 4 * 3 * (8 * 9 // 2 + 32 * 8)


def test_parameter_maps_are_inverse(weights):
    back = ref.from_program_params(ref.to_program_params(weights, CFG), CFG)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(weights)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("op", ["full_attention", "sliding_attention"])
def test_rope_is_the_references_formula(op):
    """Both rules at the published head size, program layout [B, H, T, D]
    against the reference's [B, T, H, D]; YaRN's blend interpolates the low
    frequencies by the factor and leaves the high ones."""
    from ape_x_dqn_tpu.models.laguna_moe import RopeRule, inverse_frequencies, rope

    rule = PUBLISHED["rope_parameters"][op]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 50, 128), jnp.float32)
    got = rope(x, RopeRule.of(rule, 128))
    want = jnp.swapaxes(ref.rope(jnp.swapaxes(x, 1, 2), rule), 1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    inv = np.asarray(inverse_frequencies(RopeRule.of(rule, 128)))
    if op == "full_attention":
        assert inv.shape == (32,)                                   # half of the head rotates
        plain = 1.0 / 500000 ** (np.arange(0, 64, 2) / 64)
        np.testing.assert_allclose(inv[:9], plain[:9], rtol=1e-6)   # fast dimensions as they are
        np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(got[..., 64:]), np.asarray(x[..., 64:]))
        # position 0 turns nothing: cos is the attention factor alone
        np.testing.assert_allclose(np.asarray(got[:, :, 0, :64]),
                                   np.asarray(x[:, :, 0, :64]) * rule["attention_factor"], rtol=1e-6)
    else:
        np.testing.assert_allclose(inv, 1.0 / 10000 ** (np.arange(0, 128, 2) / 128), rtol=1e-6)


def test_route_is_softmax_top_k_renormalised_and_scaled():
    from ape_x_dqn_tpu.models.expert_torso import route
    from ape_x_dqn_tpu.models.laguna_moe import spec_from_config

    spec = spec_from_config(CFG)
    logits = jax.random.normal(jax.random.PRNGKey(4), (20, 16))
    scores = jax.nn.softmax(logits, -1)
    chosen, gates = route(scores, jnp.zeros(16), spec)
    want_chosen, want_gates = ref.route(scores, CFG)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want_chosen))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.sum(gates, -1)), 2.5, rtol=1e-6)
    top = np.argsort(-np.asarray(logits), -1)[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1), np.sort(top, -1))


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts (held 0-3, 4-7, 8-11, 12-15), added,
    plus the shared expert counted once, equal the uncut reference's whole
    layer: same router, gates normalised over all the chosen experts."""
    from ape_x_dqn_tpu.models.expert_torso import ExpertShare, SwiGLU
    from ape_x_dqn_tpu.models.laguna_moe import spec_from_config

    k = jax.random.PRNGKey(3)
    whole = dict(CFG, num_experts=16, experts_held=[0, 16])
    p = ref.make_weights(k, whole)["layer_1"]
    u = jax.random.normal(jax.random.fold_in(k, 9), (4, 40, CFG["hidden_size"]), jnp.float32)
    ident = lambda x: x  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, load = ref.moe(u, p, whole, jnp.float32, ident)
        shared = SwiGLU(32, jnp.float32, jnp.float32).apply(
            {"params": {n: p["shared_" + n] for n in ("w1", "w3", "w2")}}, u)
        total = shared
        for lo in range(0, 16, 4):
            share = dict(CFG, experts_held=[lo, lo + 4])
            part = {n: (v[lo:lo + 4] if n in ("w1", "w2", "w3") else v) for n, v in p.items()}
            ref_part, share_load = ref.routed(u, part, share, jnp.float32, ident)
            np.testing.assert_array_equal(np.asarray(share_load), np.asarray(load))
            layer = ExpertShare(spec_from_config(share), jnp.float32, jnp.float32)
            got = layer.apply({"params": {
                "router": part["router"], "w2": part["w2"],
                "w13": jnp.concatenate([part["w1"], part["w3"]], -1)}}, u)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref_part), atol=2e-5)
            total = total + got
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=6e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2 and float(jnp.sum(load)) == 4 * 40 * 3


def test_published_configuration_builds_abstractly():
    """At the published widths: the program's parameter tree, mapped to the
    reference's names, has ``weight_shapes``' shapes; 737 M parameters; the
    operation count's parameter count is the same number; every number of
    the catalog's row is in the file under its key but the two cut."""
    cfg = PUBLISHED
    net = program_net(cfg, jnp.bfloat16)
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    assert n == pytest.approx(737e6, rel=0.005)
    assert n == ref.param_count(cfg) == ops.param_count(cfg)
    mapped = jax.eval_shape(lambda p: ref.from_program_params(p, cfg), params)
    assert jax.tree_util.tree_map(lambda x: tuple(x.shape), mapped) == ref.weight_shapes(cfg)
    assert params["params"]["Conv_0"]["kernel"].shape == (8, 8, 1, 32)   # one frame at a time
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256}
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["num_experts_per_tok"], cfg["router_outputs"],
            cfg["moe_routed_scaling_factor"]) == (3072, 12288, 1024, 1024, 128, 8, 512, 10, 256, 2.5)
    assert len(cfg["layer_types"]) == 48 and set(cfg["num_attention_heads_per_layer"]) == {48, 72}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [json.loads(line) for line in open(catalog) if '"Laguna-S-2.1"' in line][0]
        assert {k for k, v in row["config"].items() if cfg.get(k) != v} == {
            "num_hidden_layers", "num_experts"} <= set(cfg["reduced"])


def test_operation_count_against_hand_counts():
    cfg = PUBLISHED
    assert ops.tokens_per_sample(cfg) == 1568
    assert ops.pairs_in_mask(cfg, "full") == 1_230_096
    assert ops.pairs_in_mask(cfg, "window") == 672_000
    # ISSUE 32's table, GFLOP a sample and forward
    per_token = ops.macs_per_token(cfg)
    t = 1568
    assert 2 * t * per_token["mixer"] == pytest.approx(871e9, rel=0.01)
    assert 2 * t * per_token["dense_ffn"] == pytest.approx(355e9, rel=0.01)
    assert 2 * t * per_token["shared_expert"] == pytest.approx(118e9, rel=0.01)
    assert 2 * t * per_token["router"] == pytest.approx(10e9, rel=0.02)
    assert 2 * ops.attention_macs_per_sample(cfg, "full") == 2 * 4 * 128 * 48 * 1_230_096
    assert 2 * ops.attention_macs_per_sample(cfg, "window") == 3 * 4 * 128 * 72 * 672_000
    pairs = ops.expected_pairs_per_step(cfg)
    assert pairs == 3 * 8 * 1568 * 10 * 8 / 256 * 4 == 47_040
    assert ops.step_flops(cfg, pairs) == pytest.approx(5 * 8 * 1.53e12, rel=0.01)  # no recomputation
    peaks = json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"]
    for kind, want in (("full", 5 * 8 * 2 * 4 * 128 * 48 * 1_230_096 / 197e12),
                       ("window", 5 * 8 * 3 * 4 * 128 * 72 * 672_000 / 197e12)):
        seconds, bound = ops.attention_floor_s(cfg, peaks, kind)
        assert bound == "compute" and seconds == pytest.approx(want)
    seconds, bound = ops.expert_floor_s(cfg, peaks, pairs)
    assert bound == "compute" and seconds == pytest.approx(
        2 * 3 * 3072 * 1024 * 47_040 * (5 / 3) / 197e12)


def test_operation_count_matches_xla_on_the_dense_parts():
    """``ops_count_laguna_q`` against XLA's count of the reference's forward at
    the small size, square frames: the reference computes a held expert on
    every token (in a loop over the held ones, whose body XLA counts once)
    and every (query, key) pair, masked or not, so ours is taken at those
    loads; within 10% (XLA adds the elementwise work and RoPE), and ours is
    never the larger."""
    cfg = dict(CFG, obs_shape=[44, 44, 10])   # 4 positions a frame, 40 tokens
    obs = jax.ShapeDtypeStruct((4, *cfg["obs_shape"]), jnp.uint8)
    w = jax.eval_shape(lambda k: ref.make_weights(k, cfg), jax.random.PRNGKey(0))
    xla = jax.jit(lambda w, o: ref.forward_rows(w, o, cfg)[0]).lower(w, obs).cost_analysis()["flops"] / 4
    tokens = ops.tokens_per_sample(cfg)
    n_moe = sum(1 for _, f, _ in ops.layer_kinds(cfg) if f == "moe")
    dense_experts = 2 * tokens * n_moe * ops.expert_macs_per_pair(cfg)  # the loop's body, all tokens
    every_pair = sum(4 * cfg["head_dim"] * h * (tokens * tokens - ops.pairs_in_mask(
        cfg, "full" if op == "full_attention" else "window")) for op, _, h in ops.layer_kinds(cfg))
    ours = ops.dense_flops_per_sample(cfg)[0] + dense_experts + every_pair
    assert ours <= xla
    assert ours == pytest.approx(xla, rel=0.10)

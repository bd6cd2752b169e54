"""The network kind ``ling_hybrid`` against ``benchmark/reference/ling3_q.py`` on
seeded weights, at ``tests/test_ling_hybrid.py``'s small widths on the CPU: Q
values and every gradient leaf, and one learner step (loss, priorities, the
parameters after one RMSProp update, the counters).  Apart from that file so
that the test run's workers share the two (each is minutes long)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_ling_hybrid import CFG, obs, small_net  # puts benchmark/ on sys.path too


def test_the_network_is_the_reference():
    """Forward in float32 (1e-4 of |Q|) and at the stated precision; the
    gradients of sum(Q^2) leaf by leaf, 1e-3 of each leaf's norm; every
    mechanism flag of the reference moves Q."""
    from reference import ling3_q as ref

    weights = ref.make_weights(jax.random.PRNGKey(11), CFG)
    x = obs(jax.random.PRNGKey(5), rows=4)
    with jax.default_matmul_precision("highest"):
        want, loads = ref.forward(weights, x, CFG)
        assert loads.shape == (4, 16) and [float(v) for v in jnp.sum(loads, -1)] == [0.0] + [4 * 40 * 2.0] * 3
        scale = float(jnp.std(want)) + float(jnp.mean(jnp.abs(want)))
        for compute, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 0.5)):
            got = small_net(compute).apply(ref.to_program_params(weights, CFG), x)[2]
            assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, compute
        for flag in ref.FLAGS:
            other, _ = ref.forward(weights, x, dict(CFG, **{flag: True}))
            assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * scale, flag
        net = small_net()
        wanted = jax.grad(lambda w: jnp.sum(ref.forward(w, x, CFG)[0] ** 2))(weights)
        got = ref.from_program_params(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2))(
            ref.to_program_params(weights, CFG)), CFG)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(wanted)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * float(jnp.linalg.norm(b)) + 1e-7, name
        assert float(jnp.linalg.norm(b)) > 0 or "expert_bias" in name, name


def test_one_learner_step_is_the_references_and_counts_the_three_mechanisms():
    """Loss, priorities and the parameters after one RMSProp step of the
    program's train step, float32 compute, against ``learner_step`` (the
    balancing rule's move of the bias among them); the step's counters."""
    from ape_x_dqn_tpu.learner.train_step import build_train_step, make_optimizer
    from ape_x_dqn_tpu.types import TrainState
    from reference import ling3_q as ref
    from tests.test_solar_open2 import _batch

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
    for i in (1, 2, 3):                                  # the balancing rule moved every routing layer's bias
        moved = got_w[f"layer_{i}"]["expert_bias"] - weights[f"layer_{i}"]["expert_bias"]
        assert float(jnp.max(jnp.abs(moved))) > 1e-3
        np.testing.assert_allclose(np.asarray(got_w[f"layer_{i}"]["expert_bias"]),
                                   np.asarray(want_w[f"layer_{i}"]["expert_bias"]), atol=1e-6)
    assert "expert_bias" not in got_w["layer_0"]
    # 40 tokens in chunks of 16: 3 chunks, 48 tokens walked, three linear layers, 4 rows, 3 forwards
    assert {k: float(v) for k, v in metrics.delta.items()} == {
        "chunks": 3 * 3 * 4 * 3.0, "tokens_padded": 3 * 3 * 4 * 48.0, "tokens": 3 * 3 * 4 * 40.0}
    assert {k: float(v) for k, v in metrics.attention.items()} == {
        "pairs_in_mask_latent": 3 * 4 * (40 * 41 // 2), "pairs_computed_latent": 3 * 4 * 128 * 512.0,
        "blocks_visited_latent": 3 * 4 * 4 * 1.0, "blocks_total_latent": 3 * 4 * 4 * 1.0}
    assert float(metrics.routing["held_pairs"]) > 0 and metrics.scan is None
    share = float(metrics.routing["groups_kept_hold_share"])       # a mean, not the forwards' sum
    assert 0.0 < share < 1.0
    assert set(metrics.routing) == {"held_pairs", "load_max", "load_mean", "rows_walked",
                                    "groups_kept_hold_share"}

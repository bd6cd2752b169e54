"""The network kind ``laguna_moe`` in the program: the blocked attention
against a dense masked softmax, the window, the counters from the shapes,
what the two torsos of blocks share, the configuration path and the
trainer's loop, all at small widths on the CPU (the kernels in Pallas'
interpreter)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.config import TORSO_NETWORKS, ApexConfig, load_config, network_kwargs
from ape_x_dqn_tpu.models import dueling, expert_torso, laguna_moe, lfm2_moe
from ape_x_dqn_tpu.models.dueling import build_greedy_apply, build_network
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.utils import profiling

ROPE = {"full_attention": dict(rope_theta=500000, rope_type="yarn", factor=128,
                               original_max_position_embeddings=8192, beta_slow=1, beta_fast=32,
                               attention_factor=1.4852030263919618, partial_rotary_factor=0.5),
        "sliding_attention": dict(rope_type="default", rope_theta=10000, partial_rotary_factor=1)}
TORSO = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
    sliding_window=8, num_experts=4, router_outputs=16, experts_held=[0, 4],
    num_experts_per_tok=3, norm_topk_prob=True, moe_routed_scaling_factor=2.5,
    layer_types=["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    rope_parameters=ROPE, layers_held=[0, 1, 2, 3, 4], channels=[8, 8, 8], hidden=32,
)


def small_net(**over):
    return build_network("laguna_moe", 6, torso=dict(TORSO, **over), channels=(8, 8, 8),
                         hidden=32, compute_dtype=jnp.float32)


def obs(key, rows=2, shape=(44, 60, 5)):   # 5 frames of 2 x 4 positions: 40 tokens
    return jax.random.randint(key, (rows, *shape), 0, 256).astype(jnp.uint8)


def dense_attention(q, k, v, window):
    """softmax(q k^T + mask) v with the whole [T, T] score tensor."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    t = q.shape[2]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = (j <= i) if window is None else (j <= i) & (j > i - window)
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1), v)


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("group", [4, 6, 9])
@pytest.mark.parametrize("window", [None, 100, 400])
def test_blocked_attention_is_the_dense_masked_softmax(window, group, head_dim):
    """Forward (with and without the kept log-sum) and the gradients of q, k
    and v in Pallas' interpreter, 300 tokens: no multiple of a block of
    either kernel, so the last block of queries and of keys reads past the
    end (the interpreter fills it with NaN); a window of 100 inside a block,
    one of 400 longer than the sequence; two key-value heads of ``group``
    query heads each."""
    tokens = 300
    assert all(tokens % b for b in dataclasses.astuple(blocked.plan(tokens, window)))
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 2 * group, tokens, head_dim)) / head_dim ** 0.5
    k, v = (jax.random.normal(kk, (1, 2, tokens, head_dim)) for kk in ks[1:3])
    cot = jax.random.normal(ks[3], q.shape)
    got, pull = jax.vjp(lambda *a: blocked.blocked_attention(*a, window), q, k, v)
    want, pull_dense = jax.vjp(lambda *a: dense_attention(*a, window), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(blocked.blocked_attention(q, k, v, window)),
                                  np.asarray(got))
    for name, a, b in zip("qkv", pull(cot), pull_dense(cot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name)


@pytest.mark.parametrize("tokens,window", [
    (1568, None), (1568, 512), (300, None), (300, 100), (300, 400), (40, 8)])
def test_the_counts_from_the_shapes_are_the_masks(tokens, window):
    """``pairs_in_mask`` against the mask counted pair by pair;
    ``blocks_visited`` and ``pairs_computed`` against the dense mask cut in
    the plan's blocks; and the walks the kernels are handed: every block that
    holds a pair once, by query blocks and by key blocks, a block that is not
    marked as crossed by an edge wholly inside the mask."""
    i, j = np.arange(tokens)[:, None], np.arange(tokens)[None, :]
    mask = (j <= i) if window is None else (j <= i) & (j > i - window)
    assert blocked.pairs_in_mask(tokens, window) == int(mask.sum())
    plan = blocked.plan(tokens, window)
    bq, bkv = plan.block_q, plan.block_kv
    nq, nkv = -(-tokens // bq), -(-tokens // bkv)
    cut = np.zeros((nq * bq, nkv * bkv), bool)
    cut[:tokens, :tokens] = mask
    cut = cut.reshape(nq, bq, nkv, bkv)
    holds, whole = cut.any((1, 3)), cut.all((1, 3))
    for by_keys in (False, True):
        qi, kj, flags = blocked._schedule(tokens, window, bq, bkv, by_keys)
        assert sorted(zip(qi.tolist(), kj.tolist())) == [tuple(b) for b in np.argwhere(holds)]
        edge = flags & blocked._EDGE != 0
        assert whole[qi[~edge], kj[~edge]].all() and not whole[qi[edge], kj[edge]].any()
        row = kj if by_keys else qi
        starts = np.r_[True, row[1:] != row[:-1]]
        assert (np.diff(row) >= 0).all()
        np.testing.assert_array_equal(flags & blocked._FIRST != 0, starts)
        np.testing.assert_array_equal(flags & blocked._LAST != 0, np.r_[starts[1:], True])
        past = flags & blocked._END != 0
        np.testing.assert_array_equal(past, (np.maximum(qi * bq + bq, kj * bkv + bkv) > tokens))
        assert (edge | ~past).all()
    assert blocked.blocks_visited(tokens, window) == (int(holds.sum()), nq * nkv)
    assert blocked.pairs_computed(tokens, window) == int(holds.sum()) * bq * bkv
    if tokens == 1568:
        assert int(mask.sum()) == (1_230_096 if window is None else 672_000)
        assert (bq, bkv) == ((128, 512) if window is None else (256, 256))
        assert blocked.blocks_visited(tokens, window) == ((28, 52) if window is None else (18, 49))


def _layer(op):
    spec = laguna_moe.spec_from_config(TORSO)
    layer = laguna_moe.GatedAttention(spec, op, jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    return layer, layer.init(jax.random.PRNGKey(1), u), u


def test_a_sliding_layer_sees_its_window_and_a_full_layer_everything():
    """Position p of a sliding layer's output is unmoved by a change at
    p - window and moved by one at p - window + 1; a full layer's is moved
    by the first too; neither by a later token."""
    p, window = 30, TORSO["sliding_window"]
    for op, sees_far in (("sliding_attention", False), ("full_attention", True)):
        layer, params, u = _layer(op)
        base = layer.apply(params, u)
        far = layer.apply(params, u.at[:, p - window].add(1.0))
        near = layer.apply(params, u.at[:, p - window + 1].add(1.0))
        later = layer.apply(params, u.at[:, p + 1:].add(1.0))
        moved = lambda out: float(jnp.max(jnp.abs(out[:, p] - base[:, p])))  # noqa: E731
        assert (moved(far) > 1e-4) == sees_far, (op, moved(far))
        assert moved(near) > 1e-4 and moved(later) == 0.0, op


def test_one_attention_module_is_told_its_kind():
    spec = laguna_moe.spec_from_config(TORSO)
    kinds = dict(spec.arg("attention"))
    assert kinds["full_attention"].heads == 4 and kinds["full_attention"].window is None
    assert kinds["sliding_attention"].heads == 6 and kinds["sliding_attention"].window == 8
    assert kinds["full_attention"].rope.kind == "yarn" and kinds["full_attention"].rope.rotary_dim == 8
    assert kinds["sliding_attention"].rope.rotary_dim == 16
    assert dict(spec.mixers) == {"full_attention": laguna_moe.GatedAttention,
                                 "sliding_attention": laguna_moe.GatedAttention}
    for op, heads in (("full_attention", 4), ("sliding_attention", 6)):
        _, params, _ = _layer(op)
        assert params["params"]["w_q"].shape == (64, heads * 16)
        assert params["params"]["w_g"].shape == (64, heads)
        assert params["params"]["w_k"].shape == (64, 2 * 16)
    with pytest.raises(ValueError, match="head counts"):
        laguna_moe.spec_from_config(dict(TORSO, num_attention_heads_per_layer=[4, 6, 5, 6, 4]))


def test_the_network_has_the_issues_structure():
    net = small_net()
    x = obs(jax.random.PRNGKey(2))
    assert net.tokens_of(x.shape) == 40
    params = net.init(jax.random.PRNGKey(3), x)["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layer_0", "layers_1_3", "layer_4", "w_tok", "final_norm"}
    assert "dense" in params["layer_0"] and "shared_expert" in params["layers_1_3"]
    assert set(params["layer_4"]["moe"]) == {"router", "w13", "w2"}      # no expert bias
    assert params["layers_1_3"]["sliding_attention"]["w_q"].shape == (3, 64, 96)
    (_, _, q), sown = net.apply({"params": params}, x, mutable=["routing"])
    assert q.shape == (2, 6) and bool(jnp.all(jnp.isfinite(q)))
    loads = np.concatenate([np.asarray(v).reshape(-1, 16)
                            for v in jax.tree_util.tree_leaves(sown["routing"])])
    assert loads.shape == (4, 16) and (loads.sum(-1) == 2 * 40 * 3).all()
    # frames are tokens in time order: a change to the newest frame alone
    # leaves the oldest frames' tokens, and so nothing before it, unmoved
    actions, served = build_greedy_apply(net)({"params": params}, x)
    np.testing.assert_array_equal(np.asarray(actions), np.argmax(np.asarray(served), -1))


def test_rebalanced_is_the_identity_without_a_bias():
    net = small_net()
    x = obs(jax.random.PRNGKey(4))
    params = net.init(jax.random.PRNGKey(5), x)
    _, sown = net.apply(params, x, mutable=["routing"])
    assert net.rebalanced(params, sown) is params
    assert not any("expert_bias" in jax.tree_util.keystr(p)
                   for p, _ in jax.tree_util.tree_leaves_with_path(params))


def test_the_train_step_carries_routing_and_attention_counters():
    from ape_x_dqn_tpu.learner.train_step import (
        StepMetrics, build_train_step, init_train_state, make_optimizer,
    )
    from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch

    net = small_net()
    opt = make_optimizer("rmsprop", learning_rate=1e-4)
    x = obs(jax.random.PRNGKey(6), rows=4)
    state = init_train_state(net, opt, jax.random.PRNGKey(7), x[:1])
    batch = PrioritizedBatch(
        transition=NStepTransition(obs=x, action=jnp.zeros(4, jnp.int32), reward=jnp.ones(4),
                                   discount=jnp.full((4,), 0.9), next_obs=x[::-1]),
        indices=jnp.arange(4), is_weights=jnp.ones(4))
    step = build_train_step(net, opt, loss_kind="squared", sync_in_step=False, jit=True)
    _, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics.loss))
    full, window = 40 * 41 // 2, 8 * 9 // 2 + 32 * 8
    want = net.attention_metrics(x.shape)
    assert want["pairs_in_mask_full"] == 4 * 2 * full
    assert want["pairs_in_mask_window"] == 4 * 3 * window
    # 40 tokens lie in one block of either kind's plan, visited by 4 heads on
    # two layers and by 6 on three; the kernels compute the whole block
    assert want["blocks_visited_full"] == want["blocks_total_full"] == 4 * 2 * 4
    assert want["blocks_visited_window"] == want["blocks_total_window"] == 4 * 3 * 6
    for kind, layers, span in (("full", 2, None), ("window", 3, 8)):
        plan = blocked.plan(40, span)
        assert want[f"pairs_computed_{kind}"] == 4 * layers * plan.block_q * plan.block_kv
        assert want[f"pairs_computed_{kind}"] > want[f"pairs_in_mask_{kind}"]
    assert {k: float(v) for k, v in metrics.attention.items()} == {
        k: 3.0 * v for k, v in want.items()}
    assert float(metrics.routing["held_pairs"]) > 0
    assert float(metrics.routing["rows_walked"]) >= float(metrics.routing["held_pairs"])
    assert StepMetrics(loss=0, mean_abs_td=0, max_abs_td=0, priorities=0, mean_q=0).attention is None
    # a network with no blocked attention counts none
    lfm2 = build_network("lfm2_moe", 6, torso=dict(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, conv_L_cache=3, norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
        layer_types=["conv", "full_attention"], num_dense_layers=1, num_experts=2,
        router_outputs=4, num_experts_per_tok=2), compute_dtype=jnp.float32)
    assert lfm2.attention_metrics((4, 52, 52, 4)) is None and lfm2.tokens_of((4, 52, 52, 4)) == 9


def test_the_new_parts_are_scoped():
    assert profiling.PARTS[6:9] == ("attn_window", "attn_full", "shared_expert")
    net = small_net()
    x = obs(jax.random.PRNGKey(8))
    params = net.init(jax.random.PRNGKey(9), x)
    text = jax.jit(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2))).lower(params).as_text(
        debug_info=True)
    for part in ("attn_window", "attn_full", "mixer", "shared_expert", "router", "experts",
                 "dense_ffn", "stem", "head"):
        assert f"torso:{part}" in text, part
    assert "torso:mixer/sliding_attention/torso:attn_window" in text   # the kernels inside the mixer


def test_both_torsos_share_one_walk_and_one_wrapper():
    assert issubclass(lfm2_moe.Lfm2MoeQ, expert_torso.TorsoQ)
    assert issubclass(laguna_moe.LagunaMoeQ, expert_torso.TorsoQ)
    for name in ("held_experts", "tile_rows", "route", "ExpertShare", "Block"):
        assert getattr(lfm2_moe, name) is getattr(expert_torso, name), name
        assert not hasattr(laguna_moe, name) or getattr(laguna_moe, name) is getattr(expert_torso, name)
    assert "routing_metrics" not in vars(lfm2_moe.Lfm2MoeQ) | vars(laguna_moe.LagunaMoeQ)
    assert tuple(dueling.TORSO_KINDS) == TORSO_NETWORKS
    # the rule of the walk's tile is one rule: the cell's 125,440 pairs a forward
    assert expert_torso.tile_rows(12544 * 10, 8, 256) == 5632
    # each family's spec asks for its own keys alone
    lfm2_only = {"conv_L_cache", "num_dense_layers", "norm_eps", "num_attention_heads"}
    assert not lfm2_only & set(TORSO)
    assert laguna_moe.spec_from_config(TORSO).score_function == "softmax"


def test_config_carries_the_torso_of_either_kind():
    cfg = ApexConfig()
    cfg.network = "laguna_moe"
    with pytest.raises(ValueError, match="lfm2_moe | laguna_moe"):
        cfg.validate()
    cfg.torso = dict(TORSO)
    with pytest.raises(ValueError, match="frame_stack"):
        cfg.validate()                      # a history needs more than one frame
    cfg.env.frame_stack = 5
    kw = network_kwargs(cfg.validate())
    assert kw["channels"] == (8, 8, 8) and kw["hidden"] == 32
    assert build_network(cfg.network, 6, **kw).spec.experts_held == (0, 4)
    committed = load_config("configs/config7_laguna_q_ep32.json")
    spec = build_network(committed.network, 18, **network_kwargs(committed)).spec
    assert committed.env.frame_stack == 32 and spec.frame_history
    assert spec.hidden_size == 3072 and spec.router_outputs == 256 and spec.num_held == 8
    assert spec.shared_expert_intermediate_size == 1024 and spec.routed_scaling_factor == 2.5
    assert [op for op, _ in spec.layers] == (
        ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"])
    assert [f for _, f in spec.layers] == ["dense", "moe", "moe", "moe", "moe"]
    kinds = dict(spec.arg("attention"))
    assert (kinds["full_attention"].heads, kinds["sliding_attention"].heads) == (48, 72)
    assert kinds["sliding_attention"].window == 512


def test_the_trainers_loop_runs_the_network():
    """``runtime/single_process.py``'s loop, a few learner steps, through
    ``build_components``: the normal path builds and trains the network on
    histories of ``env.frame_stack`` frames."""
    from ape_x_dqn_tpu.runtime import SingleProcessDriver

    cfg = ApexConfig()
    cfg.env.name = "fake-atari"
    cfg.env.frame_stack = 4
    cfg.network = "laguna_moe"
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
    assert len(learned) >= 3 and all(np.isfinite(x) for x in learned), learned
    assert type(driver.network).__name__ == "LagunaMoeQ"


def test_a_network_too_large_to_copy_is_published_synchronously(monkeypatch):
    """``AsyncPipeline._copy_fits``: parameters over an eighth of the device's
    memory get no device-side copy and no publisher thread; the learner's
    loop then publishes the live parameters itself."""
    import io
    import types

    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    def leaf(nbytes, limit):
        device = types.SimpleNamespace(
            memory_stats=lambda: None if limit is None else {"bytes_limit": limit})
        return types.SimpleNamespace(nbytes=nbytes, devices=lambda: [device])

    fits = AsyncPipeline._copy_fits
    assert fits([leaf(1, 16), leaf(1, 16)]) and not fits([leaf(2, 16), leaf(1, 16)])
    assert fits([leaf(10**12, None)]) and fits([])     # a device that does not say

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.num_actors = 2
    cfg.learner.min_replay_mem_size = 64
    cfg.learner.publish_every = 2
    cfg.replay.capacity = 256
    monkeypatch.setattr(AsyncPipeline, "_copy_fits", staticmethod(lambda params: False))
    pipe = AsyncPipeline(cfg.validate(), logger=MetricLogger(stream=io.StringIO()))
    assert pipe._publisher is None and pipe._param_copy is None
    before = pipe.store.version
    pipe.run(learner_steps=6, warmup_timeout=120.0)
    assert pipe.store.version > before

"""The network kind ``lfm2_moe`` in the program: the block's structure
(causality, the expert range), the train step's routing counters and split
forwards, the ``torso:`` scopes, the configuration path and the trainer's
loop, all at small widths on the CPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.config import ApexConfig, load_config, network_kwargs
from ape_x_dqn_tpu.models.dueling import build_greedy_apply, build_network
from ape_x_dqn_tpu.models import expert_torso, lfm2_moe
from ape_x_dqn_tpu.models.lfm2_moe import (
    BIAS_UPDATE_RATE, Attention, ExpertShare, ShortConv, layer_runs, route, spec_from_config,
    tile_rows,
)
from ape_x_dqn_tpu.utils import profiling

TORSO = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, conv_L_cache=3, norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6}, layer_types=["conv", "conv", "full_attention", "conv"],
    num_dense_layers=1, num_experts=2, router_outputs=4, experts_held=[0, 2],
    num_experts_per_tok=2, layers_held=[0, 2, 3], channels=[8, 8, 8], hidden=32,
)


def small_net(**over):
    torso = dict(TORSO, **over)
    return build_network("lfm2_moe", 6, torso=torso, channels=(8, 8, 8), hidden=32,
                         compute_dtype=jnp.float32)


def obs(key, rows=4, side=52):
    return jax.random.randint(key, (rows, side, side, 4), 0, 256).astype(jnp.uint8)


@pytest.mark.parametrize("mixer", [ShortConv, Attention])
def test_mixers_are_causal(mixer):
    """A later token changes no earlier output."""
    spec = spec_from_config(TORSO)
    layer = mixer(spec, "x", jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 64))
    params = layer.init(jax.random.PRNGKey(1), u)
    base = layer.apply(params, u)
    moved = layer.apply(params, u.at[:, 6:].add(1.0))
    np.testing.assert_array_equal(np.asarray(moved[:, :6]), np.asarray(base[:, :6]))
    assert float(jnp.max(jnp.abs(moved[:, 6:] - base[:, 6:]))) > 1e-3


@pytest.mark.parametrize("held", [[0, 5], [3, 3], [2, 1], [-1, 2]])
def test_a_wrong_expert_range_is_seen(held):
    with pytest.raises(ValueError, match="experts_held"):
        small_net(experts_held=held)


def test_unknown_layer_kind_and_missing_torso_are_errors():
    with pytest.raises(ValueError, match="unknown layer"):
        small_net(layer_types=["conv", "conv", "window", "conv"])
    with pytest.raises(ValueError, match="torso"):
        build_network("lfm2_moe", 6)


def test_forward_counts_every_pair():
    net = small_net()
    x = obs(jax.random.PRNGKey(2))
    params = net.init(jax.random.PRNGKey(3), x)
    assert set(params) == {"params"}  # init returns parameters alone
    out, sown = net.apply(params, x, mutable=["routing"])
    assert out.q.shape == (4, 6) and np.isfinite(np.asarray(out.q)).all()
    # every pair of every token is on one of the router's 4 outputs:
    # 4 rows x 9 tokens x 2 a token, in each of the 2 expert layers
    loads = [np.asarray(v) for v in jax.tree_util.tree_leaves(sown["routing"])]
    assert [int(v.sum()) for v in loads] == [72, 72] and all(v.shape == (4,) for v in loads)
    totals = net.routing_metrics(sown)
    assert float(totals["held_pairs"]) == sum(int(v[:2].sum()) for v in loads)
    assert float(totals["load_max"]) >= float(totals["load_mean"]) > 0
    assert set(totals) == {"held_pairs", "load_max", "load_mean", "rows_walked"}
    # at these widths one tile holds every pair, and each layer holds some
    assert float(totals["rows_walked"]) == 2 * 72


def worst_case_share(p, u, sp):
    """The expert layer as one buffer of ``tokens x k`` rows, every pair of
    every token on a held expert, in float32: the tiled walk's oracle."""
    f, n, k = sp.moe_intermediate_size, sp.num_held, sp.num_experts_per_tok
    lo, hi = sp.experts_held
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    tokens, rows = u.shape[0], u.shape[0] * k
    scores = jax.nn.sigmoid(jnp.dot(u, p["router"], precision=jax.lax.Precision.HIGHEST))
    chosen, gates = route(scores, jax.lax.stop_gradient(p["expert_bias"]), sp)
    held = (chosen >= lo) & (chosen < hi)
    sizes = jnp.sum(jax.nn.one_hot(chosen.reshape(-1), sp.router_outputs,
                                   dtype=jnp.int32), axis=0)[lo:hi]
    order = jnp.argsort(jnp.where(held, chosen - lo, n).reshape(-1), stable=True)
    live = jnp.arange(rows) < jnp.sum(sizes)
    where = jnp.argsort(order).reshape(tokens, k)  # pair -> its row
    weight = jnp.where(held, gates, 0.0)
    xs = jnp.where(live[:, None], u[order // k], 0)
    h = jax.lax.ragged_dot(xs, p["w13"], sizes)
    ys = jax.lax.ragged_dot(jax.nn.silu(h[:, :f]) * h[:, f:], p["w2"], sizes)
    ys = jnp.where(live[:, None], ys, 0)
    return sum(ys[where[:, j]] * weight[:, j, None] for j in range(k)).reshape(shape)


# 36 tokens, 2 of the router's 4 outputs a token, outputs [0, 2) held: 72 pairs.
# (the bias that places the pairs, rows a tile, tiles walked; None: the loads say)
WALKS = {
    "typical_loads": ([0.0, 0.0, 0.0, 0.0], 8, None),
    "every_pair_held": ([5.0, 5.0, -5.0, -5.0], 8, 9),
    "every_pair_held_last_tile_part_filled": ([5.0, 5.0, -5.0, -5.0], 16, 5),
    "no_pair_held": ([-5.0, -5.0, 5.0, 5.0], 8, 0),
    "whole_tiles": ([5.0, -5.0, 0.0, 0.0], 12, 3),
    "one_expert_holds_everything": ([5.0, -5.0, 0.0, 0.0], 8, 5),
}


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_the_tiled_walk_is_the_worst_case_buffer(monkeypatch, walk):
    """Values and gradients (tokens, both expert weights, the router) of the
    layer that walks its held pairs a tile at a time, against one buffer of
    every pair, with the tile forced small."""
    bias, tile, tiles = WALKS[walk]
    monkeypatch.setattr(expert_torso, "tile_rows", lambda rows, held, outputs: tile)
    sp = spec_from_config(TORSO)
    layer = ExpertShare(sp, jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(11), (4, 9, 64))
    cot = jax.random.normal(jax.random.PRNGKey(12), u.shape)
    params = dict(layer.init(jax.random.PRNGKey(13), u)["params"], expert_bias=jnp.array(bias))

    def tiled(p, u):
        y, sown = layer.apply({"params": p}, u, mutable=["routing"])
        return jnp.sum(y * cot), (y, sown["routing"]["load"][0])

    def plain(p, u):
        y = worst_case_share(p, u, sp)
        return jnp.sum(y * cot), y

    grad = lambda fn: jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True))  # noqa: E731
    (_, (y, load)), (dp, du) = grad(tiled)(params, u)
    (_, want_y), (want_dp, want_du) = grad(plain)(params, u)
    held = int(load[:2].sum())
    assert int(load.sum()) == 72
    if tiles is None:
        assert 3 * tile < held < 72 and held % tile
    else:
        assert -(-held // tile) == tiles and (walk != "whole_tiles" or held == tiles * tile)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-6)
    np.testing.assert_allclose(np.asarray(du), np.asarray(want_du), atol=2e-6)
    for leaf in ("w13", "w2", "router", "expert_bias"):
        np.testing.assert_allclose(np.asarray(dp[leaf]), np.asarray(want_dp[leaf]),
                                   atol=1e-5, err_msg=leaf)
    if held:
        assert all(float(jnp.max(jnp.abs(g))) > 1e-3
                   for g in (y, du, dp["w13"], dp["w2"], dp["router"]))
    else:
        assert not any(np.asarray(g).any() for g in (y, du, *jax.tree_util.tree_leaves(dp)))
    if walk == "one_expert_holds_everything":
        assert int(load[0]) == held and not np.asarray(dp["w13"][1]).any()


def test_a_tile_comes_from_the_shapes():
    """A chip that holds every expert walks one tile of ``tokens x k`` rows,
    today's buffer; one that holds a share walks whole kernel tiles a little
    over the fill even loads give."""
    for rows in (72, 6272, 100352):
        assert tile_rows(rows, 4, 4) == rows == tile_rows(rows, 64, 64)
    assert tile_rows(72, 2, 4) == 72                      # the toy networks: one tile
    cell = tile_rows(512 * 49 * 4, 8, 64)                 # lfm2moe_q_ep8: 12,544 expected
    assert cell % lfm2_moe.KERNEL_ROWS == 0 and 12544 < cell < 2 * 12544
    serving = tile_rows(32 * 49 * 4, 8, 64)               # action selection: 784 expected
    assert serving % lfm2_moe.KERNEL_ROWS == 0 and 784 < serving <= 1536


def test_rows_walked_is_tiles_by_tile(monkeypatch):
    monkeypatch.setattr(expert_torso, "tile_rows", lambda rows, held, outputs: 8)
    net = small_net()
    x = obs(jax.random.PRNGKey(2))
    _, sown = net.apply(net.init(jax.random.PRNGKey(3), x), x, mutable=["routing"])
    loads = [np.asarray(v) for v in jax.tree_util.tree_leaves(sown["routing"])]
    want = sum(-(-int(v[:2].sum()) // 8) * 8 for v in loads)
    totals = net.routing_metrics(sown)
    assert float(totals["rows_walked"]) == want and want > float(totals["held_pairs"]) > want - 16


LONG = dict(layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
            layers_held=[0, 2, 3, 4, 5])


def test_a_run_of_like_layers_is_one_scanned_body():
    """Consecutive layers of one kind hold their parameters stacked and run
    as one ``scan`` body."""
    assert layer_runs(spec_from_config(dict(TORSO, **LONG)).layers) == [
        (0, 1, ("conv", "dense")), (1, 1, ("full_attention", "moe")), (2, 3, ("conv", "moe"))]
    net = small_net(**LONG)
    x = obs(jax.random.PRNGKey(2))
    params = net.init(jax.random.PRNGKey(3), x)["params"]
    assert set(params) >= {"layer_0", "layer_1", "layers_2_4"} and "layer_2" not in params
    assert params["layers_2_4"]["moe"]["w13"].shape == (3, 2, 64, 64)
    out, sown = net.apply({"params": params}, x, mutable=["routing"])
    assert sown["routing"]["layers_2_4"]["moe"]["load"][0].shape == (3, 4)
    assert out.q.shape == (4, 6) and np.isfinite(np.asarray(out.q)).all()
    # two grouped products a body, and two bodies for the four expert layers
    # (tests/benchmark holds the scanned run to the reference's layers)
    jaxpr = str(jax.make_jaxpr(lambda p: net.apply(p, x)[2])({"params": params}))
    assert jaxpr.count("ragged_dot_general[") == 2 * 2


def test_tokens_are_centred_over_a_frames_positions():
    """A constant added to every position of the stem's output moves no Q
    value: shown on the module by a frame of one colour, whose positions are
    all alike, giving the same Q values as any other such frame."""
    net = small_net()
    flat = jnp.stack([jnp.full((52, 52, 4), v, jnp.uint8) for v in (0, 90, 255)])
    params = net.init(jax.random.PRNGKey(3), flat)
    q = np.asarray(net.apply(params, flat)[2])
    # the centring leaves float32 rounding of the stem's output, and the
    # first RMSNorm scales what is near zero by up to 1/sqrt(eps) = 316
    np.testing.assert_allclose(q[1], q[0], atol=2e-3)
    np.testing.assert_allclose(q[2], q[0], atol=2e-3)
    other = np.asarray(net.apply(params, obs(jax.random.PRNGKey(1), 1))[2])
    assert np.max(np.abs(other - q[0])) > 0.05


def test_the_balancing_rule_evens_the_loads():
    """``rebalanced`` on a fixed batch, step after step: the loads of all
    the router's outputs come within a tenth of even, from a bias that
    starts far off, and nothing but the bias moves."""
    net = small_net()
    x = obs(jax.random.PRNGKey(4), rows=16)
    params = net.init(jax.random.PRNGKey(5), x)
    params["params"]["layer_1"]["moe"]["expert_bias"] = jnp.array([0.2, 0.0, -0.2, 0.0])

    @jax.jit
    def step(p):
        _, sown = net.apply(p, x, mutable=["routing"])
        return net.rebalanced(p, sown), jnp.stack(jax.tree_util.tree_leaves(sown["routing"]))

    p, first = step(params)
    for _ in range(60):
        p, loads = step(p)
    ratio = lambda v: float(jnp.max(v / jnp.mean(v, -1, keepdims=True)))  # noqa: E731
    assert ratio(first) > 1.3 and ratio(loads) <= 1.1, (first, loads)
    moved = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(p), jax.tree_util.tree_leaves(params))
        if not np.array_equal(np.asarray(a), np.asarray(b))]
    assert moved and all("expert_bias" in m for m in moved)
    still = small_net(use_expert_bias=False)
    assert still.rebalanced(params, None) is params


def _train_pieces(net, batch=4):
    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step, init_train_state, make_optimizer,
    )
    from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch

    opt = make_optimizer("rmsprop", second_moment_dtype=jnp.bfloat16)
    state = init_train_state(net, opt, jax.random.PRNGKey(0), obs(jax.random.PRNGKey(1), 1),
                             target_dtype=jnp.bfloat16)
    k = jax.random.PRNGKey(7)
    t = NStepTransition(
        obs=obs(k, batch), action=jnp.arange(batch, dtype=jnp.int32) % 6,
        reward=jnp.ones((batch,)), discount=jnp.full((batch,), 0.97),
        next_obs=obs(jax.random.fold_in(k, 1), batch))
    b = PrioritizedBatch(transition=t, indices=jnp.arange(batch, dtype=jnp.int32),
                         is_weights=jnp.ones((batch,)))
    return build_train_step(net, opt, loss_kind="squared", jit=False), state, b


def test_train_step_reports_routing_and_moves_the_bias_by_the_rule_alone():
    net = small_net()
    step, state, batch = _train_pieces(net)
    moe = state.params["params"]["layer_1"]["moe"]
    new, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics.loss))
    # three forwards of 4 rows x 9 tokens x 2 a token x 2 layers
    assert 0 < float(metrics.routing["held_pairs"]) <= 3 * 144
    # the bias moved by the rule on the two online forwards' loads, and by
    # nothing else (no gradient reaches it, RMSProp leaves it)
    t = batch.transition
    loads = sum(net.apply(state.params, o, mutable=["routing"])[1]["routing"]
                ["layer_1"]["moe"]["load"][0] for o in (t.obs, t.next_obs)).astype(jnp.float32)
    want = moe["expert_bias"] - BIAS_UPDATE_RATE * jnp.clip(loads / jnp.mean(loads) - 1, -1, 1)
    np.testing.assert_allclose(
        np.asarray(new.params["params"]["layer_1"]["moe"]["expert_bias"]), np.asarray(want),
        atol=1e-7)
    assert float(jnp.max(jnp.abs(want - moe["expert_bias"]))) > 1e-3
    assert not np.array_equal(np.asarray(new.params["params"]["w_tok"]),
                              np.asarray(state.params["params"]["w_tok"]))


def test_other_networks_report_no_routing():
    from ape_x_dqn_tpu.learner.train_step import StepMetrics

    assert StepMetrics(loss=0, mean_abs_td=0, max_abs_td=0, priorities=0, mean_q=0).routing is None
    net = build_network("nature", 6, channels=(8, 8, 8), hidden=32)
    step, state, batch = _train_pieces(net)
    _, metrics = jax.jit(step)(state, batch)
    assert metrics.routing is None


def test_parts_are_scoped_beside_the_stages():
    """The train step's text names every part under ``torso:``, forward and
    backward, and the ``stage:`` readers still see ``forward``."""
    lfm2_parts = ("stem", "mixer", "router", "experts", "dense_ffn", "head")
    assert profiling.PARTS[:6] == lfm2_parts  # the rest are another torso's
    with pytest.raises(ValueError):
        profiling.part("torso")
    net = small_net()
    step, state, batch = _train_pieces(net)
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    for part in lfm2_parts:
        assert f"torso:{part}" in text, part
    assert "transpose(jvp(stage:forward))" in text and "torso:experts" in text
    compiled = jax.jit(step).lower(state, batch).compile().as_text()
    stages = profiling.hlo_stages(compiled)
    assert {"forward", "backward"} <= set(stages.values())
    parts = profiling.hlo_parts(compiled)
    assert {"mixer", "router", "experts", "dense_ffn"} <= set(parts.values())
    # a part is read beside its stage: the experts' instructions are the
    # forward's and the backward's
    assert {stages[name] for name, p in parts.items() if p == "experts"} >= {"forward", "backward"}
    # the expert layers' hand-written backward pass: one loop a layer, and every
    # instruction of it that is named at all is the backward's and a part's
    loops = [m.groups() for m in re.finditer(
        r"%?([\w.\-]+) = [^\n]*? while\([^\n]*?condition=%?([\w.\-]+), body=%?([\w.\-]+)",
        compiled) if stages[m.group(1)] == "backward"]
    assert len(loops) == 2 and all(parts[loop] == "router" for loop, _, _ in loops)
    found = {name: set() for _, cond, body in loops for name in (cond, body)}
    computation = None
    for line in compiled.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            computation = head.group(1)
        elif computation in found and "op_name=" in line:
            name = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
            assert stages[name] == "backward" and parts.get(name) in ("router", "experts"), line
            found[computation].add(parts[name])
    assert all(found[body] == {"router", "experts"} for _, _, body in loops), found


def test_greedy_apply_serves_the_network():
    net = small_net()
    x = obs(jax.random.PRNGKey(8), rows=3)
    params = net.init(jax.random.PRNGKey(9), x)
    actions, q = build_greedy_apply(net)(params, x)
    assert actions.shape == (3,) and q.shape == (3, 6)
    np.testing.assert_array_equal(np.asarray(actions), np.argmax(np.asarray(q), -1))


def test_config_carries_the_torso():
    cfg = ApexConfig()
    cfg.network = "lfm2_moe"
    with pytest.raises(ValueError, match="torso"):
        cfg.validate()
    cfg.torso = dict(TORSO)
    kw = network_kwargs(cfg.validate())
    assert kw["channels"] == (8, 8, 8) and kw["hidden"] == 32
    assert build_network(cfg.network, 6, **kw).spec.experts_held == (0, 2)
    other = ApexConfig()
    other.torso = dict(TORSO)
    with pytest.raises(ValueError, match="torso"):
        other.validate()
    committed = load_config("configs/config6_lfm2moe_q_ep8.json")
    spec = build_network(committed.network, 18, **network_kwargs(committed)).spec
    assert spec.hidden_size == 2048 and spec.router_outputs == 64 and spec.num_held == 8
    assert [op for op, _ in spec.layers] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert [f for _, f in spec.layers] == ["dense", "moe", "moe", "moe", "moe"]


def test_the_trainers_loop_runs_the_network():
    """``runtime/single_process.py``'s loop, a few learner steps, through
    ``build_components``: the normal path builds and trains the network."""
    from ape_x_dqn_tpu.runtime import SingleProcessDriver

    cfg = ApexConfig()
    cfg.env.name = "fake-atari"
    cfg.network = "lfm2_moe"
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
    assert type(driver.network).__name__ == "Lfm2MoeQ"

"""The network kind ``lfm2_moe`` in the program: the block's structure
(causality, the expert range), every pair counted, the tiled walk of the held
pairs against a worst-case buffer, the scanned run of like layers, the
balancing rule, at small widths on the CPU; what every torso is held to (the
float32 leaves, the ``torso:`` scopes, the train step's counters, the
configuration path, the trainer's loop) is the contract's,
``tests/torso_contract.py``, on this torso's row."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models.dueling import build_greedy_apply, build_network
from ape_x_dqn_tpu.models import expert_torso, lfm2_moe
from ape_x_dqn_tpu.models.lfm2_moe import (
    Attention, ExpertShare, ShortConv, layer_runs, route, spec_from_config, tile_rows,
)
from tests import torso_contract as contract
from tests.torso_contract import built, init_of, train_pieces  # noqa: F401 - built: the module's fixture

TORSO = contract.LFM2
small_net = functools.partial(contract.network, "lfm2_moe")


class TestContract(contract.of("lfm2_moe")):
    """The contract's cases on this torso (``tests/torso_contract.py``)."""


def obs(key, rows=4, side=52):
    return contract.obs(key, rows, (side, side, 4))


@pytest.mark.parametrize("mixer", [ShortConv, Attention])
def test_mixers_are_causal(mixer):
    """A later token changes no earlier output."""
    spec = spec_from_config(TORSO)
    layer = mixer(spec, "x", jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 64))
    params = init_of(layer, jax.random.PRNGKey(1), u)
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


def test_forward_counts_every_pair(built):
    net, params = built.net(), built.params
    assert set(params) == {"params"}  # init returns parameters alone
    out, sown = built.applied
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
    params = dict(init_of(layer, jax.random.PRNGKey(13), u)["params"], expert_bias=jnp.array(bias))

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


def test_rows_walked_is_tiles_by_tile(built, monkeypatch):
    monkeypatch.setattr(expert_torso, "tile_rows", lambda rows, held, outputs: 8)
    net = small_net()      # traced anew, with the tile of 8
    _, sown = jax.jit(lambda p, x: net.apply(p, x, mutable=["routing"]))(built.params, built.x)
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
    params = init_of(net, jax.random.PRNGKey(3), x)["params"]
    assert set(params) >= {"layer_0", "layer_1", "layers_2_4"} and "layer_2" not in params
    assert params["layers_2_4"]["moe"]["w13"].shape == (3, 2, 64, 64)
    out, sown = jax.jit(lambda p: net.apply(p, x, mutable=["routing"]))({"params": params})
    assert sown["routing"]["layers_2_4"]["moe"]["load"][0].shape == (3, 4)
    assert out.q.shape == (4, 6) and np.isfinite(np.asarray(out.q)).all()
    # two grouped products a body, and two bodies for the four expert layers
    # (tests/benchmark holds the scanned run to the reference's layers)
    jaxpr = str(jax.make_jaxpr(lambda p: net.apply(p, x)[2])({"params": params}))
    assert jaxpr.count("ragged_dot_general[") == 2 * 2


def test_tokens_are_centred_over_a_frames_positions(built):
    """A constant added to every position of the stem's output moves no Q
    value: shown on the module by a frame of one colour, whose positions are
    all alike, giving the same Q values as any other such frame."""
    flat = jnp.stack([jnp.full((52, 52, 4), v, jnp.uint8) for v in (0, 90, 255)])
    params, apply = built.params, built.apply()
    q = np.asarray(apply(params, flat)[2])
    # the centring leaves float32 rounding of the stem's output, and the
    # first RMSNorm scales what is near zero by up to 1/sqrt(eps) = 316
    np.testing.assert_allclose(q[1], q[0], atol=2e-3)
    np.testing.assert_allclose(q[2], q[0], atol=2e-3)
    other = np.asarray(apply(params, obs(jax.random.PRNGKey(1), 1))[2])
    assert np.max(np.abs(other - q[0])) > 0.05


def test_the_balancing_rule_evens_the_loads(built):
    """``rebalanced`` on a fixed batch, step after step: the loads of all
    the router's outputs come within a tenth of even, from a bias that
    starts far off, and nothing but the bias moves."""
    net = built.net()
    x = obs(jax.random.PRNGKey(4), rows=16)
    params = jax.tree_util.tree_map(lambda v: v, built.params)      # this test's own tree to write
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


def test_other_networks_report_no_routing():
    from ape_x_dqn_tpu.learner.train_step import StepMetrics

    assert StepMetrics(loss=0, mean_abs_td=0, max_abs_td=0, priorities=0, mean_q=0).routing is None
    net = build_network("nature", 6, channels=(8, 8, 8), hidden=32)
    step, state, batch = train_pieces(net)
    _, metrics = step(state, batch)
    assert metrics.routing is None


def test_greedy_apply_serves_the_network(built):
    net, params = built.net(), built.params
    x = obs(jax.random.PRNGKey(8), rows=3)
    actions, q = build_greedy_apply(net)(params, x)
    assert actions.shape == (3,) and q.shape == (3, 6)
    np.testing.assert_array_equal(np.asarray(actions), np.argmax(np.asarray(q), -1))

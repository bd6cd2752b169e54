"""The expert walk's combine (``expert_torso._combined``: a token sum kept in
column blocks, one scatter-add a block) at small sizes on the CPU, at the four
expert cells' (pairs a token, experts held): against a float64 sum of the rows
by token, which has no order, against one scatter-add of whole rows, whose
sums it must give to the bit, and through ``held_experts`` and one
``ExpertShare`` against the worst case's buffer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_torso
from ape_x_dqn_tpu.models.expert_torso import ExpertShare, TorsoSpec
from tests.test_lfm2_moe import worst_case_share

# (pairs a token, experts held): ling3_q_l7, lfm2moe_q_ep8, solar2_q_ep40, laguna_q_ep32
CELLS = [(8, 16), (4, 8), (8, 8), (10, 8)]
TOKENS, WIDTH, BLOCK = 48, 40, 16       # a token sum in blocks of 16, 16 and 8 columns


def spec_of(k: int, held: int) -> TorsoSpec:
    """Twice the held experts exist, so a token holds several pairs here."""
    return TorsoSpec(hidden_size=WIDTH, intermediate_size=16, moe_intermediate_size=16,
                     norm_eps=1e-5, router_outputs=2 * held, num_experts_per_tok=k,
                     experts_held=(0, held), layers=(("op", "moe"),), mixers=(("op", None),))


def tile_of(k: int, held: int, case: str, seed: int = 0):
    """(the tokens of a tile's rows, its float32 rows): the held pairs of a
    random choice of k of ``2 held`` experts a token, sorted by expert as the
    walk has them, the rows past the last pair zero as the walk hands them
    over."""
    rng = np.random.default_rng(seed)
    chosen = np.argsort(rng.random((TOKENS, 2 * held)), -1)[:, :k]
    if case == "a_token_holds_every_row_it_can":
        chosen[0] = np.arange(k)            # experts [0, held) are the held ones
    keys = np.where(chosen < held, chosen, held).reshape(-1)
    order = np.argsort(keys, kind="stable")
    live = int((keys < held).sum()) if case != "no_live_row" else 0
    rows = -(-TOKENS * k * held // (2 * held) * 4 // 3 // 8) * 8       # a third over the even fill
    token = np.pad(order, (0, max(rows - order.size, 0)))[:rows] // k
    ys = rng.standard_normal((rows, WIDTH)).astype(np.float32)
    ys[min(live, rows):] = 0.0
    if case == "a_token_holds_every_row_it_can":
        assert (token[:min(live, rows)] == 0).sum() == min(k, held)
    return jnp.asarray(token, jnp.int32), jnp.asarray(ys)


@pytest.mark.parametrize("case", ["random_tokens", "a_token_holds_every_row_it_can",
                                  "no_live_row", "onto_sums_that_hold_something"])
@pytest.mark.parametrize("k,held", CELLS)
def test_the_combine_is_the_sum_of_a_tokens_rows(monkeypatch, k, held, case):
    monkeypatch.setattr(expert_torso, "BLOCK_COLUMNS", BLOCK)
    token, ys = tile_of(k, held, case)
    u = jnp.zeros((TOKENS, WIDTH), jnp.bfloat16)
    y0 = expert_torso._zero_blocks(u)
    assert [b.shape for b in y0] == [(TOKENS, 16), (TOKENS, 16), (TOKENS, 8)]
    assert all(b.dtype == jnp.float32 for b in y0)
    if case == "onto_sums_that_hold_something":
        y0 = tuple(jax.random.normal(jax.random.PRNGKey(i), b.shape) for i, b in enumerate(y0))
    start = jnp.concatenate(y0, axis=1)
    got = jnp.concatenate(jax.jit(expert_torso._combined)(y0, token, ys), axis=1)
    want = np.asarray(start, np.float64)
    np.add.at(want, np.asarray(token), np.asarray(ys, np.float64))
    # a token's sum has at most min(k, held) terms of order 1 beside what was there
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=0, atol=4e-6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(start.at[token].add(ys)))
    if case == "no_live_row":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(start))
    else:
        assert float(jnp.max(jnp.abs(got - start))) > 0.5


def test_a_token_sum_is_blocks_of_512_and_what_is_left(monkeypatch):
    def widths(d):
        return [b.shape[1] for b in jax.eval_shape(
            expert_torso._zero_blocks, jax.ShapeDtypeStruct((8, d), jnp.bfloat16))]

    # ling3_q_l7, laguna_q_ep32, lfm2moe_q_ep8, solar2_q_ep40
    assert [widths(d) for d in (2560, 3072, 2048, 4096)] == [[512] * n for n in (5, 6, 4, 8)]
    assert widths(1280) == [512, 512, 256] and widths(48) == [48] and widths(512) == [512]
    monkeypatch.setattr(expert_torso, "BLOCK_COLUMNS", 8)
    assert widths(24) == [8, 8, 8] and widths(20) == [8, 8, 4]


def _share(k: int, held: int, tile: int, monkeypatch):
    """(spec, parameters, tokens, cotangent, the layer's value-and-gradients
    under ``tile`` rows a tile and small column blocks)."""
    monkeypatch.setattr(expert_torso, "BLOCK_COLUMNS", BLOCK)
    monkeypatch.setattr(expert_torso, "tile_rows", lambda rows, n, outputs: min(rows, tile))
    sp = spec_of(k, held)
    layer = ExpertShare(sp, jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(21), (4, TOKENS // 4, WIDTH))
    cot = jax.random.normal(jax.random.PRNGKey(22), u.shape)
    params = layer.init(jax.random.PRNGKey(23), u)["params"]

    def tiled(p, u):
        y, sown = layer.apply({"params": p}, u, mutable=["routing"])
        return jnp.sum(y * cot), (y, sown["routing"]["load"][0])

    return sp, params, u, cot, jax.jit(jax.value_and_grad(tiled, argnums=(0, 1), has_aux=True))


@pytest.mark.parametrize("tile", [1024, 24], ids=["one_tile", "several_tiles"])
@pytest.mark.parametrize("k,held", CELLS)
def test_one_share_is_the_worst_case_buffer(monkeypatch, k, held, tile):
    """Values and gradients (tokens, both expert weights, the router) of one
    ``ExpertShare`` whose combine adds by column blocks, a token holding up
    to ``min(k, held)`` rows of a tile, against one buffer of every pair."""
    sp, params, u, cot, tiled = _share(k, held, tile, monkeypatch)

    def plain(p, u):
        y = worst_case_share(p, u, sp)
        return jnp.sum(y * cot), y

    (_, (y, load)), (dp, du) = tiled(params, u)
    (_, want_y), (want_dp, want_du) = jax.jit(
        jax.value_and_grad(plain, argnums=(0, 1), has_aux=True))(params, u)
    pairs = int(load[:held].sum())
    assert int(load.sum()) == TOKENS * k and (pairs > 3 * tile if tile == 24 else pairs < tile)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=5e-6)
    np.testing.assert_allclose(np.asarray(du), np.asarray(want_du), atol=5e-6)
    for leaf in ("w13", "w2", "router"):
        np.testing.assert_allclose(np.asarray(dp[leaf]), np.asarray(want_dp[leaf]),
                                   atol=2e-5, err_msg=leaf)
        assert float(jnp.max(jnp.abs(dp[leaf]))) > 1e-3


@pytest.mark.parametrize("k,held", CELLS)
def test_what_a_dead_row_carries_reaches_no_sum(monkeypatch, k, held):
    """The grouped kernels leave the rows past the last group unwritten:
    with 1e3 in those rows of every product, values and gradients are those
    of products that left zeros there."""
    _, params, u, _, tiled = _share(k, held, 1024, monkeypatch)
    want = tiled(params, u)
    products = expert_torso._products

    def with_garbage(xs, w13, w2, sizes, rule):
        dead = (jnp.arange(xs.shape[0]) >= jnp.sum(sizes))[:, None]
        return tuple(jnp.where(dead, 1e3, x) for x in products(xs, w13, w2, sizes, rule))

    monkeypatch.setattr(expert_torso, "_products", with_garbage)
    got = _share(k, held, 1024, monkeypatch)[-1](params, u)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-6)


@pytest.mark.parametrize("lost", [None, "a_block"])
def test_the_chips_check_of_the_combine_runs_here(monkeypatch, lost):
    """``chip_smoke.combine_against_whole_rows_on_the_chip`` at a small tile:
    it passes on the combine as it is and returns both timings; a combine
    that leaves a column block out fails it."""
    import chip_smoke

    monkeypatch.setattr(expert_torso, "BLOCK_COLUMNS", BLOCK)
    shapes = ((TOKENS, 16, 4, 8, WIDTH), (TOKENS, 16, 8, 8, 3 * BLOCK))
    if lost:
        whole = expert_torso._combined
        monkeypatch.setattr(expert_torso, "_combined",
                            lambda y, token, rows: whole(y[:-1], token, rows) + y[-1:])
        with pytest.raises(AssertionError, match="column blocks differ"):
            chip_smoke.combine_against_whole_rows_on_the_chip(shapes=shapes, repeats=1)
        return
    rows = chip_smoke.combine_against_whole_rows_on_the_chip(shapes=shapes, repeats=1)
    assert [(r["width"], r["blocks"], r["equal_bits"]) for r in rows] == [(WIDTH, 3, True), (48, 3, True)]
    assert all(0 < r["live_rows"] <= r["tile_rows"] and r["column_blocks_us"] > 0
               and r["whole_rows_us"] > 0 for r in rows)

"""The network kind ``laguna_moe`` in the program: the blocked attention
against a dense masked softmax, the window, the counts from the shapes and
what the two torsos of blocks share, at small widths on the CPU (the kernels
in Pallas' interpreter); what every torso is held to (structure, the float32
leaves, scopes, the train step's counters, the configuration path, the
trainer's loop) is the contract's, ``tests/torso_contract.py``, on this
torso's row."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.config import TORSO_NETWORKS, ApexConfig
from ape_x_dqn_tpu.models import dueling, expert_torso, laguna_moe, lfm2_moe
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from tests import torso_contract as contract
from tests.torso_contract import built, init_of, pulled  # noqa: F401 - built: the module's fixture

TORSO = contract.LAGUNA


class TestContract(contract.of("laguna_moe")):
    """The contract's cases on this torso (``tests/torso_contract.py``)."""


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
@pytest.mark.parametrize("group", [1, 4, 6, 9])
@pytest.mark.parametrize("window", [None, 100, 400])
def test_blocked_attention_is_the_dense_masked_softmax(window, group, head_dim):
    """Forward (with and without the kept log-sum) and the gradients of q, k
    and v in Pallas' interpreter, 300 tokens: no multiple of a block of
    either kernel, so the last block of queries and of keys reads past the
    end (the interpreter fills it with NaN); a window of 100 inside a block,
    one of 400 longer than the sequence; two key-value heads of ``group``
    query heads each (a group of 1 walks causal blocks of 256 tokens, and
    300 cross one)."""
    tokens = 300
    plan = blocked.plan(tokens, window, group)
    assert all(tokens % b for b in dataclasses.astuple(plan)) and plan.block_q < tokens
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 2 * group, tokens, head_dim)) / head_dim ** 0.5
    k, v = (jax.random.normal(kk, (1, 2, tokens, head_dim)) for kk in ks[1:3])
    cot = jax.random.normal(ks[3], q.shape)
    got, gots = pulled(lambda *a: blocked.blocked_attention(*a, window))(cot, q, k, v)
    want, wanted = pulled(lambda *a: dense_attention(*a, window))(cot, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(blocked.blocked_attention(q, k, v, window)),
                                  np.asarray(got))
    for name, a, b in zip("qkv", gots, wanted):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name)


@pytest.mark.parametrize("group", [1, 6])
@pytest.mark.parametrize("tokens,window", [
    (1568, None), (1568, 512), (300, None), (300, 100), (300, 400), (40, 8)])
def test_the_counts_from_the_shapes_are_the_masks(tokens, window, group):
    """``pairs_in_mask`` against the mask counted pair by pair;
    ``blocks_visited`` and ``pairs_computed`` against the dense mask cut in
    the blocks of the plan the group gets; and the walks the kernels are
    handed: every block that holds a pair once, by query blocks and by key
    blocks, a block that is not marked as crossed by an edge wholly inside
    the mask."""
    i, j = np.arange(tokens)[:, None], np.arange(tokens)[None, :]
    mask = (j <= i) if window is None else (j <= i) & (j > i - window)
    assert blocked.pairs_in_mask(tokens, window) == int(mask.sum())
    plan = blocked.plan(tokens, window, group)
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
    assert blocked.blocks_visited(tokens, window, group) == (int(holds.sum()), nq * nkv)
    assert blocked.pairs_computed(tokens, window, group) == int(holds.sum()) * bq * bkv
    if tokens == 1568:
        assert int(mask.sum()) == (1_230_096 if window is None else 672_000)
        causal = ((256, 512), (16, 28)) if group == 1 else ((128, 512), (28, 52))
        assert ((bq, bkv), blocked.blocks_visited(tokens, window, group)) == (
            causal if window is None else ((256, 256), (18, 49)))


@pytest.mark.parametrize("group", [1, 4, 6, 8, 9])
@pytest.mark.parametrize("window", [None, 100, 512])
def test_the_plan_reads_the_group(window, group):
    """A causal block of queries is the fewest whole lanes of tokens, a power
    of two, whose rows over the group reach ``_ROWS``: 256 tokens where a
    key-value head has one query head, 128 under every group of the cells
    (what every layer had before the rule read the group).  Under a window
    the group changes nothing.  The counters follow the same plan: at the
    cells' length they are the forward walk's steps and their extents."""
    plan = blocked.plan(1568, window, group)
    before = {None: (128, 512), 100: (128, 128), 512: (256, 256)}[window]
    assert dataclasses.astuple(plan) == ((256, 512) if window is None and group == 1 else before)
    assert all(b % 128 == 0 and b & (b - 1) == 0 for b in dataclasses.astuple(plan))
    if window is None:      # the rows are reached, and by the fewest tokens
        assert group * plan.block_q >= blocked._ROWS
        assert plan.block_q == 128 or group * plan.block_q // 2 < blocked._ROWS
    steps = len(blocked._schedule(1568, window, plan.block_q, plan.block_kv, False)[0])
    assert blocked.blocks_visited(1568, window, group)[0] == steps
    assert blocked.pairs_computed(1568, window, group) == steps * plan.block_q * plan.block_kv


def _layer(op):
    spec = laguna_moe.spec_from_config(TORSO)
    layer = laguna_moe.GatedAttention(spec, op, jnp.float32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    return layer, init_of(layer, jax.random.PRNGKey(1), u), u


def test_a_sliding_layer_sees_its_window_and_a_full_layer_everything():
    """Position p of a sliding layer's output is unmoved by a change at
    p - window and moved by one at p - window + 1; a full layer's is moved
    by the first too; neither by a later token."""
    p, window = 30, TORSO["sliding_window"]
    for op, sees_far in (("sliding_attention", False), ("full_attention", True)):
        layer, params, u = _layer(op)
        apply = jax.jit(layer.apply)
        base = apply(params, u)
        far = apply(params, u.at[:, p - window].add(1.0))
        near = apply(params, u.at[:, p - window + 1].add(1.0))
        later = apply(params, u.at[:, p + 1:].add(1.0))
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


def test_rebalanced_is_the_identity_without_a_bias(built):
    net, params = built.net(), built.params
    _, sown = built.applied
    assert net.rebalanced(params, sown) is params
    assert not any("expert_bias" in jax.tree_util.keystr(p)
                   for p, _ in jax.tree_util.tree_leaves_with_path(params))


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

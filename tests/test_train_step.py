"""Fused train-step tests: descent, target sync cadence, priorities, and the
rows its forwards and its backward pass cover."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ape_x_dqn_tpu.learner.train_step import (
    StepMetrics,
    build_train_step,
    init_train_state,
    make_optimizer,
)
from ape_x_dqn_tpu.models.dueling import DuelingMLP, build_network
from ape_x_dqn_tpu.ops import losses
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch


def _make_batch(rng_key, B=16, obs_dim=6, A=3):
    ks = jax.random.split(rng_key, 4)
    t = NStepTransition(
        obs=jax.random.normal(ks[0], (B, obs_dim)),
        action=jax.random.randint(ks[1], (B,), 0, A),
        reward=jax.random.normal(ks[2], (B,)),
        discount=jnp.full((B,), 0.97),
        next_obs=jax.random.normal(ks[3], (B, obs_dim)),
    )
    return PrioritizedBatch(
        transition=t,
        indices=jnp.arange(B, dtype=jnp.int32),
        is_weights=jnp.ones((B,)),
    )


def _setup(target_sync_freq=4, loss_kind="huber", jit=True):
    net = DuelingMLP(num_actions=3, hidden_sizes=(32,))
    opt = make_optimizer("adam", learning_rate=1e-3)
    state = init_train_state(net, opt, jax.random.PRNGKey(0), jnp.zeros((1, 6)))
    step = build_train_step(
        net, opt, loss_kind=loss_kind, target_sync_freq=target_sync_freq, jit=jit
    )
    return net, state, step


def test_loss_decreases_on_repeated_batch():
    _, state, step = _setup(target_sync_freq=10_000)
    batch = _make_batch(jax.random.PRNGKey(1))
    first = None
    for _ in range(60):
        state, m = step(state, batch)
        if first is None:
            first = float(m.loss)
    assert float(m.loss) < first * 0.5
    assert np.isfinite(float(m.loss))


def test_target_sync_exactly_on_schedule():
    # Intended gate: copy every `freq` steps (reference inverts it, SURVEY §2.8).
    net, state, step = _setup(target_sync_freq=3)
    batch = _make_batch(jax.random.PRNGKey(2))

    def tdiff(s):
        return sum(
            float(jnp.sum(jnp.abs(a - b)))
            for a, b in zip(
                jax.tree_util.tree_leaves(s.params),
                jax.tree_util.tree_leaves(s.target_params),
            )
        )

    diffs = []
    for _ in range(6):
        state, _ = step(state, batch)
        diffs.append(tdiff(state))
    # steps 1,2: drifted; step 3: synced (diff 0); 4,5 drift; 6 synced.
    assert diffs[0] > 0 and diffs[1] > 0
    assert diffs[2] == 0.0
    assert diffs[3] > 0 and diffs[4] > 0
    assert diffs[5] == 0.0


def test_priorities_shape_and_positivity():
    _, state, step = _setup()
    batch = _make_batch(jax.random.PRNGKey(3), B=8)
    state, m = step(state, batch)
    p = np.asarray(m.priorities)
    assert p.shape == (8,)
    assert (p > 0).all()
    # not collapsed to a single value (reference defect)
    assert len(np.unique(p)) > 1


def test_step_counter_increments():
    _, state, step = _setup()
    batch = _make_batch(jax.random.PRNGKey(4))
    assert int(state.step) == 0
    state, _ = step(state, batch)
    state, _ = step(state, batch)
    assert int(state.step) == 2


def test_squared_parity_loss_mode():
    _, state, step = _setup(loss_kind="squared")
    batch = _make_batch(jax.random.PRNGKey(5))
    state, m = step(state, batch)
    assert np.isfinite(float(m.loss))


def test_bf16_params_with_f32_master_track_f32_training():
    """param_dtype=bfloat16 + with_float32_master must track a float32 run:
    the tiny RMSProp-scale updates (~lr) are below bf16 resolution, so
    without the master copy they'd round to zero — with it, loss falls the
    same way as the float32 run."""
    from ape_x_dqn_tpu.learner.train_step import with_float32_master

    def run(param_dtype, wrap):
        net = DuelingMLP(num_actions=3, hidden_sizes=(32,),
                         param_dtype=param_dtype)
        opt = make_optimizer("rmsprop", learning_rate=1e-3, max_grad_norm=None)
        if wrap:
            opt = with_float32_master(opt)
        state = init_train_state(net, opt, jax.random.PRNGKey(0),
                                 jnp.zeros((1, 6)))
        step = build_train_step(net, opt, target_sync_freq=100, jit=False)
        batch = _make_batch(jax.random.PRNGKey(1))
        losses = []
        for _ in range(60):
            state, metrics = step(state, batch)
            losses.append(float(metrics.loss))
        return state, losses

    s16, l16 = run(jnp.bfloat16, wrap=True)
    s32, l32 = run(jnp.float32, wrap=False)
    # Params stayed bf16; master copy lives in opt state as f32.
    leaf16 = jax.tree_util.tree_leaves(s16.params)[0]
    assert leaf16.dtype == jnp.bfloat16
    master_leaf = jax.tree_util.tree_leaves(s16.opt_state[0])[0]
    assert master_leaf.dtype == jnp.float32
    # Same descent trajectory within bf16 forward noise.
    assert l16[-1] < l16[0] * 0.7
    assert abs(l16[-1] - l32[-1]) < 0.25 * abs(l32[0]) + 0.05

    # Low-precision params track cast(master) exactly (the Sterbenz add).
    master = s16.opt_state[0]
    for m, p in zip(jax.tree_util.tree_leaves(master),
                    jax.tree_util.tree_leaves(s16.params)):
        np.testing.assert_array_equal(
            np.asarray(m.astype(jnp.bfloat16)), np.asarray(p)
        )


# ------------------------------------------- the rows a step's products cover

def product_rows(jaxpr) -> set:
    """The leading dimension of every operand and result of every convolution
    and ``dot_general`` of a jaxpr, nested jaxprs included."""
    rows = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("conv_general_dilated", "dot_general"):
            rows |= {v.aval.shape[0] for v in (*eqn.invars, *eqn.outvars) if v.aval.shape}
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    rows |= product_rows(sub)
    return rows


def _recording(opt: optax.GradientTransformation) -> optax.GradientTransformation:
    """``opt``, with the gradients it was last given kept beside its state."""

    def init(params):
        return opt.init(params), jax.tree_util.tree_map(jnp.zeros_like, params)

    def update(grads, state, params=None):
        updates, inner = opt.update(grads, state[0], params)
        return updates, (inner, grads)

    return optax.GradientTransformation(init, update)


def _joined_step(net, opt, loss_kind, axis):
    """The oracle: the step as it was written until PR 29, one online forward
    over ``[obs; next_obs]``, whose backward pass covers 2B rows.  Returns
    (loss, priorities, gradients, updated parameters)."""

    def loss_fn(params, target_params, batch):
        t = batch.transition
        B = t.action.shape[0]
        q_both = net.apply(params, jnp.concatenate([t.obs, t.next_obs], axis=0))[2]
        targets = losses.double_q_target(
            q_both[B:], net.apply(target_params, t.next_obs)[2], t.reward, t.discount)
        delta = losses.td_error(q_both[:B], t.action, targets)
        return losses.td_loss(delta, batch.is_weights, kind=loss_kind), delta

    def step(state, batch):
        (loss, delta), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, state.target_params, batch)
        if axis is not None:  # as build_train_step says of shard_map
            grads = jax.tree_util.tree_map(lambda g: g / jax.lax.psum(1, axis), grads)
            loss = jax.lax.pmean(loss, axis)
        updates, _ = opt.update(grads, state.opt_state, state.params)
        return (loss, losses.priorities_from_td(delta, 1e-6), grads,
                optax.apply_updates(state.params, updates))

    return step


SMALL = {
    "conv": (dict(channels=(8, 8, 8), hidden=32, compute_dtype=jnp.float32), (44, 44, 2)),
    "nature": (dict(channels=(8, 16, 16), hidden=32, compute_dtype=jnp.float32), (44, 44, 4)),
    "mlp": (dict(hidden_sizes=(32,)), (7,)),
}


@pytest.mark.parametrize("sharded", [False, True], ids=["one_chip", "shard_map"])
@pytest.mark.parametrize("loss_kind", ["huber", "squared"])
@pytest.mark.parametrize("kind", sorted(SMALL))
def test_step_is_the_joined_forward_on_half_the_backward_rows(kind, loss_kind, sharded):
    """Two B-row online forwards give the loss, priorities, gradients and
    updated parameters of one 2B-row forward, and no product of the step
    covers 2B rows, where the joined form's do."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ape_x_dqn_tpu.parallel import make_mesh

    kwargs, obs_shape = SMALL[kind]
    n, B = 4 if sharded else 1, 12
    axis = "data" if sharded else None
    net = build_network(kind, 5, **kwargs)
    opt = make_optimizer("rmsprop", learning_rate=1e-2, max_grad_norm=1.0)
    state = init_train_state(net, _recording(opt), jax.random.PRNGKey(0),
                             jnp.zeros((1, *obs_shape)))
    # a target that differs from the online net, so the argmax matters
    state = state.replace(target_params=jax.tree_util.tree_map(
        lambda p: p * 0.9 + 0.01, state.params))
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    batch = PrioritizedBatch(
        transition=NStepTransition(
            obs=jax.random.normal(ks[0], (B, *obs_shape)),
            action=jax.random.randint(ks[1], (B,), 0, 5),
            reward=jax.random.normal(ks[2], (B,)),
            discount=jnp.full((B,), 0.97),
            next_obs=jax.random.normal(ks[3], (B, *obs_shape)),
        ),
        indices=jnp.arange(B, dtype=jnp.int32),
        is_weights=jax.random.uniform(ks[4], (B,), minval=0.2, maxval=1.0),
    )
    step = build_train_step(net, _recording(opt), loss_kind=loss_kind, sync_in_step=False,
                            grad_reduce_axis=axis, jit=False)

    def program(state, batch):
        new, m = step(state, batch)
        return m.loss, m.priorities, new.opt_state[1], new.params

    oracle_state = state.replace(opt_state=state.opt_state[0])
    oracle = lambda batch: _joined_step(net, opt, loss_kind, axis)(oracle_state, batch)  # noqa: E731
    run = lambda batch: program(state, batch)  # noqa: E731
    if sharded:
        wrap = lambda f: shard_map(  # noqa: E731
            f, mesh=make_mesh(n), in_specs=(P("data"),),
            out_specs=(P(), P("data"), P(), P()))
        run, oracle = wrap(run), wrap(oracle)
    got, want = jax.jit(run)(batch), jax.jit(oracle)(batch)
    for name, g, w in zip(("loss", "priorities", "gradients", "parameters"), got, want):
        for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(w)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-6,
                                       err_msg=name)
    assert float(jnp.max(jnp.abs(jax.tree_util.tree_leaves(got[2])[0]))) > 1e-4
    rows, seen = B // n, product_rows(jax.make_jaxpr(run)(batch).jaxpr)
    assert rows in seen and 2 * rows not in seen, sorted(seen)
    assert 2 * rows in product_rows(jax.make_jaxpr(oracle)(batch).jaxpr)


CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "benchmark" / "configs"


@pytest.mark.parametrize("config,rows,tokens", [
    ("ref_b32", 32, 1), ("apex_b512", 512, 1), ("apex_b512", 128, 1),
    ("lfm2moe_q_ep8", 512, 49)],
    ids=["ref_b32", "apex_b512", "apex_b512_shard_of_4", "lfm2moe_q_ep8"])
def test_no_product_covers_two_batches_at_the_cells_shapes(config, rows, tokens):
    """The step traced at the benchmark's shapes (abstract, nothing compiled
    or allocated): every convolution and matrix product, forward and
    backward, covers one batch of rows or of tokens, whatever the network."""
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    prec = cfg["precision"]
    kw = dict(channels=tuple(cfg["channels"]), hidden=cfg["hidden"],
              compute_dtype=jnp.dtype(prec["compute"]))
    if "layer_types" in cfg:
        kw["torso"] = cfg
    net = build_network(cfg["network"], cfg["num_actions"], **kw)
    opt = make_optimizer(cfg["optimizer"], max_grad_norm=cfg["max_grad_norm"],
                         second_moment_dtype=jnp.dtype(prec["second_moment"]))
    step = build_train_step(net, opt, loss_kind=cfg["loss"], sync_in_step=False, jit=False)
    obs = jax.ShapeDtypeStruct((rows, *cfg["obs_shape"]), jnp.uint8)
    vec = lambda dt: jax.ShapeDtypeStruct((rows,), dt)  # noqa: E731
    batch = PrioritizedBatch(
        transition=NStepTransition(obs=obs, action=vec(jnp.int32), reward=vec(jnp.float32),
                                   discount=vec(jnp.float32), next_obs=obs),
        indices=vec(jnp.int32), is_weights=vec(jnp.float32))
    state = jax.eval_shape(
        lambda key: init_train_state(
            net, opt, key, jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8),
            target_dtype=jnp.dtype(prec["target_params"])),
        jax.random.PRNGKey(0))
    seen = product_rows(jax.make_jaxpr(step)(state, batch).jaxpr)
    assert rows in seen and rows * tokens in seen
    assert not seen & {2 * rows, 2 * rows * tokens}, sorted(seen)


# ------------------------------------ the sharded step's gradient reduction

def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _count(jaxpr, name: str) -> int:
    return sum(eqn.primitive.name == name for eqn in _eqns(jaxpr))


@pytest.mark.parametrize("rows_a_shard,n_in,n_out,gathers", [
    (128, 3136, 512, True),    # apex_b512_dp4's streams: 512 x 3,648 < 2 x 1,605,632
    (128, 512, 18, False),     # its advantage head: the kernel is the smaller
    (4, 196, 128, True),
    (1024, 3136, 512, False),  # a batch of 4,096: the rows are the larger
], ids=["streams", "head", "small_streams", "large_batch"])
def test_a_dense_layer_gathers_its_rows_when_that_moves_less(rows_a_shard, n_in, n_out, gathers):
    """The rule of ``rows_gathered_dense`` on a layer's shapes, traced on four
    shards (abstract, nothing computed): the kernel's gradient from the
    gathered input and cotangent, two gathers and no sum over the axis, or
    jax's own sum of every shard's product."""
    from flax import linen as nn
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ape_x_dqn_tpu.learner.train_step import rows_gathered_dense
    from ape_x_dqn_tpu.parallel import make_mesh

    layer = nn.Dense(n_out, use_bias=False, dtype=jnp.bfloat16)

    def grad(params, x):
        def loss(p):
            with nn.intercept_methods(rows_gathered_dense("data")):
                return jnp.sum(layer.apply(p, x).astype(jnp.float32) ** 2)
        return jax.grad(loss)(params)

    fn = shard_map(grad, mesh=make_mesh(4), in_specs=(P(), P("data")), out_specs=P())
    params = {"params": {"kernel": jax.ShapeDtypeStruct((n_in, n_out), jnp.float32)}}
    x = jax.ShapeDtypeStruct((4 * rows_a_shard, n_in), jnp.float32)
    jaxpr = jax.make_jaxpr(fn)(params, x).jaxpr
    assert _count(jaxpr, "all_gather_reduced") == (2 if gathers else 0)
    assert _count(jaxpr, "psum_invariant") == (0 if gathers else 1)
    assert layer.dot_general is None  # the interceptor gave the layer back as it was


def test_the_rule_reads_shapes_not_names():
    """Another network under the axis, the dueling MLP at 64 -> 256 -> 256:
    its two hidden layers gather (32 rows x 320 and x 512 against 2 x 16,384
    and 2 x 65,536), its two heads (256 -> 1, 256 -> 5) keep jax's sum, and so
    do all four biases."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ape_x_dqn_tpu.parallel import make_mesh

    net = DuelingMLP(num_actions=5, hidden_sizes=(256, 256))
    opt = make_optimizer("rmsprop", learning_rate=1e-3)
    state = jax.eval_shape(
        lambda k: init_train_state(net, opt, k, jnp.zeros((1, 64))), jax.random.PRNGKey(0))
    rows = 32
    obs = jax.ShapeDtypeStruct((rows, 64), jnp.float32)
    vec = lambda dt: jax.ShapeDtypeStruct((rows,), dt)  # noqa: E731
    batch = PrioritizedBatch(
        transition=NStepTransition(obs=obs, action=vec(jnp.int32), reward=vec(jnp.float32),
                                   discount=vec(jnp.float32), next_obs=obs),
        indices=vec(jnp.int32), is_weights=vec(jnp.float32))
    step = build_train_step(net, opt, loss_kind="squared", sync_in_step=False,
                            grad_reduce_axis="data", jit=False)
    fn = shard_map(lambda s, b: step(s, b)[0].params, mesh=make_mesh(4),
                   in_specs=(P(), P("data")), out_specs=P())
    jaxpr = jax.make_jaxpr(fn)(state, batch).jaxpr
    assert len(jax.tree_util.tree_leaves(state.params)) == 8
    assert _count(jaxpr, "all_gather_reduced") == 4
    # six leaves, and the means of the loss, of |TD| and of Q
    assert _count(jaxpr, "psum_invariant") == (8 - 2) + 3


def test_a_step_with_no_axis_holds_no_reduction():
    """``grad_reduce_axis=None``: the one-chip cells' step is the parent's,
    no cast to varying, no sum or gather over an axis, no product with a
    backward pass of its own."""
    _, state, step = _setup(jit=False)
    jaxpr = jax.make_jaxpr(step)(state, _make_batch(jax.random.PRNGKey(1))).jaxpr
    seen = {eqn.primitive.name for eqn in _eqns(jaxpr)}
    assert not seen & {"pcast", "pvary", "psum", "psum_invariant", "optimization_barrier",
                       "all_gather", "all_gather_reduced", "custom_vjp_call"}, seen


SYNTHETIC_HLO = """\
HloModule jit_body, is_scheduled=true

%region_1.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}

%fused_computation.7 (param_0.1: bf16[3136,512]) -> (bf16[3136,512], u32[]) {
  %param_0.1 = bf16[3136,512]{1,0} parameter(0)
  %all-reduce.20 = bf16[3136,512]{1,0} all-reduce(%param_0.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  ROOT %custom-call.1 = (bf16[3136,512]{1,0}, u32[]) custom-call(%all-reduce.20)
}

%fused_computation.8 (param_0.2: bf16[3136,512], param_1.2: u32[]) -> bf16[3136,512] {
  %param_0.2 = bf16[3136,512]{1,0} parameter(0)
  %param_1.2 = u32[] parameter(1)
  %all-reduce.21 = bf16[3136,512]{1,0} all-reduce(%param_0.2), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  ROOT %custom-call.2 = bf16[3136,512]{1,0} custom-call(%all-reduce.21, %param_1.2)
}

%body (p: (f32[8,8,4,32], bf16[3136,512], f32[512])) -> (f32[8,8,4,32], bf16[3136,512], f32[512]) {
  %p = (f32[8,8,4,32]{3,2,1,0}, bf16[3136,512]{1,0}, f32[512]{0}) parameter(0)
  %g0 = f32[8,8,4,32]{3,2,1,0} get-tuple-element(%p), index=0
  %g1 = bf16[3136,512]{1,0} get-tuple-element(%p), index=1
  %g2 = f32[512]{0} get-tuple-element(%p), index=2
  %async-collective-start = (bf16[3136,512]{1,0}, u32[]) fusion(%g1), kind=kCustom, calls=%fused_computation.7
  %e0 = bf16[3136,512]{1,0} get-tuple-element(%async-collective-start), index=0
  %e1 = u32[] get-tuple-element(%async-collective-start), index=1
  %async-collective-done = bf16[3136,512]{1,0} fusion(%e0, %e1), kind=kCustom, calls=%fused_computation.8
  %all-reduce-start.3 = f32[512]{0} all-reduce-start(%g2), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  %all-reduce-done.3 = f32[512]{0} all-reduce-done(%all-reduce-start.3)
  %all-reduce.5 = (f32[8,8,4,32]{3,2,1,0:T(4,128)S(1)}, f32[]{:T(128)}) all-reduce(%g0, %s), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  %r0 = f32[8,8,4,32]{3,2,1,0} get-tuple-element(%all-reduce.5), index=0
  ROOT %out = (f32[8,8,4,32]{3,2,1,0}, bf16[3136,512]{1,0}, f32[512]{0}) tuple(%r0, %async-collective-done, %all-reduce-done.3)
}
"""


def test_the_collectives_reader_tells_synchronous_from_in_flight():
    """One synchronous all-reduce (8,192 float32 and a scalar), one
    ``-start``/``-done`` pair and one pair in the TPU compiler's fused form,
    whose steps inside the fusions' computations are not counted again."""
    from ape_x_dqn_tpu.utils.profiling import hlo_collectives

    assert hlo_collectives(SYNTHETIC_HLO) == {"all-reduce": {
        "sync": {"count": 1, "bytes": 4 * 8 * 8 * 4 * 32 + 4},
        "async": {"count": 2, "bytes": 2 * 3136 * 512 + 4 * 512}}}
    assert hlo_collectives("HloModule empty\n") == {}

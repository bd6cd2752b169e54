"""The bootstrap's pair: a conv net's two undifferentiated forwards on
``next_obs`` share their first convolution (``DuelingDQN.q_of_two``), and the
step takes that path for the nets that offer it and for no other."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import torso_contract as contract

from ape_x_dqn_tpu.learner.train_step import build_train_step, init_train_state, make_optimizer
from ape_x_dqn_tpu.models.dueling import TORSO_KINDS, DuelingDQN, build_network
from ape_x_dqn_tpu.ops import losses
from ape_x_dqn_tpu.types import ROUTING, NStepTransition, PrioritizedBatch, TrainState
from test_train_step import _eqns, _recording

# (channels, frames): apex_b512's and ref_b32's stems
CELLS = {"apex_b512": ((32, 64, 64), 4), "ref_b32": ((64, 64, 64), 1)}


def _frames(seed, rows, frames, side=84):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 256, (rows, side, side, frames), dtype=np.uint8))


def _run(fn, *args):
    """``jax.jit(fn)(*args)`` with the backend's optimizer off: nothing here
    is timed, and its passes are most of a case's seconds on the CPU."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})(*args)


def _filled(shapes, seed, dtype=None):
    """Normal leaves at ``shapes``, a kernel's spread by its inputs and a
    bias's a tenth (drawn on the host: an initializer's program is seconds
    to compile here)."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape) / max(np.prod(a.shape[:-1]), 100.0) ** 0.5,
                              dtype or a.dtype), shapes)


@pytest.mark.parametrize("compute", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_pair_is_two_applies(cell, compute):
    """Online parameters in float32, target parameters in bfloat16: to 1e-6
    in float32 compute; on the CPU both types come out bit for bit, and the
    bfloat16 case holds that (a channel's sum has the same operands in the
    same order whether 32 or 64 channels are summed beside it)."""
    channels, frames = CELLS[cell]
    net = DuelingDQN(num_actions=6, channels=channels, compute_dtype=compute)
    obs = _frames(0, 4, frames)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0), obs)
    online, target = _filled(shapes, 1), _filled(shapes, 2, jnp.bfloat16)
    got = _run(net.q_of_two, online, target, obs)
    want = _run(lambda a, b, x: (net.apply(a, x)[2], net.apply(b, x)[2]), online, target, obs)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and g.shape == (4, 6)
        if compute == jnp.bfloat16:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0, atol=1e-6)
    assert float(jnp.max(jnp.abs(got[0] - got[1]))) > 1e-3      # two nets, not one twice


def _apart_step(net, opt, loss_kind, axis):
    """The oracle: the step whose bootstrap runs its two forwards apart, as
    it was written from PR 29 to PR 49.  ``net`` may be anything with
    ``apply``; returns (loss, priorities, gradients, updated parameters)."""

    def loss_fn(params, target_params, batch):
        t = batch.transition
        q_values = net.apply(params, t.obs)[2]
        targets = losses.double_q_target(
            net.apply(jax.lax.stop_gradient(params), t.next_obs)[2],
            net.apply(target_params, t.next_obs)[2], t.reward, t.discount)
        delta = losses.td_error(q_values, t.action, targets)
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


class StoppedPair(DuelingDQN):
    """The same net, the pair's output under an explicit ``stop_gradient``."""

    def q_of_two(self, params_a, params_b, obs):
        return jax.lax.stop_gradient(super().q_of_two(params_a, params_b, obs))


OPT = make_optimizer("rmsprop", learning_rate=1e-2, max_grad_norm=1.0)
class LeakyPair(DuelingDQN):
    """The control: the online half differentiated."""

    def q_of_two(self, params_a, params_b, obs):
        return self.apply(params_a, obs)[2] * 1.0, super().q_of_two(params_a, params_b, obs)[1]


SMALL = {"conv": (dict(channels=(8, 8, 8), hidden=32), (44, 44, 2)),
         "nature": (dict(channels=(8, 16, 16), hidden=32), (44, 44, 4))}


@functools.lru_cache(maxsize=None)
def _state_and_batch(kind):
    """A float32 state with a bfloat16 target of other values than the online
    net's, so the argmax matters, and a batch of 12 rows of bytes."""
    kwargs, obs_shape = SMALL[kind]
    net = DuelingDQN(num_actions=5, compute_dtype=jnp.float32, **kwargs)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0), jnp.zeros((1, *obs_shape), jnp.uint8))
    params = _filled(shapes, 3)
    state = TrainState(
        params=params, target_params=_filled(shapes, 6, jnp.bfloat16),
        opt_state=_recording(OPT).init(params), step=jnp.zeros((), jnp.int32),
        rng=jax.random.PRNGKey(0))
    rng, B = np.random.default_rng(7), 12
    batch = PrioritizedBatch(
        transition=NStepTransition(
            obs=_frames(4, B, obs_shape[2], obs_shape[0]),
            action=jnp.asarray(rng.integers(0, 5, B), jnp.int32),
            reward=jnp.asarray(rng.standard_normal(B), jnp.float32),
            discount=jnp.full((B,), 0.97),
            next_obs=_frames(5, B, obs_shape[2], obs_shape[0]),
        ),
        indices=jnp.arange(B, dtype=jnp.int32),
        is_weights=jnp.asarray(rng.uniform(0.2, 1.0, B), jnp.float32),
    )
    return state, batch


def _small_step(kind, loss_kind, axis, cls=DuelingDQN):
    """(net, (state, batch) -> (loss, priorities, gradients, parameters) of
    the step ``build_train_step`` builds on it) at a small conv net."""
    net = cls(num_actions=5, compute_dtype=jnp.float32, **SMALL[kind][0])
    step = build_train_step(net, _recording(OPT), loss_kind=loss_kind, sync_in_step=False,
                            grad_reduce_axis=axis, jit=False)

    def program(state, batch):
        new, m = step(state, batch)
        return m.loss, m.priorities, new.opt_state[1], new.params

    return net, program


def _assert_same(got, want, atol):
    for name, g, w in zip(("loss", "priorities", "gradients", "parameters"), got, want):
        for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(w)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=atol,
                                       err_msg=name)


@pytest.mark.parametrize("sharded", [False, True], ids=["one_chip", "shard_map"])
@pytest.mark.parametrize("kind,loss_kind", [("conv", "huber"), ("nature", "squared")])
def test_step_with_the_pair_is_the_step_with_two_forwards(kind, loss_kind, sharded):
    """Loss, priorities, gradients and updated parameters of the step built
    on ``DuelingDQN`` are those of the step whose bootstrap applies the net
    twice, under plain ``jit`` and under ``shard_map`` over four."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ape_x_dqn_tpu.parallel import make_mesh

    axis = "data" if sharded else None
    state, batch = _state_and_batch(kind)
    net, program = _small_step(kind, loss_kind, axis)
    oracle_state = state.replace(opt_state=state.opt_state[0])
    oracle = lambda batch: _apart_step(net, OPT, loss_kind, axis)(oracle_state, batch)  # noqa: E731
    run = lambda batch: program(state, batch)  # noqa: E731
    if sharded:
        wrap = lambda f: shard_map(  # noqa: E731
            f, mesh=make_mesh(4), in_specs=(P("data"),),
            out_specs=(P(), P("data"), P(), P()))
        run, oracle = wrap(run), wrap(oracle)
    got, want = _run(lambda b: (run(b), oracle(b)), batch)    # one compile for both
    _assert_same(got, want, atol=1e-6)
    assert float(jnp.max(jnp.abs(jax.tree_util.tree_leaves(got[2])[0]))) > 1e-4


@pytest.mark.parametrize("kind,loss_kind", [("conv", "squared"), ("nature", "huber")])
def test_no_gradient_flows_through_the_pair(kind, loss_kind):
    """The step holds, primitive for primitive, the equations of the step
    with the pair's output under an explicit ``stop_gradient`` (its own two
    equations apart), so no backward pass runs through the pair and its
    gradients are that step's: the online parameters reach the pair stopped,
    the target parameters are not differentiated.  The control, a pair whose
    online half is a differentiated ``apply``, holds more."""
    state, batch = _state_and_batch(kind)

    def equations(cls):
        jaxpr = jax.make_jaxpr(_small_step(kind, loss_kind, None, cls)[1])(state, batch).jaxpr
        return collections.Counter(
            (eqn.primitive.name, *(str(v.aval) for v in eqn.outvars)) for eqn in _eqns(jaxpr)
            if eqn.primitive.name != "stop_gradient")

    step = equations(DuelingDQN)
    assert step == equations(StoppedPair)
    assert sum((equations(LeakyPair) - step).values()) > 10


def _first_window_convs(jaxpr) -> list:
    """The output features of every convolution of a jaxpr with the stem's
    first window on an 84 x 84 frame: the forward first convolutions, not
    their gradients."""
    return sorted(
        eqn.outvars[0].aval.shape[-1] for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "conv_general_dilated"
        and eqn.params["window_strides"] == (4, 4) and eqn.invars[1].aval.shape[:2] == (8, 8)
        and eqn.outvars[0].aval.shape[1:3] == (20, 20))


@pytest.mark.parametrize("sharded", [False, True], ids=["one_chip", "shard_map"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_conv_step_holds_two_first_convolutions(cell, sharded):
    """The step traced at the cell's stem (abstract: nothing compiled): two
    forward convolutions with the 8 x 8 window, the differentiated one of
    ``channels[0]`` outputs and the pair's of twice that, where there were
    three of ``channels[0]``."""
    channels, frames = CELLS[cell]
    net = build_network("conv", 6, channels=channels, hidden=32)
    assert callable(getattr(net, "q_of_two", None))
    opt = make_optimizer("rmsprop", learning_rate=1e-4)
    obs = jax.ShapeDtypeStruct((8, 84, 84, frames), jnp.uint8)
    state = jax.eval_shape(lambda k: init_train_state(
        net, opt, k, jnp.zeros((1, 84, 84, frames), jnp.uint8), target_dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    vec = lambda dt: jax.ShapeDtypeStruct((8,), dt)  # noqa: E731
    batch = PrioritizedBatch(
        transition=NStepTransition(obs=obs, action=vec(jnp.int32), reward=vec(jnp.float32),
                                   discount=vec(jnp.float32), next_obs=obs),
        indices=vec(jnp.int32), is_weights=vec(jnp.float32))
    step = build = build_train_step(net, opt, sync_in_step=False, jit=False,
                                    grad_reduce_axis="data" if sharded else None)
    if sharded:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ape_x_dqn_tpu.parallel import make_mesh

        step = shard_map(lambda s, b: build(s, b)[0].params, mesh=make_mesh(4),
                         in_specs=(P(), P("data")), out_specs=P())
    jaxpr = jax.make_jaxpr(step)(state, batch).jaxpr
    assert _first_window_convs(jaxpr) == [channels[0], 2 * channels[0]]


def _toy(kind):
    if kind == "mlp":
        return build_network("mlp", 6, hidden_sizes=(16,)), jnp.zeros((2, 7))
    return contract.network(kind), contract.obs(jax.random.PRNGKey(0))


@pytest.mark.parametrize("kind", ["mlp", *sorted(TORSO_KINDS)])
def test_every_other_net_is_applied_three_times(kind, monkeypatch):
    """``DuelingMLP`` and a toy torso of every kind offer no pair, and the
    step built on them applies the network three times, the path they had.
    Traced abstractly; a forward is traced once and the counted applies hand
    back zeros of its shapes (a torso's backward pass is seconds to trace)."""
    net, x = _toy(kind)
    assert not hasattr(net, "q_of_two")
    opt = make_optimizer("rmsprop", learning_rate=1e-4)
    # one trace for the parameters, the outputs and what the layers sow
    out, variables = jax.eval_shape(lambda k: net.apply(
        {}, x, rngs={"params": k}, mutable=["params", ROUTING]), jax.random.PRNGKey(0))
    params = {"params": variables.pop("params")}
    shapes = (out, variables)
    state = TrainState(
        params=params, target_params=params, opt_state=jax.eval_shape(opt.init, params),
        step=jax.ShapeDtypeStruct((), jnp.int32), rng=jax.ShapeDtypeStruct((2,), jnp.uint32))
    applies = []

    def counted(self, variables, obs, **kwargs):
        applies.append((obs.shape, kwargs))
        return jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), shapes)

    monkeypatch.setattr(type(net), "apply", counted)
    jax.eval_shape(build_train_step(net, opt, sync_in_step=False, jit=False),
                   state, contract.batch_of(x))
    assert applies == [(x.shape, {"mutable": [ROUTING]})] * 3

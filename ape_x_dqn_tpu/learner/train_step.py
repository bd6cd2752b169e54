"""The fused learner step — one XLA program per gradient update.

This is the north-star fusion (BASELINE.json): everything the reference
learner does per update across four call sites and three host↔host RPCs
(reference learner.py:63-80 — sample unpack, double-Q target, TD error, loss,
RMSProp step, target-net sync, priority computation) compiles into a single
jitted function:

    train_step(state, batch) -> (new_state, StepMetrics)

Semantics implemented are the *intended* ones (SURVEY §2.8 defect register):
  * target net copies every ``target_sync_freq`` steps (the reference's modulo
    gate is inverted — learner.py:60);
  * per-transition priorities (the reference collapses them — learner.py:50);
  * terminal masking via the n-step discount (the reference bootstraps through
    episode ends);
  * RMSProp decay is decay, not L2 weight-decay (learner.py:26 misroutes it).

The returned function is pure and donation-friendly: ``state`` is donated so
params/opt-state update in place in HBM.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct

from ape_x_dqn_tpu.ops import losses
from ape_x_dqn_tpu.types import ROUTING, PrioritizedBatch, TrainState
from ape_x_dqn_tpu.utils.profiling import launch_span, pass_, stage

@struct.dataclass
class StepMetrics:
    loss: jax.Array            # float32 []
    mean_abs_td: jax.Array     # float32 []
    max_abs_td: jax.Array      # float32 []
    priorities: jax.Array      # float32 [B] — new replay priorities
    mean_q: jax.Array          # float32 []
    # A network's ``routing_metrics`` of what its layers sowed (types.ROUTING),
    # summed over the step's three forwards; None for a network without them.
    routing: Optional[dict] = None
    # A network's ``attention_metrics`` of the step's three forwards, from the
    # shapes (blocked attention's pairs in the mask and blocks visited).
    attention: Optional[dict] = None
    # A network's ``scan_metrics`` of the step's three forwards, from the
    # shapes (state-space layers: chunks walked, tokens with and without the
    # padding to whole chunks).
    scan: Optional[dict] = None
    # A network's ``delta_metrics`` likewise (delta-rule layers).
    delta: Optional[dict] = None


def _scale_by_rms_lowp(
    decay: float, eps: float, second_moment_dtype
) -> optax.GradientTransformation:
    """``optax.scale_by_rms`` with the second-moment EMA stored in a reduced
    dtype (bfloat16 halves its HBM read+write per step — the optimizer is
    bandwidth-bound, ~91 µs/step measured for 3.4M params on a v5e).

    The EMA is *updated* in float32 (nu is upcast, blended, then stored back
    down) so the only loss is ~0.4% relative rounding on a statistic that is
    itself a noisy average — noise-level for RMSProp's denominator.
    """

    def init_fn(params):
        nu = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, dtype=second_moment_dtype), params
        )
        return optax.ScaleByRmsState(nu=nu)

    def update_fn(updates, state, params=None):
        del params
        nu32 = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32), state.nu
        )
        nu32 = jax.tree_util.tree_map(
            lambda g, v: decay * v + (1.0 - decay) * jnp.square(g.astype(jnp.float32)),
            updates,
            nu32,
        )
        # Same formula as optax.scale_by_rms(eps_in_sqrt=True), its default
        # and what optax.rmsprop uses: g * rsqrt(nu + eps).
        scaled = jax.tree_util.tree_map(
            lambda g, v: (g.astype(jnp.float32) * jax.lax.rsqrt(v + eps)).astype(g.dtype),
            updates,
            nu32,
        )
        new_nu = jax.tree_util.tree_map(
            lambda v: v.astype(second_moment_dtype), nu32
        )
        return scaled, optax.ScaleByRmsState(nu=new_nu)

    return optax.GradientTransformation(init_fn, update_fn)


def with_float32_master(
    optimizer: optax.GradientTransformation,
) -> optax.GradientTransformation:
    """Mixed-precision wrapper: run ``optimizer`` against a float32 master
    copy of the params kept inside the optimizer state, while the network's
    own params live in bfloat16.

    Why: with bfloat16 params the per-step update (~lr · normalized-grad,
    ~6e-5) is below bfloat16's resolution at typical weight magnitudes, so
    naive ``apply_updates`` rounds most updates to zero and learning stalls.
    The master copy accumulates in float32; the emitted update is exactly
    the delta that lands the low-precision params on ``cast(master)`` (the
    add is lossless whenever params and master are within 2× of each other —
    Sterbenz — i.e. always, for a per-step change this small).

    HBM accounting (3.4M-param net, per step): forward/backward read params
    at half width (−13 MB and the f32→bf16 cast op disappears), while the
    optimizer carries the master r/w (+26 MB) but drops the f32 param r/w
    (−26 MB) — net ~−20 MB/step of a ~100 MB/step bandwidth-bound program.
    """

    def init_fn(params):
        master = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), params
        )
        return (master, optimizer.init(master))

    def update_fn(updates, state, params):
        master, inner = state
        g32 = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), updates
        )
        upd, inner = optimizer.update(g32, inner, master)
        new_master = optax.apply_updates(master, upd)
        emitted = jax.tree_util.tree_map(
            lambda m, p: m.astype(p.dtype) - p, new_master, params
        )
        return emitted, (new_master, inner)

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(
    kind: str = "rmsprop",
    learning_rate: float = 0.00025 / 4,
    rmsprop_decay: float = 0.95,
    rmsprop_eps: float = 1.5e-7,
    adam_b1: float = 0.9,
    adam_b2: float = 0.999,
    max_grad_norm: float | None = 40.0,
    second_moment_dtype=None,
) -> optax.GradientTransformation:
    """Reference-parity RMSProp (lr 0.00025/4, eps 1.5e-7 — learner.py:26,
    with decay routed correctly) or Adam, with optional grad clipping.

    ``second_moment_dtype=jnp.bfloat16`` (rmsprop only) stores the RMS EMA
    in bfloat16 — an HBM-traffic knob for the fused throughput path; the
    chain-MDP learning test covers this mode end-to-end.  ``max_grad_norm=
    None`` drops the global-norm clip (the reference has none — learner.py:26
    — and the clip costs an extra full pass over the gradients)."""
    if kind == "rmsprop":
        if second_moment_dtype is not None:
            opt = optax.chain(
                _scale_by_rms_lowp(rmsprop_decay, rmsprop_eps, second_moment_dtype),
                optax.scale(-learning_rate),
            )
        else:
            opt = optax.rmsprop(learning_rate, decay=rmsprop_decay, eps=rmsprop_eps)
    elif kind == "adam":
        if second_moment_dtype is not None:
            raise ValueError("second_moment_dtype is only supported for rmsprop")
        opt = optax.adam(learning_rate, b1=adam_b1, b2=adam_b2)
    else:
        raise ValueError(f"unknown optimizer kind: {kind}")
    if max_grad_norm is not None:
        opt = optax.chain(optax.clip_by_global_norm(max_grad_norm), opt)
    return opt


def rows_gathered_dense(axis: str):
    """A ``flax.linen.intercept_methods`` interceptor for the sharded step's
    differentiated forward: a ``nn.Dense`` whose kernel is large beside the
    batch gets its kernel's gradient from the *gathered rows* instead of
    from an all-reduce of every shard's own product.

    Under ``shard_map`` jax sums a kernel's gradient over the axis where the
    backward pass yields it: ``in x out`` elements through a ring, twice.
    The same sum is ``X^T D`` over all shards' rows: the layer's input ``X``
    and its output's cotangent ``D`` are ``B x (in + out)`` elements, gathered
    once, and the product over all B rows accumulates in float32 what the
    all-reduce summed in the compute type after each shard had rounded its
    own.  A layer gathers when that moves fewer elements (``B (in + out) <
    2 in out``, both in the layer's compute type): the dueling net's two 3136
    x 512 streams at a global batch of 512, not its heads.  The rule reads the
    layer's shapes, never its name; every other gradient is summed where jax's
    transpose sums it, as before.
    """
    contract_rows = (((0,), (0,)), ((), ()))

    @jax.custom_vjp
    def dot(x, w):
        return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))

    def fwd(x, w):
        return dot(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        # ``to="reduced"``: every shard holds all rows, and what is computed
        # from them alone is typed the same on every shard, as the kernel is.
        rows = lambda a: jax.lax.all_gather(a, axis, axis=0, tiled=True, to="reduced")  # noqa: E731
        dx = jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())))
        dw = jax.lax.dot_general(rows(x), rows(g), contract_rows,
                                 preferred_element_type=jnp.float32)
        return dx, dw.astype(w.dtype)

    dot.defvjp(fwd, bwd)

    def dot_general(lhs, rhs, dimension_numbers, precision=None, **kwargs):
        rows, (n_in, n_out) = lhs.shape[0] * jax.lax.axis_size(axis), rhs.shape
        if (lhs.ndim != 2 or precision is not None or kwargs
                or rows * (n_in + n_out) >= 2 * n_in * n_out):
            return jax.lax.dot_general(lhs, rhs, dimension_numbers, precision=precision, **kwargs)
        return dot(lhs, rhs)

    def interceptor(next_fun, args, kwargs, context):
        layer = context.module
        if not (isinstance(layer, nn.Dense) and context.method_name == "__call__"
                and layer.dot_general is None and layer.dot_general_cls is None):
            return next_fun(*args, **kwargs)
        # ``dot_general`` is the hook ``nn.Dense`` has for its product; the
        # bound layer is frozen, and is given back as it was
        object.__setattr__(layer, "dot_general", dot_general)
        try:
            return next_fun(*args, **kwargs)
        finally:
            object.__setattr__(layer, "dot_general", None)

    return interceptor


@launch_span("train_state")
def init_train_state(
    network: nn.Module,
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    sample_obs: jax.Array,
    target_dtype=None,
) -> TrainState:
    """Initialize params/target/opt-state from one example observation batch.

    ``target_dtype=jnp.bfloat16`` stores the target net in bfloat16: it is
    only ever read for inference (the double-Q bootstrap), so the cast costs
    ~0.4% relative rounding on Q-targets while halving the target-params HBM
    read on every step.  Syncs cast online → target dtype."""
    params = network.init(rng, sample_obs)
    # Leaves a network keeps in float32 whatever the target's type (a
    # router's scores decide a top-k; rounding them decides it otherwise).
    keep = tuple(getattr(network, "float32_leaves", ()))
    if target_dtype is None:
        target = jax.tree_util.tree_map(jnp.copy, params)
    elif keep:
        target = jax.tree_util.tree_map_with_path(
            lambda path, p: jnp.copy(p)
            if p.dtype == target_dtype or any(
                k in jax.tree_util.keystr(path) for k in keep)
            else p.astype(target_dtype),
            params,
        )
    else:
        # A no-op astype (param dtype == target_dtype, e.g. bf16 params +
        # bf16 target) returns the SAME array — params and target_params
        # would alias one buffer, and donating the TrainState then
        # double-donates it: the TPU runtime rejects the program with an
        # opaque INVALID_ARGUMENT (round-3's "bf16 params don't compile"
        # was exactly this).  Force a real copy on the no-op path.
        target = jax.tree_util.tree_map(
            lambda p: (
                jnp.copy(p) if p.dtype == target_dtype
                else p.astype(target_dtype)
            ),
            params,
        )
    return TrainState(
        params=params,
        target_params=target,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
        rng=rng,
    )


def build_train_step(
    network: nn.Module,
    optimizer: optax.GradientTransformation,
    loss_kind: str = "huber",
    huber_kappa: float = 1.0,
    target_sync_freq: int = 2500,
    use_is_weights: bool = True,
    priority_epsilon: float = 1e-6,
    sync_in_step: bool = True,
    grad_reduce_axis: str | None = None,
    jit: bool = True,
) -> Callable[[TrainState, PrioritizedBatch], Tuple[TrainState, StepMetrics]]:
    """Build the fused step.  All knobs are static — baked into the XLA program.

    ``sync_in_step=False`` omits the per-step target-net sync: the target
    params pass through untouched and the caller syncs at its own cadence
    (the fused K-step scan hoists the sync to call boundaries — the per-step
    ``jnp.where`` tree-map rewrites the full target pytree in HBM every step,
    measured ~95 µs/step on a v5e for a 3.4M-param net, all wasted between
    the every-2500-step syncs).

    ``grad_reduce_axis``: set to a mesh axis name when the step runs inside
    ``shard_map`` with the batch sharded over that axis (the sharded fused
    learners, replay/device_dp.py and replay/device_dedup_dp.py).  Where the
    gradients are summed over the axis, and in what order: the parameters
    enter unvarying and the batch varying, so jax's transpose sums each
    leaf's gradient where the backward pass yields it (head first, stem
    last), in the type the layer computed in, and XLA joins those sums into
    one all-reduce per type at the end of the backward pass.  One kind of
    leaf is kept out of it: the kernel of a ``nn.Dense`` that is large beside
    the batch takes its gradient from the rows gathered over the axis
    (``rows_gathered_dense``: the layer's input and its output's cotangent,
    one product over all the rows, accumulated in float32), which is the same
    sum and moves fewer bytes.  The sums are divided by the axis extent in one
    place, below, to the global batch mean; the clip sees the reduced
    gradient; the update is identical on every shard.  Scalar metrics are
    ``pmean``/``pmax``ed; per-row priorities stay per-shard.  Under plain
    ``jit``/pjit leave it ``None``: XLA's SPMD partitioner inserts the
    all-reduce itself from the batch sharding (parallel/dp.py), and the step
    holds no collective and no layer with a backward pass of its own.
    """

    # A network whose layers sow counts (types.ROUTING) reads them itself:
    # ``routing_metrics(sown)`` for StepMetrics, ``rebalanced(params, sown)``
    # for what it moves by them after the update (an expert bias).
    routing_metrics = getattr(network, "routing_metrics", None)
    rebalanced = getattr(network, "rebalanced", None)
    attention_metrics = getattr(network, "attention_metrics", None)
    scan_metrics = getattr(network, "scan_metrics", None)
    delta_metrics = getattr(network, "delta_metrics", None)
    # A network whose nets share a first layer on the same observations
    # computes the bootstrap's pair itself: ``q_of_two(online, target, obs)``.
    q_of_two = getattr(network, "q_of_two", None)

    gathered = None if grad_reduce_axis is None else rows_gathered_dense(grad_reduce_axis)

    def q_of(params, obs, differentiated: bool = False):
        """(Q, what the network's layers sowed)."""
        with (nn.intercept_methods(gathered) if differentiated and gathered
              else contextlib.nullcontext()):
            out, sown = network.apply(params, obs, mutable=[ROUTING])
        return out[2], sown

    def loss_fn(params, target_params, batch: PrioritizedBatch):
        # One scope for the loss: AD names its ops jvp(stage:forward) and
        # the backward pass's transpose(jvp(stage:forward)).
        with stage("forward"):
            t = batch.transition
            q_values, s1 = q_of(params, t.obs, differentiated=True)
            # The bootstrap's forwards run apart from the differentiated one.
            # next_obs reaches the loss through an argmax only: joined with
            # obs in one 2B forward, its B rows ride through the whole
            # backward pass with cotangents of zero (neither jax nor XLA drops
            # them) and the forward keeps their activations; a product over 2B
            # rows never paid for the saved read of the parameters on a v5e
            # (PERF.md section 6, PR 29).  The two of them read the same
            # next_obs: a network that offers the pair joins them along the
            # first layer's output channels, which the array has room for
            # (DuelingDQN; PERF.md section 6, PR 49), and sows nothing.
            # Named as a pass of their own (``pass.bootstrap_step_us``).
            online_params = jax.lax.stop_gradient(params)
            with pass_("bootstrap"):
                if q_of_two is not None:
                    q_next_online, q_next_target = q_of_two(
                        online_params, target_params, t.next_obs)
                    s2 = s3 = {}
                else:
                    q_next_online, s2 = q_of(online_params, t.next_obs)
                    q_next_target, s3 = q_of(target_params, t.next_obs)
            targets = losses.double_q_target(
                q_next_online, q_next_target, t.reward, t.discount
            )
            delta = losses.td_error(q_values, t.action, targets)
            weights = batch.is_weights if use_is_weights else None
            loss = losses.td_loss(
                delta, weights, kind=loss_kind, huber_kappa=huber_kappa)
            return loss, (delta, q_values, ((s1, s2), s3))

    def train_step(state: TrainState, batch: PrioritizedBatch):
        (loss, (delta, q_values, (online, sown_target))), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params, state.target_params, batch)
        add = lambda *trees: jax.tree_util.tree_map(lambda *xs: sum(xs), *trees)  # noqa: E731
        routing = None if routing_metrics is None else add(
            *(routing_metrics(s) for s in (*online, sown_target)))
        if routing:     # a share is the three forwards' mean, every other counter their sum
            routing = {k: v / 3.0 if k.endswith("_share") else v for k, v in routing.items()}
        def of_three_forwards(counter):
            counted = counter and counter(batch.transition.obs.shape)
            return {k: jnp.float32(3.0 * v) for k, v in counted.items()} if counted else None

        attention, scan, delta_rule = (of_three_forwards(counter) for counter in (
            attention_metrics, scan_metrics, delta_metrics))
        # Under plain pjit the mean inside loss_fn makes XLA insert the
        # gradient all-reduce over ICI automatically.  Inside shard_map
        # (varying-axes AD semantics): the params enter unvarying while the
        # batch is varying, so jax's transpose ALREADY psums the param
        # cotangents over the axis, and a gathered layer's product covers
        # every shard's rows: grads arrive as Σ_shards(local-mean grads).
        # Dividing by the axis extent, here and nowhere else, yields the
        # global batch mean (equal-size shards); an explicit pmean here would
        # double-count (measured: exactly n× updates).  The scalar loss is
        # still per-shard varying and needs a real pmean for reporting.
        with stage("optimizer"):
            if grad_reduce_axis is not None:
                n_sh = jax.lax.psum(1, grad_reduce_axis)
                grads = jax.tree_util.tree_map(lambda g: g / n_sh, grads)
                loss = jax.lax.pmean(loss, grad_reduce_axis)
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            if rebalanced is not None:
                # by the online forwards' counts, every shard's together
                loads = add(*online)
                if grad_reduce_axis is not None:
                    loads = jax.lax.psum(loads, grad_reduce_axis)
                new_params = rebalanced(new_params, loads)
        step = state.step + 1
        if sync_in_step:
            # Intended target sync: copy exactly every target_sync_freq steps
            # (reference learner.py:60 inverts this gate).
            sync = (step % target_sync_freq) == 0
            new_target = jax.tree_util.tree_map(
                lambda online, target: jnp.where(
                    sync, online.astype(target.dtype), target
                ),
                new_params,
                state.target_params,
            )
        else:
            new_target = state.target_params
        mean_abs_td = jnp.mean(jnp.abs(delta))
        max_abs_td = jnp.max(jnp.abs(delta))
        mean_q = jnp.mean(q_values)
        if grad_reduce_axis is not None:
            mean_abs_td = jax.lax.pmean(mean_abs_td, grad_reduce_axis)
            max_abs_td = jax.lax.pmax(max_abs_td, grad_reduce_axis)
            mean_q = jax.lax.pmean(mean_q, grad_reduce_axis)
        with stage("restamp"):
            priorities = losses.priorities_from_td(delta, priority_epsilon)
        metrics = StepMetrics(
            loss=loss,
            mean_abs_td=mean_abs_td,
            max_abs_td=max_abs_td,
            priorities=priorities,
            mean_q=mean_q,
            routing=routing,
            attention=attention,
            scan=scan,
            delta=delta_rule,
        )
        new_state = TrainState(
            params=new_params,
            target_params=new_target,
            opt_state=new_opt_state,
            step=step,
            rng=state.rng,
        )
        return new_state, metrics

    if jit:
        return jax.jit(train_step, donate_argnums=(0,))
    return train_step

"""Plain reference for one learner step: dueling forward, n-step double-Q
target, importance-weighted loss, global-norm clip, one RMSProp update.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
with no kernels, replay or batching tricks, and nothing imported from the
program.  Equations:

  torso   Conv 8x8/4 -> Conv 4x4/2 -> Conv 3x3/1 (VALID, ReLU), flatten
  streams v = W_v2 relu(W_v1 x),  a = W_a2 relu(W_a1 x)
  Q       = v + a - mean_a(a)                    (Wang et al. 2016, eq. 9)
  a*      = argmax_a Q_online(s', a)
  G       = R + discount * Q_target(s', a*)      (discount = gamma^n, 0 past a terminal)
  delta   = Q_online(s, A) - G
  loss    = mean_i w_i l(delta_i),  l = delta^2/2 or Huber(kappa=1)
  clip    g <- g * min(1, c / ||g||_2)           (when the configuration clips)
  RMSProp nu <- d nu + (1-d) g^2;  p <- p - lr g / sqrt(nu + eps)
  priority p_i = |delta_i| + 1e-6

``precision`` other than ``stated`` makes a control, the same equations in
a precision below the one the configurations state (float32 parameters and
gradients, bfloat16 activations):

  bf16_held        parameters, moments, gradients and every intermediate held
                   in bfloat16, no float32 master copy
  fp8_activations  every activation rounded to 5 exponent and 2 mantissa bits
                   (e5m2; with 4 and 3 the small activations flush to zero)
  bf16_gradients   the gradients alone rounded to bfloat16

What is held is rounded with ``lax.reduce_precision``: XLA may keep excess
precision through a chain of bfloat16 operations (on the TPU it did, and the
control read like a float32 run), but it may not skip an explicit rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LAYERS = ("conv1", "conv2", "conv3", "value_hidden", "advantage_hidden",
          "value_head", "advantage_head")
_STRIDES = {"conv1": 4, "conv2": 2, "conv3": 1}
_KERNELS = {"conv1": 8, "conv2": 4, "conv3": 3}
PRIORITY_EPS = 1e-6
PRECISIONS = ("stated", "bf16_held", "fp8_activations", "bf16_gradients")


def weight_shapes(cfg: dict) -> dict:
    h, _, cin = cfg["obs_shape"]
    shapes = {}
    for name, ch in zip(LAYERS[:3], cfg["channels"]):
        k, s = _KERNELS[name], _STRIDES[name]
        shapes[name] = (k, k, cin, ch)
        cin, h = ch, (h - k) // s + 1
    flat, hid = h * h * cin, cfg["hidden"]
    shapes["value_hidden"] = (flat, hid)
    shapes["advantage_hidden"] = (flat, hid)
    shapes["value_head"] = (hid, 1)
    shapes["advantage_head"] = (hid, cfg["num_actions"])
    return shapes


def make_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights: LeCun-normal kernels, small non-zero biases."""
    out = {}
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        fan_in = 1
        for d in shape[:-1]:
            fan_in *= d
        out[name] = {
            "w": jax.random.normal(kw, shape, jnp.float32) / jnp.sqrt(fan_in),
            "b": 0.01 * jax.random.normal(kb, (shape[-1],), jnp.float32),
        }
    return out


def forward(weights: dict, obs, dtype=jnp.float32, act=lambda x: x):
    """Q values [B, A] for uint8 NHWC observations; ``act`` rounds every
    activation (a control's)."""
    x = act(obs.astype(dtype) / jnp.asarray(255.0, dtype))
    for name in LAYERS[:3]:
        s = _STRIDES[name]
        x = jax.lax.conv_general_dilated(
            x, weights[name]["w"].astype(dtype), (s, s), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + weights[name]["b"].astype(dtype)
        x = act(jnp.maximum(x, 0))
    x = x.reshape(x.shape[0], -1)

    def dense(name, h):
        return h @ weights[name]["w"].astype(dtype) + weights[name]["b"].astype(dtype)

    v = dense("value_head", act(jnp.maximum(dense("value_hidden", x), 0)))
    a = dense("advantage_head", act(jnp.maximum(dense("advantage_hidden", x), 0)))
    return v + a - jnp.mean(a, axis=-1, keepdims=True)


def td_errors(weights, target_weights, batch, dtype=jnp.float32, act=lambda x: x):
    q = forward(weights, batch["obs"], dtype, act)
    q_next = forward(weights, batch["next_obs"], dtype, act)
    q_next_target = forward(target_weights, batch["next_obs"], dtype, act)
    best = jnp.argmax(q_next, axis=-1)
    rows = jnp.arange(q.shape[0])
    target = batch["reward"].astype(dtype) + batch["discount"].astype(dtype) * q_next_target[rows, best]
    return q[rows, batch["action"]] - jax.lax.stop_gradient(target)


def loss_fn(weights, target_weights, batch, cfg, dtype=jnp.float32, act=lambda x: x):
    delta = td_errors(weights, target_weights, batch, dtype, act)
    if cfg["loss"] == "squared":
        per = 0.5 * delta * delta
    elif cfg["loss"] == "huber":
        quad = jnp.minimum(jnp.abs(delta), 1.0)
        per = 0.5 * quad * quad + (jnp.abs(delta) - quad)
    else:
        raise ValueError(f"unknown loss {cfg['loss']!r}")
    return jnp.mean(per * batch["is_weights"].astype(dtype)), delta


def _hold(tree, dtype):
    """``tree`` as stored in ``dtype``: rounded for real, then cast."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)
    info = jnp.finfo(dtype)
    return jax.tree_util.tree_map(
        lambda x: jax.lax.reduce_precision(
            x.astype(jnp.float32), info.nexp, info.nmant).astype(dtype), tree)


def learner_step(weights, target_weights, nu, batch, cfg, precision="stated"):
    """One update.  Returns (new_weights, new_nu, td_errors, priorities, loss),
    all as float32 whatever ``precision`` computed them."""
    if cfg["optimizer"] != "rmsprop":
        raise ValueError(f"the reference implements rmsprop, not {cfg['optimizer']!r}")
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
    dtype = jnp.bfloat16 if precision == "bf16_held" else jnp.float32
    act = (lambda x: jax.lax.reduce_precision(x, 5, 2)) \
        if precision == "fp8_activations" else (lambda x: x)
    weights, target_weights, nu = (_hold(t, dtype) for t in (weights, target_weights, nu))
    with jax.default_matmul_precision("highest"):
        (loss, delta), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            weights, target_weights, batch, cfg, dtype, act
        )
    grads = _hold(grads, dtype)
    if precision == "bf16_gradients":
        grads = _hold(_hold(grads, jnp.bfloat16), jnp.float32)
    leaves = jax.tree_util.tree_leaves(grads)
    if cfg.get("max_grad_norm") is not None:
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.minimum(1.0, cfg["max_grad_norm"] / jnp.maximum(norm, 1e-30)).astype(dtype)
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    d = jnp.asarray(cfg["rmsprop_decay"], dtype)
    eps = jnp.asarray(cfg["rmsprop_eps"], dtype)
    lr = jnp.asarray(cfg["learning_rate"], dtype)
    new_nu = _hold(jax.tree_util.tree_map(
        lambda v, g: d * v + (1 - d) * g * g, nu, grads), dtype)
    new_weights = _hold(jax.tree_util.tree_map(
        lambda p, g, v: p - lr * g / jnp.sqrt(v + eps), weights, grads, new_nu), dtype)
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    return (f32(new_weights), f32(new_nu), delta.astype(jnp.float32),
            jnp.abs(delta).astype(jnp.float32) + PRIORITY_EPS, loss.astype(jnp.float32))

"""Plain reference for the LFM2-MoE Q-network and one learner step on it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program.  The learner step is the one
``dueling_dqn.py``'s docstring sets out (double-Q target, importance-weighted
loss, global-norm clip, one RMSProp update, priorities ``|delta| + 1e-6``);
the network between the convolutional stem and the dueling head is

  stem    Conv 8x8/4 -> Conv 4x4/2 -> Conv 3x3/1 (VALID, ReLU): [B, h, w, C]
  tokens  the h*w positions in raster order, centred over the positions of
          a frame: x_0 = W_tok (z - mean_p z), no bias
  layer   h <- h + Op(RMSNorm(h));  h <- h + FFN(RMSNorm(h))
          RMSNorm(h) = w * h / sqrt(mean(h^2) + eps), statistics in float32
  ShortConv(u)  [b, c, x] = split(W_in u, 3);  z_t = b_t * x_t
                s_t = sum_j k_j z_{t-(L-1)+j}   (depthwise, causal, zero left padding)
                y_t = W_out (c_t * s_t)
  Attention(u)  q = W_q u, k = W_k u, v = W_v u;  q, k <- RMSNorm over the head,
                RoPE(theta) over the whole head (rotated in halves), positions 0..S-1;
                causal softmax(q k^T / sqrt(d)) v, each key-value head serving
                heads/kv_heads query heads;  y = W_o concat
  SwiGLU_w(u)   W_2 (silu(W_1 u) * W_3 u)
  MoE(u)        s = sigmoid(W_r u) in float32;  I = top_k(s + bias)
                g_i = s_i / (sum_{j in I} s_j + 1e-6), i in I
                y = sum_{i in I and i held} g_i SwiGLU^(i)(u)
  readout RMSNorm, mean over the tokens, two ReLU streams, Q = V + A - mean(A)

The gates are normalised over all the chosen experts, held or not; what an
expert that is not held would have added is left out.  Every held expert is
computed on every token and masked (one product over the expert axis): plain,
sixteen times the work of the program's grouped product.  The batch is walked
in blocks of ``ROW_BLOCK`` rows (``lax.map`` over a checkpointed block), so
the step fits one chip at the published widths.

``precision`` other than ``stated`` makes a control, as in ``dueling_dqn.py``:
``bf16_held`` (everything held in bfloat16), ``fp8_activations`` (every
activation rounded to e5m2), ``bf16_gradients``.

The expert bias is the model's load-balancing buffer: no gradient reaches
it, and after each update the learner step moves it against the load error of
every one of the router's outputs over the step's two online forwards,
``bias -= rate * clip(load / mean(load) - 1, -1, 1)``
(``expert_bias_update_rate``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STEM = ("conv1", "conv2", "conv3")
_STRIDES = {"conv1": 4, "conv2": 2, "conv3": 1}
_KERNELS = {"conv1": 8, "conv2": 4, "conv3": 3}
PRIORITY_EPS = 1e-6
PRECISIONS = ("stated", "bf16_held", "fp8_activations", "bf16_gradients")
ROW_BLOCK = 32


def layer_kinds(cfg: dict) -> list:
    """[(op, ffn)] of the layers run: ``layers_held`` of ``layer_types``, the
    first ``num_dense_layers`` of the model dense."""
    held = cfg.get("layers_held", range(cfg["num_hidden_layers"]))
    return [(cfg["layer_types"][i], "dense" if i < cfg["num_dense_layers"] else "moe")
            for i in held]


def experts_held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg.get("router_outputs", cfg["num_experts"]))))


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} or, for a layer, {name: {name: shape}}."""
    d, hd = cfg["hidden_size"], cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lo, hi = experts_held(cfg)
    outputs = cfg.get("router_outputs", cfg["num_experts"])
    cin = cfg["obs_shape"][2]
    shapes = {}
    for name, ch in zip(STEM, cfg["channels"]):
        k = _KERNELS[name]
        shapes[name] = {"w": (k, k, cin, ch), "b": (ch,)}
        cin = ch
    shapes["w_tok"] = (cin, d)
    for i, (op, ffn) in enumerate(layer_kinds(cfg)):
        layer = {"operator_norm": (d,), "ffn_norm": (d,)}
        if op == "conv":
            layer.update(w_in=(d, 3 * d), kernel=(d, cfg["conv_L_cache"]), w_out=(d, d))
        else:
            layer.update(w_q=(d, heads * hd), w_k=(d, kv * hd), w_v=(d, kv * hd),
                         w_o=(heads * hd, d), q_norm=(hd,), k_norm=(hd,))
        if ffn == "dense":
            w = cfg["intermediate_size"]
            layer.update(w1=(d, w), w3=(d, w), w2=(w, d))
        else:
            w, n = cfg["moe_intermediate_size"], hi - lo
            layer.update(router=(d, outputs), expert_bias=(outputs,),
                         w1=(n, d, w), w3=(n, d, w), w2=(n, w, d))
        shapes[f"layer_{i}"] = layer
    shapes["final_norm"] = (d,)
    hid = cfg["hidden"]
    shapes["value_hidden"] = {"w": (d, hid), "b": (hid,)}
    shapes["advantage_hidden"] = {"w": (d, hid), "b": (hid,)}
    shapes["value_head"] = {"w": (hid, 1), "b": (1,)}
    shapes["advantage_head"] = {"w": (hid, cfg["num_actions"]), "b": (cfg["num_actions"],)}
    return shapes


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def param_count(cfg: dict) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(weight_shapes(cfg), is_leaf=_is_shape):
        n = 1
        for s in leaf:
            n *= s
        total += n
    return total


def make_weights(key, cfg: dict) -> dict:
    """Seeded float32 weights: LeCun-normal matrices and kernels, norm weights
    near one, small non-zero biases."""
    paths = jax.tree_util.tree_flatten_with_path(weight_shapes(cfg), is_leaf=_is_shape)[0]
    out = {}
    for i, (path, shape) in enumerate(paths):
        names = [p.key for p in path]
        k = jax.random.fold_in(key, i)
        last = names[-1]
        if last.endswith("norm"):
            w = 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
        elif last in ("b", "expert_bias"):
            w = 0.01 * jax.random.normal(k, shape, jnp.float32)
        else:
            # fan-in: the taps of the short convolution's kernel [d, L]; the
            # inputs of a matrix (an expert's, past the expert axis); a
            # convolution's window times its input channels
            fan = shape[-1:] if last == "kernel" else shape[1:-1] if len(shape) == 3 else shape[:-1]
            fan_in = 1
            for s in fan:
                fan_in *= s
            w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[last] = w
    return out


# ------------------------------------------------------------------ forward

def rms_norm(x, w, eps, dtype):
    x32 = x.astype(jnp.float32)
    x32 = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * w.astype(jnp.float32)).astype(dtype)


def rope(x, theta):
    """x: [B, S, H, D]."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., d // 2:], x32[..., : d // 2]], -1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def short_conv(u, p, cfg, dtype, act):
    width = cfg["conv_L_cache"]
    b, c, x = jnp.split(act(u @ p["w_in"].astype(dtype)), 3, axis=-1)
    z = jnp.pad(act(b * x), ((0, 0), (width - 1, 0), (0, 0)))
    s = sum(z[:, j:j + u.shape[1], :] * p["kernel"][:, j].astype(dtype) for j in range(width))
    return act(act(c * act(s)) @ p["w_out"].astype(dtype))


def attention(u, p, cfg, dtype, act):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    bsz, s, _ = u.shape
    eps = cfg["norm_eps"]
    theta = cfg["rope_parameters"]["rope_theta"]
    q = act(u @ p["w_q"].astype(dtype)).reshape(bsz, s, heads, hd)
    k = act(u @ p["w_k"].astype(dtype)).reshape(bsz, s, kv, hd)
    v = act(u @ p["w_v"].astype(dtype)).reshape(bsz, s, kv, hd)
    q = act(rope(rms_norm(q, p["q_norm"], eps, dtype), theta))
    k = act(rope(rms_norm(k, p["k_norm"], eps, dtype), theta))
    k = jnp.repeat(k, heads // kv, axis=2)   # key-value head g serves query heads g*r..g*r+r-1
    v = jnp.repeat(v, heads // kv, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / jnp.sqrt(float(hd))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = act(jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(dtype))
    out = act(jnp.einsum("bhst,bthd->bshd", probs, v)).reshape(bsz, s, heads * hd)
    return act(out @ p["w_o"].astype(dtype))


def swiglu(u, w1, w3, w2, dtype, act):
    gate = act(jax.nn.silu(act(u @ w1.astype(dtype))))
    return act(act(gate * act(u @ w3.astype(dtype))) @ w2.astype(dtype))


def router_scores(u, p):
    """Float32 scores [.., E], whatever precision the rest runs in."""
    return jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32), p["router"].astype(jnp.float32),
                                     precision="highest"))


def route(scores, bias, k: int):
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)


def moe(u, p, cfg, dtype, act, held=None):
    """(the part of the mixture the experts ``held`` = [lo, hi) give, the
    pairs on each of the router's outputs [E]); the held experts' weights
    are ``p['w1'][e - lo]``."""
    lo, hi = held or experts_held(cfg)
    scores = router_scores(u, p)
    chosen, gates = route(scores, p["expert_bias"], cfg["num_experts_per_tok"])
    gates = gates * cfg.get("routed_scaling_factor", 1)
    # each held expert's gate on each token, 0 where the token did not choose it
    g = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(lo, hi), gates[..., None], 0.0),
                axis=-2).astype(dtype)
    gate = act(jax.nn.silu(act(jnp.einsum("...d,edf->...ef", u, p["w1"].astype(dtype)))))
    up = act(jnp.einsum("...d,edf->...ef", u, p["w3"].astype(dtype)))
    each = act(jnp.einsum("...ef,efd->...ed", act(gate * up), p["w2"].astype(dtype)))
    y = jnp.sum(g[..., None] * each, axis=-2)
    load = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
                   .reshape(-1, scores.shape[-1]), axis=0)
    return act(y), load


def stem(weights, obs, dtype, act):
    x = act(obs.astype(dtype) / jnp.asarray(255.0, dtype))
    for name in STEM:
        s = _STRIDES[name]
        x = jax.lax.conv_general_dilated(
            x, weights[name]["w"].astype(dtype), (s, s), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + weights[name]["b"].astype(dtype)
        x = act(jnp.maximum(x, 0))
    z = x.reshape(x.shape[0], -1, x.shape[-1])
    z = act(z - jnp.mean(z.astype(jnp.float32), axis=1, keepdims=True).astype(dtype))
    return act(z @ weights["w_tok"].astype(dtype))


def layer(h, p, kinds, cfg, dtype, act):
    """(the layer's output, its expert loads [E] or None)."""
    op, ffn = kinds
    eps = cfg["norm_eps"]
    u = rms_norm(h, p["operator_norm"], eps, dtype)
    h = h + (short_conv if op == "conv" else attention)(u, p, cfg, dtype, act)
    u = rms_norm(h, p["ffn_norm"], eps, dtype)
    if ffn == "dense":
        return h + swiglu(u, p["w1"], p["w3"], p["w2"], dtype, act), None
    y, load = moe(u, p, cfg, dtype, act)
    return h + y, load


def readout(weights, h, cfg, dtype, act):
    h = rms_norm(h, weights["final_norm"], cfg["norm_eps"], dtype)
    x = act(jnp.mean(h.astype(jnp.float32), axis=1).astype(dtype))

    def dense(name, a):
        return a @ weights[name]["w"].astype(dtype) + weights[name]["b"].astype(dtype)

    v = dense("value_head", act(jnp.maximum(dense("value_hidden", x), 0)))
    a = dense("advantage_head", act(jnp.maximum(dense("advantage_hidden", x), 0)))
    return v + a - jnp.mean(a, axis=-1, keepdims=True)


def forward_rows(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x):
    """(Q values [B, A], the expert layers' loads [layers, E]) of the rows
    given, all at once."""
    h = stem(weights, obs, dtype, act)
    loads = []
    for i, kinds in enumerate(layer_kinds(cfg)):
        h, load = layer(h, weights[f"layer_{i}"], kinds, cfg, dtype, act)
        if load is not None:
            loads.append(load)
    return readout(weights, h, cfg, dtype, act), jnp.stack(loads)


def forward(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x, row_block=ROW_BLOCK):
    """(Q values [B, A], loads [layers, E]) for uint8 NHWC observations, in
    blocks of rows, each recomputed in a backward pass."""
    n = obs.shape[0]
    if n <= row_block or n % row_block:
        return forward_rows(weights, obs, cfg, dtype, act)
    block = jax.checkpoint(lambda o: forward_rows(weights, o, cfg, dtype, act))
    q, loads = jax.lax.map(block, obs.reshape(n // row_block, row_block, *obs.shape[1:]))
    return q.reshape(n, -1), jnp.sum(loads, axis=0)


# ------------------------------------------------------------- learner step

def td_errors(weights, target_weights, batch, cfg, dtype=jnp.float32, act=lambda x: x):
    """(TD errors [B], the loads of the two online forwards [layers, E])."""
    q, loads = forward(weights, batch["obs"], cfg, dtype, act)
    frozen = jax.lax.stop_gradient(weights)
    q_next, loads_next = forward(frozen, batch["next_obs"], cfg, dtype, act)
    q_next_target, _ = forward(target_weights, batch["next_obs"], cfg, dtype, act)
    best = jnp.argmax(q_next, axis=-1)
    rows = jnp.arange(q.shape[0])
    target = batch["reward"].astype(dtype) + batch["discount"].astype(dtype) * q_next_target[rows, best]
    return q[rows, batch["action"]] - jax.lax.stop_gradient(target), loads + loads_next


def loss_fn(weights, target_weights, batch, cfg, dtype=jnp.float32, act=lambda x: x):
    delta, loads = td_errors(weights, target_weights, batch, cfg, dtype, act)
    if cfg["loss"] == "squared":
        per = 0.5 * delta * delta
    elif cfg["loss"] == "huber":
        quad = jnp.minimum(jnp.abs(delta), 1.0)
        per = 0.5 * quad * quad + (jnp.abs(delta) - quad)
    else:
        raise ValueError(f"unknown loss {cfg['loss']!r}")
    return jnp.mean(per * batch["is_weights"].astype(dtype)), (delta, loads)


def _hold(tree, dtype):
    """``tree`` as stored in ``dtype``: rounded for real, then cast."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)
    info = jnp.finfo(dtype)
    return jax.tree_util.tree_map(
        lambda x: jax.lax.reduce_precision(
            x.astype(jnp.float32), info.nexp, info.nmant).astype(dtype), tree)


def learner_step(weights, target_weights, nu, batch, cfg, precision="stated",
                 round_activations=None):
    """One update.  Returns (new_weights, new_nu, td_errors, priorities, loss),
    all as float32 whatever ``precision`` computed them.  The expert bias is
    a buffer: no gradient reaches it, and the balancing rule moves it.

    ``round_activations``, a traced boolean, makes the ``fp8_activations``
    control a value and not a program: activations are rounded to e5m2
    where it is true, so one compiled program (minutes to compile at the
    published widths) serves that control and the precision it is given
    with."""
    if cfg["optimizer"] != "rmsprop":
        raise ValueError(f"the reference implements rmsprop, not {cfg['optimizer']!r}")
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
    dtype = jnp.bfloat16 if precision == "bf16_held" else jnp.float32
    if precision == "fp8_activations":
        act = lambda x: jax.lax.reduce_precision(x, 5, 2)  # noqa: E731
    elif round_activations is not None:
        act = lambda x: jnp.where(  # noqa: E731
            round_activations, jax.lax.reduce_precision(x, 5, 2), x)
    else:
        act = lambda x: x  # noqa: E731
    weights, target_weights, nu = (_hold(t, dtype) for t in (weights, target_weights, nu))
    with jax.default_matmul_precision("highest"):
        (loss, (delta, loads)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            weights, target_weights, batch, cfg, dtype, act
        )
    grads = {k: ({n: (jnp.zeros_like(g) if n == "expert_bias" else g) for n, g in v.items()}
                 if k.startswith("layer_") else v) for k, v in grads.items()}
    grads = _hold(grads, dtype)
    if precision == "bf16_gradients":
        grads = _hold(_hold(grads, jnp.bfloat16), jnp.float32)
    leaves = jax.tree_util.tree_leaves(grads)
    if cfg.get("max_grad_norm") is not None:
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
        scale = jnp.minimum(1.0, cfg["max_grad_norm"] / jnp.maximum(norm, 1e-30)).astype(dtype)
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    d = jnp.asarray(cfg["rmsprop_decay"], dtype)
    eps = jnp.asarray(cfg["rmsprop_eps"], dtype)
    lr = jnp.asarray(cfg["learning_rate"], dtype)
    new_nu = _hold(jax.tree_util.tree_map(
        lambda v, g: d * v + (1 - d) * g * g, nu, grads), dtype)
    new_weights = jax.tree_util.tree_map(
        lambda p, g, v: p - lr * g / jnp.sqrt(v + eps), weights, grads, new_nu)
    rate = cfg["expert_bias_update_rate"]
    moe_layers = [i for i, (_, ffn) in enumerate(layer_kinds(cfg)) if ffn == "moe"]
    for load, i in zip(loads.astype(jnp.float32), moe_layers):
        error = jnp.clip(load / jnp.mean(load) - 1.0, -1.0, 1.0)
        p = new_weights[f"layer_{i}"]
        new_weights[f"layer_{i}"] = dict(
            p, expert_bias=p["expert_bias"] - (rate * error).astype(dtype))
    new_weights = _hold(new_weights, dtype)
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    return (f32(new_weights), f32(new_nu), delta.astype(jnp.float32),
            jnp.abs(delta).astype(jnp.float32) + PRIORITY_EPS, loss.astype(jnp.float32))


# --------------------------------------------- to and from the program's tree

_FLAX_STEM = {"conv1": "Conv_0", "conv2": "Conv_1", "conv3": "Conv_2"}
_FLAX_HEAD = {"value_hidden": "Dense_0", "advantage_hidden": "Dense_1",
              "value_head": "Dense_2", "advantage_head": "Dense_3"}
_CONV = ("w_in", "kernel", "w_out")
_ATTN = ("w_q", "w_k", "w_v", "w_o")


def layer_runs(cfg: dict) -> list:
    """[(first index, count)]: the consecutive layers of one kind, which the
    program holds stacked under ``layers_<first>_<last>``."""
    runs = []
    for i, kind in enumerate(layer_kinds(cfg)):
        if runs and runs[-1][2] == kind:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1, kind)
        else:
            runs.append((i, 1, kind))
    return [(first, count) for first, count, _ in runs]


def to_program_params(weights: dict, cfg: dict, dtype=None) -> dict:
    """The program's parameter tree (``models/lfm2_moe.Lfm2MoeQ``) holding
    these weights: experts' W_1 and W_3 side by side as ``w13``, a run of
    layers of one kind stacked."""
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    p = {"w_tok": cast(weights["w_tok"]), "final_norm": {"weight": cast(weights["final_norm"])}}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        p[flax] = {"kernel": cast(weights[name]["w"]), "bias": cast(weights[name]["b"])}
    layers = []
    for i, (op, ffn) in enumerate(layer_kinds(cfg)):
        w = weights[f"layer_{i}"]
        out = {"operator_norm": {"weight": cast(w["operator_norm"])},
               "ffn_norm": {"weight": cast(w["ffn_norm"])}}
        if op == "conv":
            out["conv"] = {n: cast(w[n]) for n in _CONV}
        else:
            out["full_attention"] = {
                **{n: cast(w[n]) for n in _ATTN},
                "q_norm": {"weight": cast(w["q_norm"])}, "k_norm": {"weight": cast(w["k_norm"])}}
        if ffn == "dense":
            out["dense"] = {n: cast(w[n]) for n in ("w1", "w3", "w2")}
        else:
            # the router's weights and bias stay float32 in every copy
            out["moe"] = {"router": w["router"].astype(jnp.float32),
                          "expert_bias": w["expert_bias"].astype(jnp.float32),
                          "w13": cast(jnp.concatenate([w["w1"], w["w3"]], axis=-1)),
                          "w2": cast(w["w2"])}
        layers.append(out)
    for first, count in layer_runs(cfg):
        if count == 1:
            p[f"layer_{first}"] = layers[first]
        else:
            p[f"layers_{first}_{first + count - 1}"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *layers[first:first + count])
    return {"params": p}


def from_program_params(params: dict, cfg: dict) -> dict:
    p = params["params"]
    f32 = lambda x: jnp.asarray(x).astype(jnp.float32)  # noqa: E731
    w = {"w_tok": f32(p["w_tok"]), "final_norm": f32(p["final_norm"]["weight"])}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        w[name] = {"w": f32(p[flax]["kernel"]), "b": f32(p[flax]["bias"])}
    held = {}
    for first, count in layer_runs(cfg):
        if count == 1:
            held[first] = p[f"layer_{first}"]
        else:
            stacked = p[f"layers_{first}_{first + count - 1}"]
            for j in range(count):
                held[first + j] = jax.tree_util.tree_map(lambda x: x[j], stacked)
    for i, (op, ffn) in enumerate(layer_kinds(cfg)):
        q = held[i]
        out = {"operator_norm": f32(q["operator_norm"]["weight"]),
               "ffn_norm": f32(q["ffn_norm"]["weight"])}
        if op == "conv":
            out.update({n: f32(q["conv"][n]) for n in _CONV})
        else:
            a = q["full_attention"]
            out.update({n: f32(a[n]) for n in _ATTN})
            out.update(q_norm=f32(a["q_norm"]["weight"]), k_norm=f32(a["k_norm"]["weight"]))
        if ffn == "dense":
            out.update({n: f32(q["dense"][n]) for n in ("w1", "w3", "w2")})
        else:
            m = q["moe"]
            f = m["w13"].shape[-1] // 2
            out.update(router=f32(m["router"]), expert_bias=f32(m["expert_bias"]),
                       w1=f32(m["w13"][..., :f]), w3=f32(m["w13"][..., f:]), w2=f32(m["w2"]))
        w[f"layer_{i}"] = out
    return w

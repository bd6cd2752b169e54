"""Plain reference for the Laguna-S-2.1 Q-network over a history of frames and
one learner step on it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program; the leaf helpers that are no model's
(RMSNorm, SwiGLU, the dueling readout, ``_hold``) are ``lfm2_moe_q.py``'s.
The learner step is the one ``dueling_dqn.py``'s docstring sets out (double-Q
target, importance-weighted loss, global-norm clip, one RMSProp update,
priorities ``|delta| + 1e-6``); the network is ISSUE 32's section 1:

  stem    each of the observation's F frames alone, [B x F, H, W, 1]:
          Conv 8x8/4 -> Conv 4x4/2 -> Conv 3x3/1 (VALID, ReLU): [B x F, h, w, C]
  tokens  the frames' h*w positions, time-major (frame 0 oldest), raster order
          inside a frame, centred over each frame's positions:
          x_0 = W_tok (z - mean_p z), no bias;  T = F h w
  layer   h <- h + Attn_l(RMSNorm(h));  h <- h + FFN_l(RMSNorm(h)), eps 1e-6
  Attn_l(u)  H_l heads (48 full, 72 sliding) over 8 key-value heads of 128:
          q = W_q u, k = W_k u, v = W_v u;  RoPE on q and k, positions 0..T-1:
          full layers rotate the first 64 dimensions of a head with YaRN's
          frequencies (below) and cos, sin times attention_factor, the other
          64 pass; sliding layers rotate all 128 with theta 10,000; rotated in
          halves as transformers does.  Key j is seen by query i if j <= i
          and, on sliding layers, j > i - 512.  a = softmax(q k^T / sqrt(128)
          + mask) v in float32, key-value head g serving query heads
          g r .. g r + r - 1;  g = sigmoid(W_g u) per head;
          y = W_o concat_h(g_h a_h).  Computed ``QUERY_BLOCK`` queries at a
          time against all keys (a dense masked softmax a block), so that no
          [H, T, T] tensor is kept.
  YaRN    dim = 64, pos_j = theta^(2j/dim); low = floor(c(beta_fast)), high =
          ceil(c(beta_slow)), c(r) = dim ln(original_max / (2 pi r)) / (2 ln
          theta); ramp_j = clip((j - low) / (high - low), 0, 1);
          inv_j = ramp_j / (factor pos_j) + (1 - ramp_j) / pos_j
          (transformers' ``_compute_yarn_parameters``, as remembered)
  FFN_0   SwiGLU_12288(u)  (``mlp_layer_types[0] = dense``)
  FFN_l   s = softmax(W_r u) over the 256 outputs, float32;  I = top_10(s)
          g_i = 2.5 s_i / sum_{j in I} s_j
          y = sum_{i in I and i held} g_i SwiGLU^(i)(u) + SwiGLU^shared(u)
  readout RMSNorm, mean over the T tokens, two ReLU streams, Q = V + A - mean(A)

The gates are normalised over all the chosen experts, held or not; what an
expert that is not held would have added is left out; the shared expert is
added ungated.  Every held expert is computed on every token, one after the
other, and weighted by its gate (zero where the token did not choose it).  The batch is walked a row at a time
(``lax.map`` over a checkpointed row) and every layer of a row is recomputed
again in the backward pass, so the step fits one chip at the published widths
(7.7 GB of temporaries beside 7.4 GB of arguments, compiled for v5e).  The
held experts are walked by ``lax.scan``: written as a Python loop the program
held eight copies of an expert's products a layer and pass, 265 MiB stored
and six minutes of compiling against 49 MiB and three.

Departures from the issue's equations: none known.  Assumed, as the
configuration file says: the softmax router without a bias, the ungated
shared expert, the head gate a projection of the layer's normed input, no
q/k norm, YaRN's blend.

``precision`` other than ``stated`` makes a control, as in ``dueling_dqn.py``:
``bf16_held`` (everything held in bfloat16), ``fp8_activations`` (every
activation rounded to e5m2), ``bf16_gradients``.  ``cfg["reference_ignores_window"]``
makes the control whose sliding layers see every earlier key.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.lfm2_moe_q import (  # leaf helpers, no model's
    PRECISIONS, PRIORITY_EPS, STEM, _FLAX_HEAD, _FLAX_STEM, _KERNELS, _STRIDES, _hold, _is_shape,
    readout, rms_norm, swiglu,
)

QUERY_BLOCK = 224          # 1,568 = 7 x 224
_ATTN = ("w_q", "w_k", "w_v", "w_g", "w_o")
_FFN = ("w1", "w3", "w2")
_SHARED = ("shared_w1", "shared_w3", "shared_w2")


def layer_kinds(cfg: dict) -> list:
    """[(op, ffn, heads)] of the layers run: ``layers_held`` of ``layer_types``."""
    held = cfg.get("layers_held", range(cfg["num_hidden_layers"]))
    return [(cfg["layer_types"][i],
             "dense" if cfg["mlp_layer_types"][i] == "dense" else "moe",
             cfg["num_attention_heads_per_layer"][i]) for i in held]


def experts_held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg.get("router_outputs", cfg["num_experts"]))))


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} or, for a layer, {name: {name: shape}}."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    lo, hi = experts_held(cfg)
    outputs = cfg.get("router_outputs", cfg["num_experts"])
    cin = 1                                    # the stem sees one frame
    shapes = {}
    for name, ch in zip(STEM, cfg["channels"]):
        k = _KERNELS[name]
        shapes[name] = {"w": (k, k, cin, ch), "b": (ch,)}
        cin = ch
    shapes["w_tok"] = (cin, d)
    for i, (_, ffn, heads) in enumerate(layer_kinds(cfg)):
        layer = {"operator_norm": (d,), "ffn_norm": (d,), "w_q": (d, heads * hd),
                 "w_k": (d, kv * hd), "w_v": (d, kv * hd), "w_g": (d, heads),
                 "w_o": (heads * hd, d)}
        if ffn == "dense":
            w = cfg["intermediate_size"]
            layer.update(w1=(d, w), w3=(d, w), w2=(w, d))
        else:
            w, n, s = cfg["moe_intermediate_size"], hi - lo, cfg["shared_expert_intermediate_size"]
            layer.update(router=(d, outputs), w1=(n, d, w), w3=(n, d, w), w2=(n, w, d),
                         shared_w1=(d, s), shared_w3=(d, s), shared_w2=(s, d))
        shapes[f"layer_{i}"] = layer
    shapes["final_norm"] = (d,)
    hid = cfg["hidden"]
    shapes["value_hidden"] = {"w": (d, hid), "b": (hid,)}
    shapes["advantage_hidden"] = {"w": (d, hid), "b": (hid,)}
    shapes["value_head"] = {"w": (hid, 1), "b": (1,)}
    shapes["advantage_head"] = {"w": (hid, cfg["num_actions"]), "b": (cfg["num_actions"],)}
    return shapes


def param_count(cfg: dict) -> int:
    return sum(math.prod(leaf) for leaf in
               jax.tree_util.tree_leaves(weight_shapes(cfg), is_leaf=_is_shape))


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
        elif last == "b":
            w = 0.01 * jax.random.normal(k, shape, jnp.float32)
        else:  # fan-in: a matrix's inputs (an expert's, past the expert axis); a window x channels
            fan_in = math.prod(shape[1:-1] if len(shape) == 3 else shape[:-1])
            w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[last] = w
    return out


# ------------------------------------------------------------------ forward

def inverse_frequencies(rule: dict, head_dim: int):
    """float32 [rotary / 2] of one entry of ``rope_parameters`` (docstring)."""
    dim = int(head_dim * rule.get("partial_rotary_factor", 1))
    theta = float(rule["rope_theta"])
    pos = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rule.get("rope_type", "default") == "default":
        return 1.0 / pos
    span = rule["original_max_position_embeddings"]
    c = lambda r: dim * math.log(span / (r * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731
    low, high = max(math.floor(c(rule["beta_fast"])), 0), min(math.ceil(c(rule["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return ramp / (rule["factor"] * pos) + (1.0 - ramp) / pos


def rope(x, rule: dict):
    """x: [B, T, H, D]."""
    inv = inverse_frequencies(rule, x.shape[-1])
    rot = 2 * inv.shape[0]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    factor = rule.get("attention_factor", 1.0)
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * factor)[None, :, None, :]
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * factor)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    turn, keep = x32[..., :rot], x32[..., rot:]
    rotated = jnp.concatenate([-turn[..., rot // 2:], turn[..., : rot // 2]], -1)
    return jnp.concatenate([turn * cos + rotated * sin, keep], -1).astype(x.dtype)


def attention(u, p, op, heads, cfg, dtype, act):
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    bsz, t, _ = u.shape
    rule = cfg["rope_parameters"][op]
    window = cfg["sliding_window"] if op == "sliding_attention" else None
    if cfg.get("reference_ignores_window"):
        window = None
    q = act(rope(act(u @ p["w_q"].astype(dtype)).reshape(bsz, t, heads, hd), rule))
    k = act(rope(act(u @ p["w_k"].astype(dtype)).reshape(bsz, t, kv, hd), rule))
    v = act(u @ p["w_v"].astype(dtype)).reshape(bsz, t, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=2)   # key-value head g serves query heads g*r..g*r+r-1
    v = jnp.repeat(v, heads // kv, axis=2)
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qb, first = args                                   # [B, n, H, D], the block's first query
        rows = first + jnp.arange(qb.shape[1])
        scores = jnp.einsum("bshd,bthd->bhst", qb, k).astype(jnp.float32) / math.sqrt(hd)
        mask = keys[None, :] <= rows[:, None]
        if window is not None:
            mask = mask & (keys[None, :] > rows[:, None] - window)
        probs = act(jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(dtype))
        return act(jnp.einsum("bhst,bthd->bshd", probs, v))

    n = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jnp.moveaxis(q.reshape(bsz, t // n, n, heads, hd), 1, 0)
    out = jax.lax.map(block, (blocks, jnp.arange(0, t, n)))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, heads, hd)
    gate = act(jax.nn.sigmoid(act(u @ p["w_g"].astype(dtype))))          # [B, T, H]
    out = act(out * gate[..., None]).reshape(bsz, t, heads * hd)
    return act(out @ p["w_o"].astype(dtype))


def router_scores(u, p):
    """Float32 scores [.., E], whatever precision the rest runs in."""
    return jax.nn.softmax(jnp.matmul(u.astype(jnp.float32), p["router"].astype(jnp.float32),
                                     precision="highest"), axis=-1)


def route(scores, cfg: dict):
    """(chosen [.., k], gates [.., k]): the k largest scores, normalised over
    the k and scaled."""
    gates, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return chosen, gates * cfg.get("moe_routed_scaling_factor", 1.0)


def routed(u, p, cfg, dtype, act, held=None):
    """(the part of the mixture the experts ``held`` = [lo, hi) give, the
    pairs on each of the router's outputs [E]); the held experts' weights
    are ``p['w1'][e - lo]``: a plain loop over them (``lax.scan``: one
    expert's products in the program, not one a held expert)."""
    lo, hi = held or experts_held(cfg)
    scores = router_scores(u, p)
    chosen, gates = route(scores, cfg)

    def one(y, e_w):                      # the next held expert's part, added
        e, w1, w3, w2 = e_w
        g = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1).astype(dtype)   # 0 if not chosen
        return y + g[..., None] * swiglu(u, w1, w3, w2, dtype, act), None

    y, _ = jax.lax.scan(one, jnp.zeros(u.shape, dtype),
                        (jnp.arange(lo, hi), p["w1"], p["w3"], p["w2"]))
    load = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
                   .reshape(-1, scores.shape[-1]), axis=0)
    return act(y), load


def moe(u, p, cfg, dtype, act):
    y, load = routed(u, p, cfg, dtype, act)
    return y + swiglu(u, p["shared_w1"], p["shared_w3"], p["shared_w2"], dtype, act), load


def stem(weights, obs, dtype, act):
    """[B, H, W, F] uint8 -> [B, F h w, d]."""
    bsz, frames = obs.shape[0], obs.shape[-1]
    x = jnp.moveaxis(obs, -1, 1).reshape(bsz * frames, *obs.shape[1:3], 1)
    x = act(x.astype(dtype) / jnp.asarray(255.0, dtype))
    for name in STEM:
        s = _STRIDES[name]
        x = jax.lax.conv_general_dilated(
            x, weights[name]["w"].astype(dtype), (s, s), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + weights[name]["b"].astype(dtype)
        x = act(jnp.maximum(x, 0))
    z = x.reshape(x.shape[0], -1, x.shape[-1])
    z = act(z - jnp.mean(z.astype(jnp.float32), axis=1, keepdims=True).astype(dtype))
    return act(z.reshape(bsz, -1, z.shape[-1]) @ weights["w_tok"].astype(dtype))


def layer(h, p, kinds, cfg, dtype, act):
    """(the layer's output, its expert loads [E] or zeros)."""
    op, ffn, heads = kinds
    eps = cfg["rms_norm_eps"]
    u = rms_norm(h, p["operator_norm"], eps, dtype)
    h = h + attention(u, p, op, heads, cfg, dtype, act)
    u = rms_norm(h, p["ffn_norm"], eps, dtype)
    if ffn == "dense":
        return h + swiglu(u, p["w1"], p["w3"], p["w2"], dtype, act), None
    y, load = moe(u, p, cfg, dtype, act)
    return h + y, load


def forward_rows(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x):
    """(Q values [B, A], the expert layers' loads [layers, E]) of the rows
    given, all at once; each layer recomputed in a backward pass."""
    h = stem(weights, obs, dtype, act)
    loads = []
    for i, kinds in enumerate(layer_kinds(cfg)):
        h, load = jax.checkpoint(
            lambda h, p, kinds=kinds: layer(h, p, kinds, cfg, dtype, act))(h, weights[f"layer_{i}"])
        if load is not None:
            loads.append(load)
    return readout(weights, h, dict(cfg, norm_eps=cfg["rms_norm_eps"]), dtype, act), jnp.stack(loads)


def forward(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x, row_block=1):
    """(Q values [B, A], loads [layers, E]) for uint8 NHWC observations, a
    block of rows at a time (each layer's input is all a backward pass keeps
    of a row)."""
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


def learner_step(weights, target_weights, nu, batch, cfg, precision="stated",
                 round_activations=None):
    """One update.  Returns (new_weights, new_nu, td_errors, priorities, loss),
    all as float32 whatever ``precision`` computed them.  ``round_activations``,
    a traced boolean, makes the ``fp8_activations`` control a value and not a
    program (``lfm2_moe_q.learner_step``)."""
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
        (loss, (delta, _loads)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            weights, target_weights, batch, cfg, dtype, act
        )
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
    new_weights = _hold(jax.tree_util.tree_map(
        lambda p, g, v: p - lr * g / jnp.sqrt(v + eps), weights, grads, new_nu), dtype)
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    return (f32(new_weights), f32(new_nu), delta.astype(jnp.float32),
            jnp.abs(delta).astype(jnp.float32) + PRIORITY_EPS, loss.astype(jnp.float32))


# --------------------------------------------- to and from the program's tree

def layer_runs(cfg: dict) -> list:
    """[(first index, count)]: the consecutive layers of one kind, which the
    program holds stacked under ``layers_<first>_<last>``."""
    runs = []
    for i, (op, ffn, _) in enumerate(layer_kinds(cfg)):
        if runs and runs[-1][2] == (op, ffn):
            runs[-1] = (runs[-1][0], runs[-1][1] + 1, (op, ffn))
        else:
            runs.append((i, 1, (op, ffn)))
    return [(first, count) for first, count, _ in runs]


def to_program_params(weights: dict, cfg: dict, dtype=None) -> dict:
    """The program's parameter tree (``models/laguna_moe.LagunaMoeQ``) holding
    these weights: experts' W_1 and W_3 side by side as ``w13``, a run of
    layers of one kind stacked."""
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    p = {"w_tok": cast(weights["w_tok"]), "final_norm": {"weight": cast(weights["final_norm"])}}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        p[flax] = {"kernel": cast(weights[name]["w"]), "bias": cast(weights[name]["b"])}
    layers = []
    for i, (op, ffn, _) in enumerate(layer_kinds(cfg)):
        w = weights[f"layer_{i}"]
        out = {"operator_norm": {"weight": cast(w["operator_norm"])},
               "ffn_norm": {"weight": cast(w["ffn_norm"])},
               op: {n: cast(w[n]) for n in _ATTN}}
        if ffn == "dense":
            out["dense"] = {n: cast(w[n]) for n in _FFN}
        else:  # the router's weights stay float32 in every copy
            out["moe"] = {"router": w["router"].astype(jnp.float32),
                          "w13": cast(jnp.concatenate([w["w1"], w["w3"]], axis=-1)),
                          "w2": cast(w["w2"])}
            out["shared_expert"] = {n: cast(w[s]) for n, s in zip(_FFN, _SHARED)}
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
    for i, (op, ffn, _) in enumerate(layer_kinds(cfg)):
        q = held[i]
        out = {"operator_norm": f32(q["operator_norm"]["weight"]),
               "ffn_norm": f32(q["ffn_norm"]["weight"]), **{n: f32(q[op][n]) for n in _ATTN}}
        if ffn == "dense":
            out.update({n: f32(q["dense"][n]) for n in _FFN})
        else:
            m = q["moe"]
            f = m["w13"].shape[-1] // 2
            out.update(router=f32(m["router"]), w1=f32(m["w13"][..., :f]),
                       w3=f32(m["w13"][..., f:]), w2=f32(m["w2"]),
                       **{s: f32(q["shared_expert"][n]) for n, s in zip(_FFN, _SHARED)})
        w[f"layer_{i}"] = out
    return w

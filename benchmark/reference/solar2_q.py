"""Plain reference for the Solar-Open2 Q-network over a history of frames and
one learner step on it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program; the leaf helpers that are no model's
(RMSNorm, SwiGLU, the dueling readout, ``_hold``) are ``lfm2_moe_q.py``'s, the
stem over single frames ``laguna_q.py``'s.  The learner step is the one
``dueling_dqn.py``'s docstring sets out (double-Q target, importance-weighted
loss, global-norm clip, one RMSProp update, priorities ``|delta| + 1e-6``)
with ``lfm2_moe_q.py``'s balancing rule on the expert bias; the network is
ISSUE 39's section 1, eps 1e-5 in every norm, ``H`` the heads held (the
configuration's ``num_attention_heads``, ``num_key_value_heads`` and
``linear_attn_config.num_heads`` count what this chip holds):

  tokens  x_0 = W_tok (z - mean_p z)          T = F h w, time-major (``laguna_q.stem``)
  layer   h <- h + Mix_l(RMSNorm(h));  h <- h + MoE(RMSNorm(h))
          Mix_l the softmax layer on ``gqa_layers``, else the linear one
  linear  q = silu(conv4(W_q u)), k = silu(conv4(W_k u)), v = silu(conv4(W_v u))
          4096 -> H x 128 each, no bias; depthwise, causal, 4 taps, zeros before t = 0
          q_t <- q_t / sqrt(|q_t|^2 + 1e-6) / sqrt(128);  k_t <- k_t / sqrt(|k_t|^2 + 1e-6)
          g_t = -exp(A_log) softplus(W_f2 (W_f1 u_t) + dt_bias)   [H, 128], A_log a head
          beta_t = 2 sigmoid(W_b u_t)   [H]  (the 2: kda_allow_neg_eigval)
          S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
          per head, S in R^{128 x 128}, S_{-1} = 0, float32 whatever the rest is held in
          o_t = S_t^T q_t
          y_t = w o_t / sqrt(mean(o_t^2) + eps) sigmoid(W_g2 (W_g1 u_t) + b_g)
          Mix = W_o y
  softmax H heads over H / 8 key-value heads of 128, q = W_q u, k = W_k u,
          v = W_v u, no bias, no positional rule;  a = softmax(q k^T / sqrt(128)
          + causal mask) v in float32;  a <- a sigmoid(W_g u);  Mix = W_o a
          (``QUERY_BLOCK`` queries at a time against all keys)
  MoE     s = sigmoid(W_r u) in float32 over the router's outputs;  I = the
          num_experts_per_tok largest of s + bias;  g_i = s_i / sum_{j in I} s_j
          times routed_scaling_factor;  y = sum_{i in I and i held} g_i
          SwiGLU^(i)(u) + SwiGLU^shared(u)
  readout RMSNorm, mean over the T tokens, two ReLU streams, Q = V + A - mean(A)

**The recurrence is the literal one**: ``lax.scan`` over the T tokens, the
state decayed, read, corrected and written a token at a time: no chunks, no
triangular solve.  Only its memory is arranged (``granite_h_q.py``'s way):
the scan runs in segments whose backward pass keeps the state at each
segment's start and steps the segment again.  The batch is walked a row at a
time, every layer of a row is recomputed in the backward pass, and the held
experts are walked by ``lax.scan``, as ``laguna_q.py``.

Departures from the issue's equations: none known.  Assumed, as the
configuration file says: the sigmoid router and its bias rule, the ungated
shared expert, the gate per element, the gates' rank, the L2 norms' eps, the
initialisation.

``precision`` other than ``stated`` makes a control, as in ``dueling_dqn.py``:
``bf16_held``, ``fp8_activations``, ``bf16_gradients``.  Two controls of this
mechanism are keys of the configuration: ``cfg["reference_resets_state"]``
sets the state to zero every ``CHUNK`` tokens (a chunked scan that lost its
carry); ``cfg["reference_drops_delta"]`` writes ``beta k v^T`` without ``-
beta k k^T S`` (plain gated linear attention: the mathematics a faster
program would leave out).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.laguna_q import stem as history_stem
from reference.lfm2_moe_q import (  # leaf helpers, no model's
    PRECISIONS, PRIORITY_EPS, STEM, _FLAX_HEAD, _FLAX_STEM, _KERNELS, _hold, _is_shape,
    readout, rms_norm, swiglu,
)

QUERY_BLOCK = 224          # 1,568 = 7 x 224
SEGMENT = 256              # the recurrence's backward pass keeps a state this often, at most
CHUNK = 64                 # the program's chunk (``kda_chunk_size`` if stated): where the control resets
L2_EPS = 1e-6
_LINEAR = ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f1", "w_f2", "A_log",
           "dt_bias", "w_b", "w_g1", "w_g2", "b_g", "norm", "w_o")
_FULL = ("w_q", "w_k", "w_v", "w_g", "w_o")
_FFN = ("w1", "w3", "w2")
_SHARED = ("shared_w1", "shared_w3", "shared_w2")
FLOAT32_ALWAYS = ("A_log", "dt_bias")          # in every copy the program holds
OPS = ("linear_attention", "full_attention")


def layer_kinds(cfg: dict) -> list:
    """The layer types run: ``layers_held`` of the pattern ``gqa_layers`` gives."""
    gqa = set(cfg["gqa_layers"])
    return [OPS[i in gqa] for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def experts_held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg.get("router_outputs", cfg["n_routed_experts"]))))


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} or, for a layer, {name: {name: shape}}."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    lin = cfg["linear_attn_config"]
    n, lhd, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    rank = cfg.get("kda_gate_rank", lhd)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lo, hi = experts_held(cfg)
    outputs = cfg.get("router_outputs", cfg["n_routed_experts"])
    w, s = cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    cin = 1                                    # the stem sees one frame
    shapes = {}
    for name, ch in zip(STEM, cfg["channels"]):
        k = _KERNELS[name]
        shapes[name] = {"w": (k, k, cin, ch), "b": (ch,)}
        cin = ch
    shapes["w_tok"] = (cin, d)
    for i, op in enumerate(layer_kinds(cfg)):
        layer = {"operator_norm": (d,), "ffn_norm": (d,), "router": (d, outputs),
                 "expert_bias": (outputs,), "w1": (hi - lo, d, w), "w3": (hi - lo, d, w),
                 "w2": (hi - lo, w, d), "shared_w1": (d, s), "shared_w3": (d, s),
                 "shared_w2": (s, d)}
        if op == "linear_attention":
            layer.update(w_q=(d, n * lhd), w_k=(d, n * lhd), w_v=(d, n * lhd),
                         conv_q=(n * lhd, taps), conv_k=(n * lhd, taps), conv_v=(n * lhd, taps),
                         w_f1=(d, rank), w_f2=(rank, n * lhd), A_log=(n,), dt_bias=(n * lhd,),
                         w_b=(d, n), w_g1=(d, rank), w_g2=(rank, n * lhd), b_g=(n * lhd,),
                         norm=(lhd,), w_o=(n * lhd, d))
        else:
            layer.update(w_q=(d, h * hd), w_k=(d, kv * hd), w_v=(d, kv * hd),
                         w_g=(d, h * hd), w_o=(h * hd, d))
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
    near one, small non-zero biases, ``A_log = log U[1, 16]`` and ``dt_bias``
    the inverse softplus of a step size log-uniform in [1e-3, 1e-1]."""
    paths = jax.tree_util.tree_flatten_with_path(weight_shapes(cfg), is_leaf=_is_shape)[0]
    out = {}
    for i, (path, shape) in enumerate(paths):
        names = [p.key for p in path]
        k = jax.random.fold_in(key, i)
        last = names[-1]
        if last.endswith("norm"):
            w = 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
        elif last in ("b", "b_g", "expert_bias"):
            w = 0.01 * jax.random.normal(k, shape, jnp.float32)
        elif last == "A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif last == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:  # fan-in: a depthwise kernel's taps; an expert's inputs; a matrix's; a window x channels
            fan_in = (shape[-1] if last.startswith("conv_") else
                      math.prod(shape[1:-1] if len(shape) == 3 else shape[:-1]))
            w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[last] = w
    return out


# ------------------------------------------------------------------ forward

def _segment(tokens: int) -> int:
    return max(s for s in range(1, min(SEGMENT, tokens) + 1) if tokens % s == 0)


def recurrence(q, k, v, g, beta, reset_every: int = 0, drop_delta: bool = False):
    """The literal recurrence, float32: ``q``, ``k``, ``g`` [B, T, H, K], ``v``
    [B, T, H, V], ``beta`` [B, T, H] -> ``o`` [B, T, H, V].  With
    ``reset_every`` the state is zeroed before every token whose index is a
    multiple of it; with ``drop_delta`` the write is ``beta k v^T`` alone
    (the two controls)."""
    bsz, t, heads, kw = q.shape
    keep = jnp.ones((t,), jnp.float32)
    if reset_every:
        keep = (jnp.arange(t) % reset_every != 0).astype(jnp.float32)

    def step(state, token):
        qt, kt, vt, gt, bt, kept = token            # [B, H, K] x 2, [B, H, V], [B, H, K], [B, H], []
        state = jnp.exp(gt)[..., None] * (state * kept)              # decayed
        read = 0.0 if drop_delta else jnp.sum(kt[..., None] * state, axis=-2)   # k^T S: [B, H, V]
        state = state + (bt[..., None] * kt)[..., None] * (vt - read)[..., None, :]
        return state, jnp.sum(qt[..., None] * state, axis=-2)

    seg = _segment(t)

    @jax.checkpoint
    def segment(state, tokens):
        return jax.lax.scan(step, state, tokens)

    by_time = tuple(jnp.moveaxis(x, 1, 0).reshape(t // seg, seg, *x.shape[:1], *x.shape[2:])
                    for x in (q, k, v, g, beta)) + (keep.reshape(t // seg, seg),)
    _, os = jax.lax.scan(segment, jnp.zeros((bsz, heads, kw, v.shape[-1]), jnp.float32), by_time)
    return jnp.moveaxis(os.reshape(t, bsz, heads, v.shape[-1]), 0, 1)


def _short_conv(x, kernel, dtype, act):
    """silu(conv4(x)) over the tokens of ``x`` [B, T, C]; ``kernel`` [C, taps]."""
    taps, t = kernel.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + t, :] * kernel[:, j].astype(dtype) for j in range(taps))
    return act(jax.nn.silu(act(out)))


def linear_attention(u, p, cfg, dtype, act):
    lin, f32 = cfg["linear_attn_config"], jnp.float32
    n, hd = lin["num_heads"], lin["head_dim"]
    bsz, t, _ = u.shape
    heads = lambda x: x.reshape(bsz, t, n, hd)  # noqa: E731
    q, k, v = (heads(_short_conv(act(u @ p["w_" + x].astype(dtype)), p["conv_" + x], dtype, act))
               .astype(f32) for x in "qkv")
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) / math.sqrt(hd)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = act(act(u @ p["w_f1"].astype(dtype)) @ p["w_f2"].astype(dtype))
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        heads(f.astype(f32)) + p["dt_bias"].astype(f32).reshape(n, hd))
    scale = 2.0 if cfg.get("kda_allow_neg_eigval") else 1.0
    beta = scale * jax.nn.sigmoid(act(u @ p["w_b"].astype(dtype)).astype(f32))
    q, k, v = (act(x.astype(dtype)).astype(f32) for x in (q, k, v))
    o = recurrence(q, k, v, g, beta,
                   cfg.get("kda_chunk_size", CHUNK) if cfg.get("reference_resets_state") else 0,
                   bool(cfg.get("reference_drops_delta")))
    o = act(o.astype(dtype)).astype(f32)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(heads(act(act(u @ p["w_g1"].astype(dtype)) @ p["w_g2"].astype(dtype))
                                .astype(f32)) + p["b_g"].astype(f32).reshape(n, hd))
    y = act((o * p["norm"].astype(f32) * gate).astype(dtype)).reshape(bsz, t, n * hd)
    return act(y @ p["w_o"].astype(dtype))


def full_attention(u, p, cfg, dtype, act):
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    bsz, t, _ = u.shape
    q = act(u @ p["w_q"].astype(dtype)).reshape(bsz, t, heads, hd)
    k = act(u @ p["w_k"].astype(dtype)).reshape(bsz, t, kv, hd)
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
        probs = act(jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(dtype))
        return act(jnp.einsum("bhst,bthd->bshd", probs, v))

    n = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jnp.moveaxis(q.reshape(bsz, t // n, n, heads, hd), 1, 0)
    out = jax.lax.map(block, (blocks, jnp.arange(0, t, n)))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, heads * hd)
    if cfg.get("use_gqa_gate"):
        out = act(out * act(jax.nn.sigmoid(act(u @ p["w_g"].astype(dtype)))))
    return act(out @ p["w_o"].astype(dtype))


def router_scores(u, p):
    """Float32 scores [.., E], whatever precision the rest runs in."""
    return jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32), p["router"].astype(jnp.float32),
                                     precision="highest"))


def route(scores, bias, cfg: dict):
    """(chosen [.., k], gates [.., k]): the k largest of scores + bias, the
    chosen scores over their sum, scaled."""
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return chosen, gates * cfg.get("routed_scaling_factor", 1.0)


def routed(u, p, cfg, dtype, act, held=None):
    """(the part of the mixture the experts ``held`` = [lo, hi) give, the
    pairs on each of the router's outputs [E]); the held experts' weights
    are ``p['w1'][e - lo]``, walked one after the other."""
    lo, hi = held or experts_held(cfg)
    scores = router_scores(u, p)
    chosen, gates = route(scores, p["expert_bias"], cfg)

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


def layer(h, p, op, cfg, dtype, act):
    """(the layer's output, its expert loads [E])."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(h, p["operator_norm"], eps, dtype)
    h = h + (linear_attention if op == "linear_attention" else full_attention)(
        u, p, cfg, dtype, act)
    u = rms_norm(h, p["ffn_norm"], eps, dtype)
    y, load = moe(u, p, cfg, dtype, act)
    return h + y, load


def forward_rows(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x):
    """(Q values [B, A], the layers' expert loads [layers, E]) of the rows
    given, all at once; each layer recomputed in a backward pass."""
    h = history_stem(weights, obs, dtype, act)
    loads = []
    for i, op in enumerate(layer_kinds(cfg)):
        h, load = jax.checkpoint(
            lambda h, p, op=op: layer(h, p, op, cfg, dtype, act))(h, weights[f"layer_{i}"])
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
    all as float32 whatever ``precision`` computed them.  The expert bias is a
    buffer: no gradient reaches it, and the balancing rule moves it
    (``lfm2_moe_q.learner_step``).  ``round_activations``, a traced boolean,
    makes the ``fp8_activations`` control a value and not a program."""
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
    for i, load in enumerate(loads.astype(jnp.float32)):       # every layer routes
        error = jnp.clip(load / jnp.mean(load) - 1.0, -1.0, 1.0)
        p = new_weights[f"layer_{i}"]
        new_weights[f"layer_{i}"] = dict(
            p, expert_bias=p["expert_bias"] - (rate * error).astype(dtype))
    new_weights = _hold(new_weights, dtype)
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    return (f32(new_weights), f32(new_nu), delta.astype(jnp.float32),
            jnp.abs(delta).astype(jnp.float32) + PRIORITY_EPS, loss.astype(jnp.float32))


# --------------------------------------------- to and from the program's tree

def layer_runs(cfg: dict) -> list:
    """[(first index, count)]: the consecutive layers of one kind, which the
    program holds stacked under ``layers_<first>_<last>``."""
    runs = []
    for i, op in enumerate(layer_kinds(cfg)):
        if runs and runs[-1][2] == op:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1, op)
        else:
            runs.append((i, 1, op))
    return [(first, count) for first, count, _ in runs]


def to_program_params(weights: dict, cfg: dict, dtype=None) -> dict:
    """The program's parameter tree (``models/solar_open2.SolarOpen2Q``)
    holding these weights: experts' W_1 and W_3 side by side as ``w13``, a
    run of layers of one kind stacked; the router, its bias, ``A_log`` and
    ``dt_bias`` float32 in every copy."""
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    p = {"w_tok": cast(weights["w_tok"]), "final_norm": {"weight": cast(weights["final_norm"])}}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        p[flax] = {"kernel": cast(weights[name]["w"]), "bias": cast(weights[name]["b"])}
    layers = []
    for i, op in enumerate(layer_kinds(cfg)):
        w = weights[f"layer_{i}"]
        layers.append({
            "operator_norm": {"weight": cast(w["operator_norm"])},
            "ffn_norm": {"weight": cast(w["ffn_norm"])},
            op: {n: w[n].astype(jnp.float32) if n in FLOAT32_ALWAYS else cast(w[n])
                 for n in (_LINEAR if op == "linear_attention" else _FULL)},
            "moe": {"router": w["router"].astype(jnp.float32),
                    "expert_bias": w["expert_bias"].astype(jnp.float32),
                    "w13": cast(jnp.concatenate([w["w1"], w["w3"]], axis=-1)),
                    "w2": cast(w["w2"])},
            "shared_expert": {n: cast(w[s]) for n, s in zip(_FFN, _SHARED)}})
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
    for i, op in enumerate(layer_kinds(cfg)):
        q = held[i]
        m = q["moe"]
        f = m["w13"].shape[-1] // 2
        w[f"layer_{i}"] = {
            "operator_norm": f32(q["operator_norm"]["weight"]),
            "ffn_norm": f32(q["ffn_norm"]["weight"]),
            **{n: f32(q[op][n]) for n in (_LINEAR if op == "linear_attention" else _FULL)},
            "router": f32(m["router"]), "expert_bias": f32(m["expert_bias"]),
            "w1": f32(m["w13"][..., :f]), "w3": f32(m["w13"][..., f:]), "w2": f32(m["w2"]),
            **{s: f32(q["shared_expert"][n]) for n, s in zip(_FFN, _SHARED)}}
    return w

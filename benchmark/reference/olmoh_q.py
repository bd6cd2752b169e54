"""Plain reference for the Olmo-Hybrid Q-network over a history of frames and
one learner step on it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program; the leaf helpers that are no model's
(RMSNorm, SwiGLU, the dueling readout, ``_hold``) are ``lfm2_moe_q.py``'s, the
stem over single frames ``laguna_q.py``'s.  The learner step is the one
``dueling_dqn.py``'s docstring sets out (double-Q target, importance-weighted
loss, global-norm clip, one RMSProp update, priorities ``|delta| + 1e-6``);
the network is ISSUE 51's section 1, eps ``rms_norm_eps`` in every norm, ``H``
heads of keys ``K`` and values ``V`` in the linear layers (``linear_*``), ``N``
heads of ``D = hidden_size / N`` in the full ones:

  tokens  x_0 = W_tok (z - mean_p z)          T = F h w, time-major (``laguna_q.stem``)
  layer   h <- x + RMSNorm(Mix_l(x));  y <- h + RMSNorm(SwiGLU(h))       (post-norm)
          Mix_l by ``layer_types``
  linear  q = silu(conv4(W_q x)), k = silu(conv4(W_k x)), v = silu(conv4(W_v x))
          d -> H x K, H x K, H x V, no bias; depthwise, causal, 4 taps, zeros before t = 0
          q_t <- q_t / sqrt(|q_t|^2 + 1e-6) / sqrt(K);  k_t <- k_t / sqrt(|k_t|^2 + 1e-6)
          g_t = -exp(A_log) softplus(w_a . x_t + dt_bias)     [H]: one scalar a head and token
          beta_t = 2 sigmoid(w_b . x_t)   [H]  (the 2: linear_allow_neg_eigval)
          S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
          per head, S in R^{K x V}, S_{-1} = 0, float32 whatever the rest is held in
          o_t = S_t^T q_t
          y_t = w o_t / sqrt(mean(o_t^2) + eps) silu(W_g x_t)      w in R^V, one for all heads
          Mix = W_o y
  full    q = RMSNorm_d(W_q x), k = RMSNorm_d(W_k x)  over the whole width, then N heads
          of D; v = W_v x; no bias, no positional rule;  a = softmax(q k^T / sqrt(D) +
          causal mask) v in float32;  Mix = W_o a   (``QUERY_BLOCK`` queries at a time)
  readout RMSNorm, mean over the T tokens, two ReLU streams, Q = V + A - mean(A)

(the decay is applied to the state first and the correction reads the decayed
state: ``S' = exp(g) S; S_t = S' + beta k (v - k^T S')^T``, the line above
multiplied out.)

**The recurrence is the literal one**: ``lax.scan`` over the T tokens, the
state decayed, read, corrected and written a token at a time: no chunks, no
triangular system.  Only its memory is arranged (``granite_h_q.py``'s way):
the scan runs in segments whose backward pass keeps the state at each
segment's start and steps the segment again.  The batch is walked a row at a
time and every layer of a row is recomputed in the backward pass.  **The
layers are a Python loop over weights held a layer each**
(``solar2_q.py``'s way), not a ``lax.scan`` over a stacked run: compiled for
v5e at the cell's 837 M parameters the scanned form takes 9.47 GB of
temporaries beside 8.37 GB of arguments (the stacked run's gradient of a row
stands whole beside the sum over rows, and every layer's weights are copied
out of the stack) and does not fit the chip; the loop takes 5.34 GB and is
stored as 37.6 MB where the scan is 32.1 (PERF.md, section 6, PR 51).

Departures from the issue's equations: none known.  Assumed, as the
configuration file says: the post-norm order, the QK-norm over the whole
width, the linear layer's lay-out, the L2 norms' eps, the initialisation.

``precision`` other than ``stated`` makes a control, as in ``dueling_dqn.py``:
``bf16_held``, ``fp8_activations``, ``bf16_gradients``.  Four controls of this
model's mechanisms are keys of the configuration: ``reference_pre_norm`` (the
norms before the sublayers, the block every other torso here has),
``reference_drops_decay`` (``g = 0``: the delta rule without its gate),
``reference_beta_to_one`` (``beta = sigmoid(.)``: no negative eigenvalue) and
``reference_norms_by_head`` (the full layer's q and k normed a head at a
time).
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
SEGMENT = 56               # the recurrence's backward pass keeps a state this often, at most: a
#                            segment stepped again holds a state a token, 2.2 MB a row at the cell's
#                            30 heads of [96, 192]
L2_EPS = 1e-6
_LINEAR = ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_a", "A_log", "dt_bias",
           "w_b", "w_g", "norm", "w_o")
_FULL = ("w_q", "w_k", "w_v", "q_norm", "k_norm", "w_o")
_FFN = ("w1", "w3", "w2")
FLOAT32_ALWAYS = ("A_log", "dt_bias")          # in every copy the program holds
FLAGS = ("reference_pre_norm", "reference_drops_decay", "reference_beta_to_one",
         "reference_norms_by_head")            # the controls that are keys of the configuration


def layer_kinds(cfg: dict) -> list:
    """The layer types run: ``layers_held`` of ``layer_types``."""
    types = cfg["layer_types"]
    return [types[i] for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


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


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} or, for a layer, {name: {name: shape}}."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    n, kd, vd = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    cin = 1                                    # the stem sees one frame
    shapes = {}
    for name, ch in zip(STEM, cfg["channels"]):
        k = _KERNELS[name]
        shapes[name] = {"w": (k, k, cin, ch), "b": (ch,)}
        cin = ch
    shapes["w_tok"] = (cin, d)
    for i, op in enumerate(layer_kinds(cfg)):
        layer = {"operator_norm": (d,), "ffn_norm": (d,), "w1": (d, f), "w3": (d, f), "w2": (f, d)}
        if op == "linear_attention":
            layer.update(w_q=(d, n * kd), w_k=(d, n * kd), w_v=(d, n * vd),
                         conv_q=(n * kd, taps), conv_k=(n * kd, taps), conv_v=(n * vd, taps),
                         w_a=(d, n), A_log=(n,), dt_bias=(n,), w_b=(d, n), w_g=(d, n * vd),
                         norm=(vd,), w_o=(n * vd, d))
        else:
            layer.update(w_q=(d, d), w_k=(d, d), w_v=(d, d), q_norm=(d,), k_norm=(d,), w_o=(d, d))
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
        elif last == "b":
            w = 0.01 * jax.random.normal(k, shape, jnp.float32)
        elif last == "A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif last == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:  # fan-in: a depthwise kernel's taps; a matrix's inputs; a window x channels
            fan_in = shape[-1] if last.startswith("conv_") else math.prod(shape[:-1])
            w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[last] = w
    return out


# ------------------------------------------------------------------ forward

def _segment(tokens: int) -> int:
    return max(s for s in range(1, min(SEGMENT, tokens) + 1) if tokens % s == 0)


def recurrence(q, k, v, g, beta):
    """The literal recurrence, float32: ``q``, ``k`` [B, T, H, K], ``v`` [B,
    T, H, V], ``g``, ``beta`` [B, T, H] -> ``o`` [B, T, H, V]."""
    bsz, t, heads, kw = q.shape

    def step(state, token):
        qt, kt, vt, gt, bt = token                  # [B, H, K] x 2, [B, H, V], [B, H] x 2
        state = jnp.exp(gt)[..., None, None] * state                 # decayed
        read = jnp.sum(kt[..., None] * state, axis=-2)               # k^T S: [B, H, V]
        state = state + (bt[..., None] * kt)[..., None] * (vt - read)[..., None, :]
        return state, jnp.sum(qt[..., None] * state, axis=-2)

    seg = _segment(t)

    @jax.checkpoint
    def segment(state, tokens):
        return jax.lax.scan(step, state, tokens)

    by_time = tuple(jnp.moveaxis(x, 1, 0).reshape(t // seg, seg, *x.shape[:1], *x.shape[2:])
                    for x in (q, k, v, g, beta))
    _, os = jax.lax.scan(segment, jnp.zeros((bsz, heads, kw, v.shape[-1]), jnp.float32), by_time)
    return jnp.moveaxis(os.reshape(t, bsz, heads, v.shape[-1]), 0, 1)


def _short_conv(x, kernel, dtype, act):
    """silu(conv4(x)) over the tokens of ``x`` [B, T, C]; ``kernel`` [C, taps]."""
    taps, t = kernel.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + t, :] * kernel[:, j].astype(dtype) for j in range(taps))
    return act(jax.nn.silu(act(out)))


def linear_attention(x, p, cfg, dtype, act):
    f32 = jnp.float32
    n, kd, vd = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    bsz, t, _ = x.shape
    heads = lambda y: y.reshape(bsz, t, n, -1)  # noqa: E731
    q, k, v = (heads(_short_conv(act(x @ p["w_" + c].astype(dtype)), p["conv_" + c], dtype, act))
               .astype(f32) for c in "qkv")
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) / math.sqrt(kd)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    a = act(x @ p["w_a"].astype(dtype)).astype(f32)
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(a + p["dt_bias"].astype(f32))
    if cfg.get("reference_drops_decay"):
        g = jnp.zeros_like(g)
    scale = 2.0 if cfg.get("linear_allow_neg_eigval") and not cfg.get("reference_beta_to_one") else 1.0
    beta = scale * jax.nn.sigmoid(act(x @ p["w_b"].astype(dtype)).astype(f32))
    q, k, v = (act(y.astype(dtype)).astype(f32) for y in (q, k, v))
    o = act(recurrence(q, k, v, g, beta).astype(dtype)).astype(f32)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    gate = jax.nn.silu(heads(act(x @ p["w_g"].astype(dtype))).astype(f32))
    y = act((o * p["norm"].astype(f32) * gate).astype(dtype)).reshape(bsz, t, n * vd)
    return act(y @ p["w_o"].astype(dtype))


def full_attention(x, p, cfg, dtype, act):
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    bsz, t, _ = x.shape
    by_head = lambda y: y.reshape(bsz, t, heads, hd)  # noqa: E731

    def qk_norm(y, w):
        if cfg.get("reference_norms_by_head"):     # the control: a norm a head, the weight's slice
            return rms_norm(by_head(y), w.reshape(heads, hd), eps, dtype)
        return by_head(rms_norm(y, w, eps, dtype))

    q = act(qk_norm(act(x @ p["w_q"].astype(dtype)), p["q_norm"]))
    k = act(qk_norm(act(x @ p["w_k"].astype(dtype)), p["k_norm"]))
    v = by_head(act(x @ p["w_v"].astype(dtype)))
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
    return act(out @ p["w_o"].astype(dtype))


def layer(x, p, op, cfg, dtype, act):
    eps = cfg["rms_norm_eps"]
    mix = linear_attention if op == "linear_attention" else full_attention
    ffn = lambda u: swiglu(u, p["w1"], p["w3"], p["w2"], dtype, act)  # noqa: E731
    if cfg.get("reference_pre_norm"):          # the control: every other torso's block
        h = x + mix(rms_norm(x, p["operator_norm"], eps, dtype), p, cfg, dtype, act)
        return h + ffn(rms_norm(h, p["ffn_norm"], eps, dtype))
    h = x + rms_norm(mix(x, p, cfg, dtype, act), p["operator_norm"], eps, dtype)
    return h + rms_norm(ffn(h), p["ffn_norm"], eps, dtype)


def forward_rows(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x):
    """Q values [B, A] of the rows given, all at once; each layer recomputed
    in a backward pass."""
    h = history_stem(weights, obs, dtype, act)
    for i, op in enumerate(layer_kinds(cfg)):
        h = jax.checkpoint(
            lambda h, p, op=op: layer(h, p, op, cfg, dtype, act))(h, weights[f"layer_{i}"])
    return readout(weights, h, dict(cfg, norm_eps=cfg["rms_norm_eps"]), dtype, act)


def forward(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x, row_block=1):
    """(Q values [B, A], None: no layer counts anything) for uint8 NHWC
    observations, a block of rows at a time (each layer's input is all a
    backward pass keeps of a row)."""
    n = obs.shape[0]
    if n <= row_block or n % row_block:
        return forward_rows(weights, obs, cfg, dtype, act), None
    block = jax.checkpoint(lambda o: forward_rows(weights, o, cfg, dtype, act))
    q = jax.lax.map(block, obs.reshape(n // row_block, row_block, *obs.shape[1:]))
    return q.reshape(n, -1), None


# ------------------------------------------------------------- learner step

def td_errors(weights, target_weights, batch, cfg, dtype=jnp.float32, act=lambda x: x):
    q, _ = forward(weights, batch["obs"], cfg, dtype, act)
    q_next, _ = forward(jax.lax.stop_gradient(weights), batch["next_obs"], cfg, dtype, act)
    q_next_target, _ = forward(target_weights, batch["next_obs"], cfg, dtype, act)
    best = jnp.argmax(q_next, axis=-1)
    rows = jnp.arange(q.shape[0])
    target = batch["reward"].astype(dtype) + batch["discount"].astype(dtype) * q_next_target[rows, best]
    return q[rows, batch["action"]] - jax.lax.stop_gradient(target)


def loss_fn(weights, target_weights, batch, cfg, dtype=jnp.float32, act=lambda x: x):
    delta = td_errors(weights, target_weights, batch, cfg, dtype, act)
    if cfg["loss"] == "squared":
        per = 0.5 * delta * delta
    elif cfg["loss"] == "huber":
        quad = jnp.minimum(jnp.abs(delta), 1.0)
        per = 0.5 * quad * quad + (jnp.abs(delta) - quad)
    else:
        raise ValueError(f"unknown loss {cfg['loss']!r}")
    return jnp.mean(per * batch["is_weights"].astype(dtype)), delta


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
        (loss, delta), grads = jax.value_and_grad(loss_fn, has_aux=True)(
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

def to_program_params(weights: dict, cfg: dict, dtype=None) -> dict:
    """The program's parameter tree (``models/olmo_hybrid.OlmoHybridQ``)
    holding these weights: a run of layers of one kind stacked; ``A_log`` and
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
            "dense": {n: cast(w[n]) for n in _FFN}})
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
        w[f"layer_{i}"] = {
            "operator_norm": f32(q["operator_norm"]["weight"]),
            "ffn_norm": f32(q["ffn_norm"]["weight"]),
            **{n: f32(q[op][n]) for n in (_LINEAR if op == "linear_attention" else _FULL)},
            **{n: f32(q["dense"][n]) for n in _FFN}}
    return w

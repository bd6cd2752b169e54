"""Plain reference for the Granite 4.0-H Q-network over a history of frames and
one learner step on it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program; the leaf helpers that are no model's
(RMSNorm, SwiGLU, the dueling readout, ``_hold``) are ``lfm2_moe_q.py``'s, the
stem over single frames ``laguna_q.py``'s.  The learner step is the one
``dueling_dqn.py``'s docstring sets out (double-Q target, importance-weighted
loss, global-norm clip, one RMSProp update, priorities ``|delta| + 1e-6``);
the network is ISSUE 34's section 1, eps 1e-5 in every norm:

  tokens  x_0 = 12 W_tok (z - mean_p z)       embedding_multiplier on what
          replaces the embedding; T = F h w, time-major (``laguna_q.stem``)
  layer   h <- h + 0.22 Mix_l(RMSNorm(h));  h <- h + 0.22 SwiGLU_8192(RMSNorm(h))
  mamba   [z | xBC | dt] = W_in u             2048 -> 4096 + 4352 + 64, no bias
          xBC_t <- silu(sum_{k=0..3} w[c,k] xBC_{t-3+k} + b[c])   depthwise,
          causal, zeros before t = 0
          [x | B | C] = xBC     4096 | 128 | 128; x as 64 heads of 64; B, C
          shared by all heads
          dt_t = softplus(dt_t + dt_bias) [64];  A = -exp(A_log) [64]
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   per head, S in R^{64 x
          128}, S_{-1} = 0, float32 whatever the rest is held in
          y_t = S_t C_t + D x_t
          y <- w g / sqrt(mean(g^2) + eps),  g = y silu(z)   over all 4096
          Mix = W_out y
  attention  32 heads over 8 key-value heads of 64, q = W_q u, k = W_k u,
          v = W_v u, no bias, no positional rule;  a = softmax(q k^T
          attention_multiplier + causal mask) v in float32;  Mix = W_o concat(a)
          (``QUERY_BLOCK`` queries at a time against all keys)
  readout RMSNorm, mean over the T tokens, two ReLU streams, Q = V + A - mean(A)

**The recurrence is the literal one**: ``lax.scan`` over the T tokens, a token
a step, no chunks, no dual form.  Only its memory is arranged: the scan runs
in segments (a divisor of T, at most 256 tokens) whose backward pass keeps
the state at each segment's start and steps the segment again, so that the
1,568 states of a row (3.3 GB at the published widths) are never held at
once.  The batch is walked a row at a time and every layer of a row is
recomputed in the backward pass, as ``laguna_q.py``.

Departures from the issue's equations: none known.  Assumed, as the
configuration file says: where ``embedding_multiplier`` applies, the gate
before the norm, the initialisation.

``precision`` other than ``stated`` makes a control, as in ``dueling_dqn.py``:
``bf16_held``, ``fp8_activations``, ``bf16_gradients``.
``cfg["reference_resets_state"]`` makes the control of this mechanism: the
state is set to zero at every multiple of ``mamba_chunk_size`` tokens, which
is what a chunked scan that loses its carry computes.
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
_MAMBA = ("w_in", "conv_kernel", "conv_bias", "A_log", "dt_bias", "D", "norm", "w_out")
_ATTN = ("w_q", "w_k", "w_v", "w_o")
_FFN = ("w1", "w3", "w2")
FLOAT32_ALWAYS = ("A_log", "dt_bias", "D")     # in every copy the program holds


def layer_kinds(cfg: dict) -> list:
    """The layer types run: ``layers_held`` of ``layer_types``."""
    return [cfg["layer_types"][i] for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def sizes(cfg: dict) -> dict:
    heads, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    inner, n = heads * hd, cfg["mamba_d_state"] * cfg["mamba_n_groups"]
    return dict(heads=heads, head_dim=hd, inner=inner, state=n, mixed=inner + 2 * n,
                attn_head=cfg["hidden_size"] // cfg["num_attention_heads"])


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} or, for a layer, {name: {name: shape}}."""
    d, s, w = cfg["hidden_size"], sizes(cfg), cfg["shared_intermediate_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], s["attn_head"]
    cin = 1                                    # the stem sees one frame
    shapes = {}
    for name, ch in zip(STEM, cfg["channels"]):
        k = _KERNELS[name]
        shapes[name] = {"w": (k, k, cin, ch), "b": (ch,)}
        cin = ch
    shapes["w_tok"] = (cin, d)
    for i, op in enumerate(layer_kinds(cfg)):
        layer = {"operator_norm": (d,), "ffn_norm": (d,), "w1": (d, w), "w3": (d, w), "w2": (w, d)}
        if op == "mamba":
            layer.update(w_in=(d, s["inner"] + s["mixed"] + s["heads"]),
                         conv_kernel=(s["mixed"], cfg["mamba_d_conv"]), conv_bias=(s["mixed"],),
                         A_log=(s["heads"],), dt_bias=(s["heads"],), D=(s["heads"],),
                         norm=(s["inner"],), w_out=(s["inner"], d))
        elif op == "attention":
            layer.update(w_q=(d, h * hd), w_k=(d, kv * hd), w_v=(d, kv * hd), w_o=(h * hd, d))
        else:
            raise ValueError(f"unknown layer type {op!r}")
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
    and ``D`` near one, small non-zero biases, ``A_log = log U[1, 16]`` and
    ``dt_bias`` the inverse softplus of a step size log-uniform in [1e-3,
    1e-1] (Mamba-2's initialisation)."""
    paths = jax.tree_util.tree_flatten_with_path(weight_shapes(cfg), is_leaf=_is_shape)[0]
    out = {}
    for i, (path, shape) in enumerate(paths):
        names = [p.key for p in path]
        k = jax.random.fold_in(key, i)
        last = names[-1]
        if last.endswith("norm") or last == "D":
            w = 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
        elif last in ("b", "conv_bias"):
            w = 0.01 * jax.random.normal(k, shape, jnp.float32)
        elif last == "A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif last == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:  # fan-in: a matrix's inputs; a window x channels; a depthwise kernel's taps
            fan_in = shape[-1] if last == "conv_kernel" else math.prod(shape[:-1])
            w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[last] = w
    return out


# ------------------------------------------------------------------ forward

def _segment(tokens: int) -> int:
    return max(s for s in range(1, min(SEGMENT, tokens) + 1) if tokens % s == 0)


def recurrence(x, dt, a, b, c, d, reset_every: int = 0):
    """The literal recurrence, float32: ``x`` [B, T, H, P], ``dt`` [B, T, H],
    ``a``, ``d`` [H], ``b``, ``c`` [B, T, N] -> ``y`` [B, T, H, P].  With
    ``reset_every`` the state is zeroed before every token whose index is a
    multiple of it (the control)."""
    bsz, t, heads, p = x.shape
    keep = jnp.ones((t,), jnp.float32)
    if reset_every:
        keep = (jnp.arange(t) % reset_every != 0).astype(jnp.float32)

    def step(state, token):
        xt, dtt, bt, ct, kept = token               # [B, H, P], [B, H], [B, N], [B, N], []
        state = (jnp.exp(dtt * a)[..., None, None] * (state * kept)
                 + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return state, jnp.sum(state * ct[:, None, None, :], axis=-1) + d[:, None] * xt

    seg = _segment(t)

    @jax.checkpoint
    def segment(state, tokens):
        return jax.lax.scan(step, state, tokens)

    by_time = tuple(jnp.moveaxis(v, 1, 0).reshape(t // seg, seg, *v.shape[:1], *v.shape[2:])
                    for v in (x, dt, b, c)) + (keep.reshape(t // seg, seg),)
    _, ys = jax.lax.scan(segment, jnp.zeros((bsz, heads, p, b.shape[-1]), jnp.float32), by_time)
    return jnp.moveaxis(ys.reshape(t, bsz, heads, p), 0, 1)


def mamba(u, p, cfg, dtype, act):
    s, k, f32 = sizes(cfg), cfg["mamba_d_conv"], jnp.float32
    bsz, t, _ = u.shape
    z, xbc, dt = jnp.split(act(u @ p["w_in"].astype(dtype)),
                           (s["inner"], s["inner"] + s["mixed"]), axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + t, :] * p["conv_kernel"][:, j].astype(dtype) for j in range(k))
    xbc = act(jax.nn.silu(act(xbc + p["conv_bias"].astype(dtype))))
    x, b, c = jnp.split(xbc, (s["inner"], s["inner"] + s["state"]), axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    y = recurrence(x.reshape(bsz, t, s["heads"], s["head_dim"]).astype(f32), dt,
                   -jnp.exp(p["A_log"].astype(f32)), b.astype(f32), c.astype(f32),
                   p["D"].astype(f32),
                   cfg["mamba_chunk_size"] if cfg.get("reference_resets_state") else 0)
    g = act(y.reshape(bsz, t, s["inner"]).astype(dtype)).astype(f32) * jax.nn.silu(z.astype(f32))
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return act(act((g * p["norm"].astype(f32)).astype(dtype)) @ p["w_out"].astype(dtype))


def attention(u, p, cfg, dtype, act):
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], sizes(cfg)["attn_head"]
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
        scores = (jnp.einsum("bshd,bthd->bhst", qb, k).astype(jnp.float32)
                  * cfg["attention_multiplier"])
        mask = keys[None, :] <= rows[:, None]
        probs = act(jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(dtype))
        return act(jnp.einsum("bhst,bthd->bshd", probs, v))

    n = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jnp.moveaxis(q.reshape(bsz, t // n, n, heads, hd), 1, 0)
    out = jax.lax.map(block, (blocks, jnp.arange(0, t, n)))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, heads * hd)
    return act(out @ p["w_o"].astype(dtype))


def layer(h, p, op, cfg, dtype, act):
    eps, m = cfg["rms_norm_eps"], jnp.asarray(cfg["residual_multiplier"], dtype)
    u = rms_norm(h, p["operator_norm"], eps, dtype)
    h = h + m * (mamba if op == "mamba" else attention)(u, p, cfg, dtype, act)
    u = rms_norm(h, p["ffn_norm"], eps, dtype)
    return h + m * swiglu(u, p["w1"], p["w3"], p["w2"], dtype, act)


def forward_rows(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x):
    """Q values [B, A] of the rows given, all at once; each layer recomputed
    in a backward pass."""
    h = act(history_stem(weights, obs, dtype, act) * jnp.asarray(cfg["embedding_multiplier"], dtype))
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
    """The program's parameter tree (``models/granite_hybrid.GraniteHybridQ``)
    holding these weights: a run of layers of one kind stacked; ``A_log``,
    ``dt_bias`` and ``D`` float32 in every copy."""
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    p = {"w_tok": cast(weights["w_tok"]), "final_norm": {"weight": cast(weights["final_norm"])}}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        p[flax] = {"kernel": cast(weights[name]["w"]), "bias": cast(weights[name]["b"])}
    layers = []
    for i, op in enumerate(layer_kinds(cfg)):
        w = weights[f"layer_{i}"]
        names = _MAMBA if op == "mamba" else _ATTN
        layers.append({
            "operator_norm": {"weight": cast(w["operator_norm"])},
            "ffn_norm": {"weight": cast(w["ffn_norm"])},
            op: {n: w[n].astype(jnp.float32) if n in FLOAT32_ALWAYS else cast(w[n])
                 for n in names},
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
            **{n: f32(q[op][n]) for n in (_MAMBA if op == "mamba" else _ATTN)},
            **{n: f32(q["dense"][n]) for n in _FFN}}
    return w

"""Plain reference for the Nemotron 3 Super Q-network over a history of frames
and one learner step on it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program; the leaf helpers that are no model's
(RMSNorm, the dueling readout, ``_hold``) are ``lfm2_moe_q.py``'s and the stem
over single frames ``laguna_q.py``'s, as the other references take them.  The
learner step is the one ``dueling_dqn.py``'s docstring sets out (double-Q
target, importance-weighted loss, global-norm clip, one RMSProp update,
priorities ``|delta| + 1e-6``) with ``lfm2_moe_q.py``'s balancing rule on the
expert bias.  The network is ISSUE 59's section 1, eps ``layer_norm_epsilon``
in every norm, no bias but the convolution's, ``d`` = ``hidden_size``.  The
layers are the published ones, **one sublayer each** and held as such
(``layer_<i>``, ``i`` counting the held layers): nothing is paired here, where
the program runs a mixer and the expert layer after it as one block.

  tokens  x_0 = W_tok (z - mean_p z)          T = F h w, time-major (``laguna_q.stem``)
  layer   h <- h + Mixer_l(RMSNorm_l(h)),  Mixer_l by ``hybrid_override_pattern``
  M       [z | xBC | dt] = W_in u      widths H P | H P + 2 G N | H  (the heads held)
          xBC_t <- silu(sum_{k=0..3} w[c,k] xBC_{t-3+k} + b[c])   depthwise, causal
          [x | B | C] = xBC;  x as H heads of P;  B, C as G groups of N
          dt_t = softplus(dt_t + dt_bias) [H];  A = -exp(A_log) [H]
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_{g(h),t}^T,  S_{-1} = 0,  g(h) = h // (H / G)
          y_t = S_t C_{g(h),t} + D x_t                        a token a step, float32
          y <- w g / sqrt(mean_group(g^2) + eps),  g = y silu(z), the mean over a
          group's H P / G channels;  Mixer = W_out y
  *       q = W_q u (heads of ``head_dim``), k = W_k u, v = W_v u (key-value heads),
          no bias, no positional rule;  a = softmax(q k^T / sqrt(head_dim) + causal
          mask) v in float32;  Mixer = W_o concat(a)   (``QUERY_BLOCK`` queries at a time)
  E       s = sigmoid(W_r u) in float32 over the router's outputs; I = the
          num_experts_per_tok largest of s + b (the earlier of two equal ones
          first; b chooses and does not weigh; n_group = topk_group = 1)
          g_i = routed_scaling_factor s_i / (sum_{j in I} s_j + 1e-20)
          v = W_down u;  E_i(v) = relu(v W1_i)^2 W2_i
          Mixer = (sum_{i in I, i held} g_i E_i(v)) W_up + relu(u Ws1)^2 Ws2
  readout RMSNorm, mean over the T tokens, two ReLU streams, Q = V + A - mean(A)

**The recurrence is the literal one**: ``lax.scan`` over the T tokens, a token
a step, no chunks, no dual form.  Only its memory is arranged, as
``granite_h_q.py``'s: the scan runs in segments (a divisor of T, at most 256
tokens) whose backward pass keeps the state at each segment's start and steps
the segment again.  The held experts are walked by ``lax.scan`` over masks (no
sort, no walk), attention a block of queries at a time against every key, the
batch a row at a time and every layer of a row recomputed in the backward
pass, so that the step fits the chip beside the driver's arguments.

What a chip holds is the weights' shapes: ``mamba_num_heads`` heads (whole
groups of ``published.mamba_num_heads / n_groups``), ``num_attention_heads``
query heads on ``num_key_value_heads`` key-value heads, the shared expert's
columns ``shared_expert_held``, the experts ``experts_held``; the partial sums
of ``W_out``, ``W_o`` and ``Ws2`` go on as they are.

Departures from the issue's equations: none known.  Assumed, as the
configuration file says under ``assumed``: no positional rule, the router and
the shared expert at ``d``, the latent projections bare, the bias's rule, the
initialisation.

``precision`` other than ``stated`` makes a control, as in ``dueling_dqn.py``:
``bf16_held``, ``fp8_activations``, ``bf16_gradients``.  Five controls of this
configuration's mechanisms are keys of the configuration (``FLAGS``):
``reference_shares_group0`` gives every head the ``B`` and ``C`` of group 0 (a
scan that lost its groups); ``reference_norms_all_channels`` takes the gated
norm's mean square over all held channels; ``reference_silu_experts`` gives
the experts, routed and shared, ``silu`` in place of ``relu^2``;
``reference_router_reads_latent`` scores ``v`` with the router's first
``moe_latent_size`` rows in place of ``u``; ``reference_unscaled_gates``
leaves ``routed_scaling_factor`` off the gates.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.laguna_q import stem as history_stem
from reference.lfm2_moe_q import (  # leaf helpers, no model's
    PRECISIONS, PRIORITY_EPS, STEM, _FLAX_HEAD, _FLAX_STEM, _KERNELS, _hold, _is_shape,
    readout, rms_norm,
)

QUERY_BLOCK = 224          # 1,568 = 7 x 224
SEGMENT = 256              # the recurrence's backward pass keeps a state this often, at most
GATE_SUM_EPS = 1e-20
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
_MAMBA = ("w_in", "conv_kernel", "conv_bias", "A_log", "dt_bias", "D", "norm", "w_out")
_ATTN = ("w_q", "w_k", "w_v", "w_o")
_MOE = ("router", "expert_bias", "w_down", "w_up", "w1", "w2")
_SHARED = {"w1": "shared_w1", "w2": "shared_w2"}
FLOAT32_ALWAYS = ("A_log", "dt_bias", "D")     # in every copy the program holds
FLAGS = ("reference_shares_group0", "reference_norms_all_channels", "reference_silu_experts",
         "reference_router_reads_latent", "reference_unscaled_gates")


def layer_kinds(cfg: dict) -> list:
    """The kinds of the one-sublayer layers run: ``layers_held`` of the pattern."""
    pattern = cfg["hybrid_override_pattern"]
    return [KINDS[pattern[i]] for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def experts_held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", (0, router_outputs(cfg))))


def router_outputs(cfg: dict) -> int:
    return cfg.get("router_outputs", cfg["n_routed_experts"])


def sizes(cfg: dict) -> dict:
    """The held heads' and groups' counts and widths."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    per_group = cfg.get("published", cfg).get("mamba_num_heads", heads) // cfg["n_groups"]
    groups, n = heads // per_group, cfg["ssm_state_size"]
    lo, hi = cfg.get("shared_expert_held") or (
        0, cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"])
    return dict(heads=heads, head_dim=hd, inner=heads * hd, groups=groups, state=n,
                mixed=heads * hd + 2 * groups * n, shared=hi - lo)


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} or, for a layer, {name: {name: shape}}."""
    d, s = cfg["hidden_size"], sizes(cfg)
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lo, hi = experts_held(cfg)
    width, f, outputs = cfg["moe_latent_size"], cfg["moe_intermediate_size"], router_outputs(cfg)
    cin = 1                                    # the stem sees one frame
    shapes = {}
    for name, ch in zip(STEM, cfg["channels"]):
        k = _KERNELS[name]
        shapes[name] = {"w": (k, k, cin, ch), "b": (ch,)}
        cin = ch
    shapes["w_tok"] = (cin, d)
    for i, kind in enumerate(layer_kinds(cfg)):
        layer = {"pre_norm": (d,)}
        if kind == "mamba":
            layer.update(w_in=(d, s["inner"] + s["mixed"] + s["heads"]),
                         conv_kernel=(s["mixed"], cfg["conv_kernel"]), conv_bias=(s["mixed"],),
                         A_log=(s["heads"],), dt_bias=(s["heads"],), D=(s["heads"],),
                         norm=(s["inner"],), w_out=(s["inner"], d))
        elif kind == "attention":
            layer.update(w_q=(d, h * hd), w_k=(d, kv * hd), w_v=(d, kv * hd), w_o=(h * hd, d))
        else:
            layer.update(router=(d, outputs), expert_bias=(outputs,), w_down=(d, width),
                         w_up=(width, d), w1=(hi - lo, width, f), w2=(hi - lo, f, width),
                         shared_w1=(d, s["shared"]), shared_w2=(s["shared"], d))
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
    """Seeded float32 weights: LeCun-normal matrices and kernels (an expert's
    fan-in its own inputs), norm weights and ``D`` near one, small non-zero
    biases, the expert bias among them, ``A_log = log U[1, 16]`` and
    ``dt_bias`` the inverse softplus of a step size log-uniform in
    [``time_step_min``, ``time_step_max``] (Mamba-2's initialisation)."""
    lo, hi = (math.log(cfg.get(k, v)) for k, v in (("time_step_min", 1e-3), ("time_step_max", 1e-1)))
    paths = jax.tree_util.tree_flatten_with_path(weight_shapes(cfg), is_leaf=_is_shape)[0]
    out = {}
    for i, (path, shape) in enumerate(paths):
        names = [p.key for p in path]
        k = jax.random.fold_in(key, i)
        last = names[-1]
        if last.endswith("norm") or last == "D":
            w = 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
        elif last in ("b", "conv_bias", "expert_bias"):
            w = 0.01 * jax.random.normal(k, shape, jnp.float32)
        elif last == "A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif last == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi)),
                             cfg.get("time_step_floor", 1e-4))
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:  # fan-in: a depthwise kernel's taps; an expert's inputs; a matrix's; a window x channels
            fan_in = (shape[-1] if last == "conv_kernel"
                      else math.prod(shape[1:-1] if len(shape) == 3 else shape[:-1]))
            w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[last] = w
    return out


# ------------------------------------------------------------------ forward

def _segment(tokens: int) -> int:
    return max(s for s in range(1, min(SEGMENT, tokens) + 1) if tokens % s == 0)


def recurrence(x, dt, a, b, c, d):
    """The literal recurrence, float32: ``x`` [B, T, H, P], ``dt`` [B, T, H],
    ``a``, ``d`` [H], ``b``, ``c`` [B, T, G, N] -> ``y`` [B, T, H, P]; head
    ``h`` reads group ``h // (H / G)``."""
    bsz, t, heads, p = x.shape
    per = heads // b.shape[2]

    def step(state, token):
        xt, dtt, bt, ct = token                     # [B, H, P], [B, H], [B, G, N], [B, G, N]
        bt, ct = jnp.repeat(bt, per, axis=1), jnp.repeat(ct, per, axis=1)        # [B, H, N]
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.sum(state * ct[:, :, None, :], axis=-1) + d[:, None] * xt

    seg = _segment(t)

    @jax.checkpoint
    def segment(state, tokens):
        return jax.lax.scan(step, state, tokens)

    by_time = tuple(jnp.moveaxis(v, 1, 0).reshape(t // seg, seg, *v.shape[:1], *v.shape[2:])
                    for v in (x, dt, b, c))
    _, ys = jax.lax.scan(segment, jnp.zeros((bsz, heads, p, b.shape[-1]), jnp.float32), by_time)
    return jnp.moveaxis(ys.reshape(t, bsz, heads, p), 0, 1)


def mamba(u, p, cfg, dtype, act):
    """The held heads' part of ``W_out``'s sum; their count and the groups'
    are the weights' (``A_log`` a head, the convolution's channels)."""
    k, n, hd, f32 = cfg["conv_kernel"], cfg["ssm_state_size"], cfg["mamba_head_dim"], jnp.float32
    heads = p["A_log"].shape[0]
    inner = heads * hd
    groups = (p["conv_kernel"].shape[0] - inner) // (2 * n)
    bsz, t, _ = u.shape
    z, xbc, dt = jnp.split(act(u @ p["w_in"].astype(dtype)), (inner, 2 * inner + 2 * groups * n), axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + t, :] * p["conv_kernel"][:, j].astype(dtype) for j in range(k))
    xbc = act(jax.nn.silu(act(xbc + p["conv_bias"].astype(dtype))))
    x, b, c = jnp.split(xbc, (inner, inner + groups * n), axis=-1)
    b, c = (v.astype(f32).reshape(bsz, t, groups, n) for v in (b, c))
    if cfg.get("reference_shares_group0"):
        b, c = (jnp.broadcast_to(v[:, :, :1], v.shape) for v in (b, c))
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    y = recurrence(x.reshape(bsz, t, heads, hd).astype(f32), dt, -jnp.exp(p["A_log"].astype(f32)),
                   b, c, p["D"].astype(f32))
    g = act(y.reshape(bsz, t, inner).astype(dtype)).astype(f32) * jax.nn.silu(z.astype(f32))
    over = 1 if cfg.get("reference_norms_all_channels") else groups
    g = g.reshape(bsz, t, over, inner // over)
    g = (g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg["layer_norm_epsilon"]))
    g = g.reshape(bsz, t, inner)
    return act(act((g * p["norm"].astype(f32)).astype(dtype)) @ p["w_out"].astype(dtype))


def attention(u, p, cfg, dtype, act):
    """The held query heads' part of ``W_o``'s sum; the heads' counts are the
    weights'."""
    hd = cfg["head_dim"]
    heads, kv = p["w_q"].shape[1] // hd, p["w_k"].shape[1] // hd
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
    return act(out @ p["w_o"].astype(dtype))


def expert_rule(h, cfg):
    return jax.nn.silu(h) if cfg.get("reference_silu_experts") else jnp.square(jax.nn.relu(h))


def route(scores, bias, cfg: dict):
    """(chosen [.., k], gates [.., k]): the k largest of ``scores + bias``, the
    earlier of two equal ones first (``lax.top_k``); the gates the chosen
    scores themselves over their sum, scaled."""
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_SUM_EPS)
    if cfg.get("reference_unscaled_gates"):
        return chosen, gates
    return chosen, gates * cfg.get("routed_scaling_factor", 1.0)


def routed(u, p, cfg, dtype, act, held=None):
    """(the part of the latent mixture the experts ``held`` = [lo, hi) give,
    back at ``d`` through ``W_up``; the pairs on each of the router's outputs
    [E]); the held experts' weights are ``p['w1'][e - lo]``, walked one after
    the other over masks."""
    lo, hi = held or experts_held(cfg)
    v = act(u @ p["w_down"].astype(dtype))
    read = (v.astype(jnp.float32), p["router"][:v.shape[-1]]) if cfg.get(
        "reference_router_reads_latent") else (u.astype(jnp.float32), p["router"])
    scores = jax.nn.sigmoid(jnp.matmul(read[0], read[1].astype(jnp.float32), precision="highest"))
    chosen, gates = route(scores, p["expert_bias"], cfg)

    def one(y, e_w):                      # the next held expert's part, added
        e, w1, w2 = e_w
        g = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1).astype(dtype)   # 0 if not chosen
        out = act(act(expert_rule(act(v @ w1.astype(dtype)), cfg)) @ w2.astype(dtype))
        return y + g[..., None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros(v.shape, dtype), (jnp.arange(lo, hi), p["w1"], p["w2"]))
    load = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
                   .reshape(-1, scores.shape[-1]), axis=0)
    return act(act(y) @ p["w_up"].astype(dtype)), load


def shared_expert(u, p, cfg, dtype, act):
    """The held columns' part of ``Ws2``'s sum."""
    h = act(expert_rule(act(u @ p["shared_w1"].astype(dtype)), cfg))
    return act(h @ p["shared_w2"].astype(dtype))


def moe(u, p, cfg, dtype, act):
    y, load = routed(u, p, cfg, dtype, act)
    return y + shared_expert(u, p, cfg, dtype, act), load


def layer(h, p, kind, cfg, dtype, act):
    """(the layer's output, its expert loads [E]: zeros where it has no experts)."""
    u = rms_norm(h, p["pre_norm"], cfg["layer_norm_epsilon"], dtype)
    if kind == "moe":
        y, load = moe(u, p, cfg, dtype, act)
        return h + y, load
    mixer = mamba if kind == "mamba" else attention
    return h + mixer(u, p, cfg, dtype, act), jnp.zeros((router_outputs(cfg),))


def forward_rows(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x):
    """(Q values [B, A], the layers' expert loads [layers, E], zeros where a
    layer has no experts) of the rows given, all at once; each layer
    recomputed in a backward pass."""
    h = history_stem(weights, obs, dtype, act)
    loads = []
    for i, kind in enumerate(layer_kinds(cfg)):
        h, load = jax.checkpoint(
            lambda h, p, kind=kind: layer(h, p, kind, cfg, dtype, act))(h, weights[f"layer_{i}"])
        loads.append(load)
    return (readout(weights, h, dict(cfg, norm_eps=cfg["layer_norm_epsilon"]), dtype, act),
            jnp.stack(loads))


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
    for i, (kind, load) in enumerate(zip(layer_kinds(cfg), loads.astype(jnp.float32))):
        if kind == "moe":
            error = jnp.clip(load / jnp.mean(load) - 1.0, -1.0, 1.0)
            p = new_weights[f"layer_{i}"]
            new_weights[f"layer_{i}"] = dict(
                p, expert_bias=p["expert_bias"] - (rate * error).astype(dtype))
    new_weights = _hold(new_weights, dtype)
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    return (f32(new_weights), f32(new_nu), delta.astype(jnp.float32),
            jnp.abs(delta).astype(jnp.float32) + PRIORITY_EPS, loss.astype(jnp.float32))


# --------------------------------------------- to and from the program's tree

def blocks(cfg: dict) -> list:
    """[(the mixer's layer, its kind, the expert layer that follows it or
    None)]: how the program pairs the held layers into blocks of two
    sublayers (a mixer alone is a block with no FFN)."""
    kinds, out, i = layer_kinds(cfg), [], 0
    held = list(cfg.get("layers_held", range(cfg["num_hidden_layers"])))
    while i < len(kinds):
        follows = i + 1 < len(kinds) and kinds[i + 1] == "moe" and held[i + 1] == held[i] + 1
        out.append((i, kinds[i], i + 1 if follows else None))
        i += 2 if follows else 1
    return out


def block_runs(cfg: dict) -> list:
    """[(first block, count)]: the consecutive blocks of one kind, which the
    program holds stacked under ``layers_<first>_<last>``."""
    runs = []
    for j, (_, kind, ffn) in enumerate(blocks(cfg)):
        key = (kind, ffn is not None)
        if runs and runs[-1][2] == key:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1, key)
        else:
            runs.append((j, 1, key))
    return [(first, count) for first, count, _ in runs]


def to_program_params(weights: dict, cfg: dict, dtype=None) -> dict:
    """The program's parameter tree (``models/nemotron_h.NemotronHQ``) holding
    these weights: a mixer's layer and the expert layer after it as one block
    (``operator_norm`` and ``ffn_norm`` the two layers' norms), a run of blocks
    of one kind stacked; the router, its bias and a Mamba-2 layer's ``A_log``,
    ``dt_bias`` and ``D`` float32 in every copy."""
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    p = {"w_tok": cast(weights["w_tok"]), "final_norm": {"weight": cast(weights["final_norm"])}}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        p[flax] = {"kernel": cast(weights[name]["w"]), "bias": cast(weights[name]["b"])}
    made = []
    for i, kind, ffn in blocks(cfg):
        w = weights[f"layer_{i}"]
        out = {"operator_norm": {"weight": cast(w["pre_norm"])},
               kind: {n: (f32 if n in FLOAT32_ALWAYS else cast)(w[n])
                      for n in (_MAMBA if kind == "mamba" else _ATTN)}}
        if ffn is not None:
            e = weights[f"layer_{ffn}"]
            out["ffn_norm"] = {"weight": cast(e["pre_norm"])}
            out["moe"] = {n: (f32 if n in ("router", "expert_bias") else cast)(e[n]) for n in _MOE}
            out["shared_expert"] = {n: cast(e[s]) for n, s in _SHARED.items()}
        made.append(out)
    for first, count in block_runs(cfg):
        if count == 1:
            p[f"layer_{first}"] = made[first]
        else:
            p[f"layers_{first}_{first + count - 1}"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *made[first:first + count])
    return {"params": p}


def from_program_params(params: dict, cfg: dict) -> dict:
    p = params["params"]
    f32 = lambda x: jnp.asarray(x).astype(jnp.float32)  # noqa: E731
    w = {"w_tok": f32(p["w_tok"]), "final_norm": f32(p["final_norm"]["weight"])}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        w[name] = {"w": f32(p[flax]["kernel"]), "b": f32(p[flax]["bias"])}
    held = {}
    for first, count in block_runs(cfg):
        if count == 1:
            held[first] = p[f"layer_{first}"]
        else:
            stacked = p[f"layers_{first}_{first + count - 1}"]
            for j in range(count):
                held[first + j] = jax.tree_util.tree_map(lambda x: x[j], stacked)
    for j, (i, kind, ffn) in enumerate(blocks(cfg)):
        q = held[j]
        w[f"layer_{i}"] = {"pre_norm": f32(q["operator_norm"]["weight"]),
                           **{n: f32(q[kind][n]) for n in (_MAMBA if kind == "mamba" else _ATTN)}}
        if ffn is not None:
            w[f"layer_{ffn}"] = {"pre_norm": f32(q["ffn_norm"]["weight"]),
                                 **{n: f32(q["moe"][n]) for n in _MOE},
                                 **{s: f32(q["shared_expert"][n]) for n, s in _SHARED.items()}}
    return w

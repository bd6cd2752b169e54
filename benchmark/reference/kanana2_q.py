"""Plain reference for the Kanana-2 Q-network over a history of frames and one
learner step on it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program; the leaf helpers that are no model's
(RMSNorm, SwiGLU, the dueling readout, ``_hold``) are ``lfm2_moe_q.py``'s and
the stem over single frames ``laguna_q.py``'s, as ``ling3_q.py`` takes them.
The latent layer and the router are written here from ISSUE 56's equations and
not taken from ``ling3_q.py``, which holds the same mechanism for another
family: two readings of it, made apart (this one turns each rotary pair by
its 2 x 2 rotation, masks whole score rows a block of queries at a time under a
``lax.scan``, and chooses a token's experts by rounds of "the largest not yet
taken", where that one stacks the pairs, maps over blocks and sorts).  The
learner step is the one ``dueling_dqn.py``'s docstring sets out (double-Q
target, importance-weighted loss, global-norm clip, one RMSProp update,
priorities ``|delta| + 1e-6``) with ``lfm2_moe_q.py``'s balancing rule on the
expert bias.  The network, eps 1e-6 in every norm, no bias anywhere, ``d`` =
``hidden_size``, ``H`` = ``num_attention_heads`` heads, all held:

  tokens  x_0 = W_tok (z - mean_p z)          T = F h w, time-major (``laguna_q.stem``)
  layer   h <- h + Mix(RMSNorm(h));  h <- h + FFN_l(RMSNorm(h))
          FFN_l the dense SwiGLU of intermediate_size for l < first_k_dense_replace,
          else the experts
  Mix     [qN_h; qR_h] = W_q,h u in R^(nope+rope)      (q_lora_rank null: one product, no norm)
          [c; kR] = W_dkv u in R^(kv_lora_rank+rope)   (kv_a_proj_with_mqa)
          c' = w_c c / sqrt(mean(c^2) + eps)           (kv_a_layernorm)
          [kN_h; v_h] = W_ukv,h c' in R^(nope+v)       (kv_b_proj)
          qR_h, kR turned at the token's index t in the pairs (2j, 2j + 1) by the
          angle t theta^(-2j/rope) (rope_interleave; rope_scaling null: nothing on
          the scale); kR is one key for all heads
          score_h(t, s) = (qN_h,t . kN_h,s + qR_h,t . kR_s) / sqrt(nope + rope),  s <= t
          a_h = softmax(score_h) v_h in float32;  Mix = W_o [a_h]     (no gate)
  MoE     s = sigmoid(W_r u) in float32 over the router's outputs; I = the
          num_experts_per_tok largest of s + b (the earlier of two equal ones
          first; b chooses and does not weigh; n_group = topk_group = 1)
          g_i = s_i / (sum_{j in I} s_j + 1e-20) x routed_scaling_factor
          y = sum_{i in I, i held} g_i SwiGLU^(i)(u) + SwiGLU^shared(u), the shared
          one of width n_shared_experts x moe_intermediate_size
  readout RMSNorm, mean over the T tokens, two ReLU streams, Q = V + A - mean(A)

The learner has no cache: the latent is expanded to a key and a value a head
(the non-absorbed form), as in the program.  The batch is walked a row at a
time, every layer of a row is recomputed in the backward pass, and the held
experts are walked by ``lax.scan``, so that the step fits the chip beside the
driver's arguments.

Departures from the issue's equations: none known.  Assumed, as the
configuration file says under ``assumed``: the shared experts as one SwiGLU of
their summed width, the bias's rule, the initialisation.

``precision`` other than ``stated`` makes a control, as in ``dueling_dqn.py``:
``bf16_held``, ``fp8_activations``, ``bf16_gradients``.  Three controls of this
configuration's mechanisms are keys of the configuration (``FLAGS``):
``cfg["reference_drops_shared_key"]`` leaves ``qR . kR`` out of the scores (a
kernel that lost its second operand); ``cfg["reference_skips_latent_norm"]``
expands the latent as it comes, ``c' = c`` (a mixer that lost
``kv_a_layernorm``); ``cfg["reference_unscaled_gates"]`` leaves
``routed_scaling_factor`` off the gates.
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
GATE_SUM_EPS = 1e-20
_LATENT = ("w_q", "w_dkv", "kv_norm", "w_ukv", "w_o")
_FFN = ("w1", "w3", "w2")
_SHARED = ("shared_w1", "shared_w3", "shared_w2")
OP = "latent_attention"
FLAGS = ("reference_drops_shared_key", "reference_skips_latent_norm", "reference_unscaled_gates")


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] of the layers run: ``layers_held`` of the published
    depth, dense before ``first_k_dense_replace``."""
    dense = cfg.get("first_k_dense_replace", 0)
    return [(OP, "dense" if i < dense else "moe")
            for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def experts_held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg.get("router_outputs", cfg["n_routed_experts"]))))


def router_outputs(cfg: dict) -> int:
    return cfg.get("router_outputs", cfg["n_routed_experts"])


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} or, for a layer, {name: {name: shape}}."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    lo, hi = experts_held(cfg)
    outputs = router_outputs(cfg)
    w, f = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    s = cfg["n_shared_experts"] * w
    cin = 1                                    # the stem sees one frame
    shapes = {}
    for name, ch in zip(STEM, cfg["channels"]):
        k = _KERNELS[name]
        shapes[name] = {"w": (k, k, cin, ch), "b": (ch,)}
        cin = ch
    shapes["w_tok"] = (cin, d)
    for i, (_, ffn) in enumerate(layer_kinds(cfg)):
        layer = {"operator_norm": (d,), "ffn_norm": (d,),
                 "w_q": (d, h * (dn + dr)), "w_dkv": (d, r + dr), "kv_norm": (r,),
                 "w_ukv": (r, h * (dn + dv)), "w_o": (h * dv, d)}
        if ffn == "dense":
            layer.update(w1=(d, f), w3=(d, f), w2=(f, d))
        else:
            layer.update(router=(d, outputs), expert_bias=(outputs,), w1=(hi - lo, d, w),
                         w3=(hi - lo, d, w), w2=(hi - lo, w, d), shared_w1=(d, s),
                         shared_w3=(d, s), shared_w2=(s, d))
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
    fan-in its own inputs), norm weights near one, small non-zero biases, the
    expert bias among them."""
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
        else:  # fan-in: an expert's inputs; a matrix's; a window x channels
            fan_in = math.prod(shape[1:-1] if len(shape) == 3 else shape[:-1])
            w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[last] = w
    return out


# ------------------------------------------------------------------ forward

def turned(x, theta: float):
    """``x`` [B, T, .., R], float32, with the pairs ``(2j, 2j + 1)`` turned by
    ``t theta^(-2j / R)`` at token ``t``: each pair times its 2 x 2 rotation."""
    r, t = x.shape[-1], x.shape[1]
    rate = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * rate[None, :]               # [T, R/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    turn = jnp.stack([jnp.stack([cos, sin], -1), jnp.stack([-sin, cos], -1)], -2)   # [T, R/2, in, out]
    pairs = jnp.moveaxis(x.astype(jnp.float32), 1, -2).reshape(*x.shape[:1], *x.shape[2:-1], t, r // 2, 2)
    out = jnp.einsum("...tji,tjio->...tjo", pairs, turn, precision="highest")
    return jnp.moveaxis(out.reshape(*x.shape[:1], *x.shape[2:-1], t, r), -2, 1)


def latent_attention(u, p, cfg, dtype, act):
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    bsz, t, _ = u.shape
    theta, scale = float(cfg["rope_theta"]), 1.0 / math.sqrt(dn + dr)
    q = act(u @ p["w_q"].astype(dtype)).reshape(bsz, t, h, dn + dr)
    q_n = act((q[..., :dn].astype(jnp.float32) * scale).astype(dtype))
    q_r = act((turned(q[..., dn:], theta) * scale).astype(dtype))
    down = act(u @ p["w_dkv"].astype(dtype))
    c, k_r = down[..., :r], act(turned(down[..., r:], theta).astype(dtype))         # [B, T, R]: one key
    if not cfg.get("reference_skips_latent_norm"):
        c = rms_norm(c, p["kv_norm"], cfg["rms_norm_eps"], dtype)
    kv = act(c @ p["w_ukv"].astype(dtype)).reshape(bsz, t, h, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    n = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def rows_of(_, first):
        """The outputs of queries ``first .. first + n`` against every key."""
        qn = jax.lax.dynamic_slice_in_dim(q_n, first, n, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_r, first, n, axis=1)
        score = jnp.einsum("bqhd,bkhd->bhqk", qn, k_n).astype(jnp.float32)
        if not cfg.get("reference_drops_shared_key"):
            score = score + jnp.einsum("bqhd,bkd->bhqk", qr, k_r).astype(jnp.float32)
        seen = jnp.arange(t)[None, :] <= (first + jnp.arange(n))[:, None]
        score = jnp.where(seen, score, -jnp.inf)
        weight = jnp.exp(score - jnp.max(score, axis=-1, keepdims=True))
        weight = act((weight / jnp.sum(weight, axis=-1, keepdims=True)).astype(dtype))
        return None, act(jnp.einsum("bhqk,bkhd->bqhd", weight, v))

    _, out = jax.lax.scan(rows_of, None, jnp.arange(0, t, n))          # [t / n, B, n, H, v]
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, h * dv)
    return act(out @ p["w_o"].astype(dtype))


def router_scores(u, p):
    """Float32 scores [.., E], whatever precision the rest runs in."""
    return jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32), p["router"].astype(jnp.float32),
                                     precision="highest"))


def route(scores, bias, cfg: dict):
    """(chosen [.., k], gates [.., k]): k rounds of "the largest ``scores +
    bias`` not yet taken", the first output that holds it; the gates the
    chosen scores themselves over their sum, scaled."""
    biased = scores + bias.astype(jnp.float32)
    outputs = jnp.arange(scores.shape[-1])
    chosen = []
    for _ in range(cfg["num_experts_per_tok"]):
        best = jnp.argmax(biased, axis=-1)
        chosen.append(best)
        biased = jnp.where(outputs == best[..., None], -jnp.inf, biased)
    chosen = jnp.stack(chosen, axis=-1)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_SUM_EPS)
    if cfg.get("reference_unscaled_gates"):
        return chosen, gates
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


def layer(h, p, kinds, cfg, dtype, act):
    """(the layer's output, its expert loads [E]: zeros under a dense FFN)."""
    _, ffn = kinds
    eps = cfg["rms_norm_eps"]
    h = h + latent_attention(rms_norm(h, p["operator_norm"], eps, dtype), p, cfg, dtype, act)
    u = rms_norm(h, p["ffn_norm"], eps, dtype)
    if ffn == "dense":
        return (h + swiglu(u, p["w1"], p["w3"], p["w2"], dtype, act),
                jnp.zeros((router_outputs(cfg),)))
    y, load = moe(u, p, cfg, dtype, act)
    return h + y, load


def forward_rows(weights, obs, cfg, dtype=jnp.float32, act=lambda x: x):
    """(Q values [B, A], the layers' expert loads [layers, E], zeros where a
    layer's FFN is dense) of the rows given, all at once; each layer
    recomputed in a backward pass."""
    h = history_stem(weights, obs, dtype, act)
    loads = []
    for i, kinds in enumerate(layer_kinds(cfg)):
        h, load = jax.checkpoint(
            lambda h, p, kinds=kinds: layer(h, p, kinds, cfg, dtype, act))(h, weights[f"layer_{i}"])
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
    for i, ((_, ffn), load) in enumerate(zip(layer_kinds(cfg), loads.astype(jnp.float32))):
        if ffn == "moe":
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
    for i, kinds in enumerate(layer_kinds(cfg)):
        if runs and runs[-1][2] == kinds:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1, kinds)
        else:
            runs.append((i, 1, kinds))
    return [(first, count) for first, count, _ in runs]


def to_program_params(weights: dict, cfg: dict, dtype=None) -> dict:
    """The program's parameter tree (``models/kanana_moe.KananaMoeQ``) holding
    these weights: experts' W_1 and W_3 side by side as ``w13``, a run of
    layers of one kind stacked; the router and its bias float32 in every
    copy."""
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    p = {"w_tok": cast(weights["w_tok"]), "final_norm": {"weight": cast(weights["final_norm"])}}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        p[flax] = {"kernel": cast(weights[name]["w"]), "bias": cast(weights[name]["b"])}
    layers = []
    for i, (op, ffn) in enumerate(layer_kinds(cfg)):
        w = weights[f"layer_{i}"]
        out = {"operator_norm": {"weight": cast(w["operator_norm"])},
               "ffn_norm": {"weight": cast(w["ffn_norm"])},
               op: {n: cast(w[n]) for n in _LATENT}}
        if ffn == "dense":
            out["dense"] = {n: cast(w[n]) for n in _FFN}
        else:
            out["moe"] = {"router": w["router"].astype(jnp.float32),
                          "expert_bias": w["expert_bias"].astype(jnp.float32),
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
    for i, (op, ffn) in enumerate(layer_kinds(cfg)):
        q = held[i]
        out = {"operator_norm": f32(q["operator_norm"]["weight"]),
               "ffn_norm": f32(q["ffn_norm"]["weight"]),
               **{n: f32(q[op][n]) for n in _LATENT}}
        if ffn == "dense":
            out.update({n: f32(q["dense"][n]) for n in _FFN})
        else:
            m = q["moe"]
            f = m["w13"].shape[-1] // 2
            out.update(router=f32(m["router"]), expert_bias=f32(m["expert_bias"]),
                       w1=f32(m["w13"][..., :f]), w3=f32(m["w13"][..., f:]), w2=f32(m["w2"]),
                       **{s: f32(q["shared_expert"][n]) for n, s in zip(_FFN, _SHARED)})
        w[f"layer_{i}"] = out
    return w

"""Plain reference for the Ling-3.0 Q-network over a history of frames and one
learner step on it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program; the leaf helpers that are no model's
(RMSNorm, SwiGLU, the dueling readout, ``_hold``) are ``lfm2_moe_q.py``'s, the
stem over single frames ``laguna_q.py``'s, the delta rule's literal recurrence
``solar2_q.py``'s.  The learner step is the one ``dueling_dqn.py``'s docstring
sets out (double-Q target, importance-weighted loss, global-norm clip, one
RMSProp update, priorities ``|delta| + 1e-6``) with ``lfm2_moe_q.py``'s
balancing rule on the expert bias; the network is ISSUE 42's section 1, eps
1e-6 in every norm, ``H`` the heads held (the configuration's
``num_attention_heads`` counts what this chip holds):

  tokens  x_0 = W_tok (z - mean_p z)          T = F h w, time-major (``laguna_q.stem``)
  layer   h <- h + Mix_l(RMSNorm(h));  h <- h + FFN_l(RMSNorm(h))
          Mix_l latent attention where (l + 1) % layer_group_size == 0, else the
          delta rule; FFN_l the dense SwiGLU_6144 for l < first_k_dense_replace
          (the published count), else the experts
  linear  q = silu(conv4(W_q u)), k = silu(conv4(W_k u)), v = silu(conv4(W_v u))
          2560 -> H x 128 each, no bias; depthwise, causal, 4 taps, zeros before t = 0
          q_t <- q_t / sqrt(|q_t|^2 + 1e-6) / sqrt(128);  k_t <- k_t / sqrt(|k_t|^2 + 1e-6)
          g_t = kda_lower_bound sigmoid(exp(A_log) (W_f u_t + dt_bias))   [H, 128], in (-5, 0)
          beta_t = sigmoid(W_b u_t)   [H]
          S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,  S_{-1} = 0
          o_t = S_t^T q_t       (``solar2_q.recurrence``: a token a step, float32)
          y_t = w o_t / sqrt(mean(o_t^2) + eps) sigmoid(W_g u_t);   Mix = W_o y
  latent  [qN_h; qR_h] = W_q,h u in R^(128+64);  [c; kR] = W_dkv u in R^(512+64)
          c' = w_c c / sqrt(mean(c^2) + eps);  [kN_h; v_h] = W_ukv,h c' in R^(128+128)
          qR_h, kR rotated at the token's index, the pairs (2j, 2j + 1) by
          angle t theta^(-2j/64); kR one key for all heads
          score_h(t, s) = (qN_h,t . kN_h,s + qR_h,t . kR_s) / sqrt(192),  s <= t
          a_h = softmax(score_h) v_h in float32;  a_h <- a_h sigmoid(w_g,h . u)
          Mix = W_o [a_h]      (``QUERY_BLOCK`` queries at a time against all keys;
          the latent is expanded to a key and a value a head: the non-absorbed
          form, as the program's learner, which has no cache)
  MoE     s = sigmoid(W_r u) in float32 over the router's outputs;  s' = s + bias;
          the outputs lie in n_group groups of consecutive ones; a group's score
          is the sum of its two largest s'; the topk_group largest groups are
          kept; I = the num_experts_per_tok largest s' inside them, found by
          sorting;  g_i = s_i / sum_{j in I} s_j times routed_scaling_factor;
          y = sum_{i in I and i held} g_i SwiGLU^(i)(u) + SwiGLU^shared(u)
  readout RMSNorm, mean over the T tokens, two ReLU streams, Q = V + A - mean(A)

The batch is walked a row at a time, every layer of a row is recomputed in
the backward pass, and the held experts are walked by ``lax.scan``, as
``laguna_q.py``.

Departures from the issue's equations: none known.  Left out, as the
configuration file says under ``departures``: multi-token prediction.
Assumed, as it says under ``assumed``: the bounded gate's form, the group's
score, the ungated shared expert, the bias rule, the latent's norm as all of
``use_qk_norm``, the L2 norms' eps, the initialisation.

``precision`` other than ``stated`` makes a control, as in ``dueling_dqn.py``:
``bf16_held``, ``fp8_activations``, ``bf16_gradients``.  Four controls of this
configuration's mechanisms are keys of the configuration:
``cfg["reference_ungrouped_router"]`` takes the top 8 of all 512 outputs (a
router that forgot its groups); ``cfg["reference_drops_shared_key"]`` leaves
``qR . kR`` out of the scores (a kernel that lost its second operand);
``cfg["reference_unbounded_gate"]`` computes the log decay as ``-exp(A_log)
softplus(W_f u + dt_bias)`` (the other family's gate);
``cfg["reference_resets_state"]`` sets the state to zero every ``CHUNK``
tokens (a chunked scan that lost its carry).
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
from reference.solar2_q import _short_conv, recurrence

QUERY_BLOCK = 224          # 1,568 = 7 x 224
CHUNK = 64                 # the program's chunk (``kda_chunk_size`` if stated): where the control resets
L2_EPS = 1e-6
_LINEAR = ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f", "A_log", "dt_bias",
           "w_b", "w_g", "norm", "w_o")
_LATENT = ("w_q", "w_dkv", "kv_norm", "w_ukv", "w_g", "w_o")
_FFN = ("w1", "w3", "w2")
_SHARED = ("shared_w1", "shared_w3", "shared_w2")
FLOAT32_ALWAYS = ("A_log", "dt_bias")          # in every copy the program holds
OPS = ("linear_attention", "latent_attention")
FLAGS = ("reference_ungrouped_router", "reference_drops_shared_key", "reference_unbounded_gate",
         "reference_resets_state")


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] of the layers run: ``layers_held`` of the pattern
    ``layer_group_size`` gives, dense before the published
    ``first_k_dense_replace``."""
    published = cfg.get("published", {})
    dense = published.get("first_k_dense_replace", cfg.get("first_k_dense_replace", 0))
    period = cfg["layer_group_size"]
    return [(OPS[(i + 1) % period == 0], "dense" if i < dense else "moe")
            for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def experts_held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg.get("router_outputs", cfg["num_experts"]))))


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} or, for a layer, {name: {name: shape}}."""
    d, hd, h, taps = (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
                      cfg["short_conv_kernel_size"])
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    lo, hi = experts_held(cfg)
    outputs = cfg.get("router_outputs", cfg["num_experts"])
    w, f = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    s = cfg["moe_shared_expert_intermediate_size"] * cfg["num_shared_experts"]
    cin = 1                                    # the stem sees one frame
    shapes = {}
    for name, ch in zip(STEM, cfg["channels"]):
        k = _KERNELS[name]
        shapes[name] = {"w": (k, k, cin, ch), "b": (ch,)}
        cin = ch
    shapes["w_tok"] = (cin, d)
    for i, (op, ffn) in enumerate(layer_kinds(cfg)):
        layer = {"operator_norm": (d,), "ffn_norm": (d,)}
        if ffn == "dense":
            layer.update(w1=(d, f), w3=(d, f), w2=(f, d))
        else:
            layer.update(router=(d, outputs), expert_bias=(outputs,), w1=(hi - lo, d, w),
                         w3=(hi - lo, d, w), w2=(hi - lo, w, d), shared_w1=(d, s),
                         shared_w3=(d, s), shared_w2=(s, d))
        if op == "linear_attention":
            layer.update(w_q=(d, h * hd), w_k=(d, h * hd), w_v=(d, h * hd),
                         conv_q=(h * hd, taps), conv_k=(h * hd, taps), conv_v=(h * hd, taps),
                         w_f=(d, h * hd), A_log=(h,), dt_bias=(h * hd,), w_b=(d, h),
                         w_g=(d, h * hd), norm=(hd,), w_o=(h * hd, d))
        else:
            layer.update(w_q=(d, h * (dn + dr)), w_dkv=(d, r + dr), kv_norm=(r,),
                         w_ukv=(r, h * (dn + dv)), w_g=(d, h), w_o=(h * dv, d))
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
    """Seeded float32 weights by ``solar2_q.make_weights``'s laws: LeCun-normal
    matrices and kernels, norm weights near one, small non-zero biases,
    ``A_log = log U[1, 16]`` and ``dt_bias`` the inverse softplus of a step
    size log-uniform in [1e-3, 1e-1]."""
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

def linear_attention(u, p, cfg, dtype, act):
    f32 = jnp.float32
    n, hd = cfg["num_attention_heads"], cfg["head_dim"]
    bsz, t, _ = u.shape
    heads = lambda x: x.reshape(bsz, t, n, hd)  # noqa: E731
    q, k, v = (heads(_short_conv(act(u @ p["w_" + x].astype(dtype)), p["conv_" + x], dtype, act))
               .astype(f32) for x in "qkv")
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) / math.sqrt(hd)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = heads(act(u @ p["w_f"].astype(dtype)).astype(f32)) + p["dt_bias"].astype(f32).reshape(n, hd)
    a = jnp.exp(p["A_log"].astype(f32))[:, None]
    if cfg.get("reference_unbounded_gate"):
        g = -a * jax.nn.softplus(f)
    else:
        g = cfg["kda_lower_bound"] * jax.nn.sigmoid(a * f)
    beta = jax.nn.sigmoid(act(u @ p["w_b"].astype(dtype)).astype(f32))
    q, k, v = (act(x.astype(dtype)).astype(f32) for x in (q, k, v))
    o = recurrence(q, k, v, g, beta,
                   cfg.get("kda_chunk_size", CHUNK) if cfg.get("reference_resets_state") else 0)
    o = act(o.astype(dtype)).astype(f32)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(heads(act(u @ p["w_g"].astype(dtype)).astype(f32)))
    y = act((o * p["norm"].astype(f32) * gate).astype(dtype)).reshape(bsz, t, n * hd)
    return act(y @ p["w_o"].astype(dtype))


def rope_pairs(x, theta: float):
    """``x`` [B, T, n, R] with the pairs ``(2j, 2j + 1)`` turned by ``t
    theta^(-2j / R)``, float32."""
    r = x.shape[-1]
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)[None, :])
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], r // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def latent_attention(u, p, cfg, dtype, act):
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    bsz, t, _ = u.shape
    q = act(u @ p["w_q"].astype(dtype)).reshape(bsz, t, h, dn + dr)
    down = act(u @ p["w_dkv"].astype(dtype))
    c = rms_norm(down[..., :r], p["kv_norm"], cfg["rms_norm_eps"], dtype)
    kv = act(c @ p["w_ukv"].astype(dtype)).reshape(bsz, t, h, dn + dv)
    scale = 1.0 / math.sqrt(dn + dr)
    q_nope = act((q[..., :dn].astype(jnp.float32) * scale).astype(dtype))
    q_rope = act((rope_pairs(q[..., dn:], cfg["rope_theta"]) * scale).astype(dtype))
    k_rope = act(rope_pairs(down[..., None, r:], cfg["rope_theta"]).astype(dtype))   # [B, T, 1, R]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qn, qr, first = args                               # [B, n, H, .], the block's first query
        rows = first + jnp.arange(qn.shape[1])
        scores = jnp.einsum("bshd,bthd->bhst", qn, k_nope).astype(jnp.float32)
        if not cfg.get("reference_drops_shared_key"):
            scores = scores + jnp.einsum("bshd,btd->bhst", qr, k_rope[:, :, 0]).astype(jnp.float32)
        mask = keys[None, :] <= rows[:, None]
        probs = act(jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(dtype))
        return act(jnp.einsum("bhst,bthd->bshd", probs, v))

    n = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = lambda x: jnp.moveaxis(x.reshape(bsz, t // n, n, h, x.shape[-1]), 1, 0)  # noqa: E731
    out = jax.lax.map(block, (blocks(q_nope), blocks(q_rope), jnp.arange(0, t, n)))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, h, dv)
    gate = act(jax.nn.sigmoid(act(u @ p["w_g"].astype(dtype))))                    # [B, T, H]
    out = act(out * gate[..., None]).reshape(bsz, t, h * dv)
    return act(out @ p["w_o"].astype(dtype))


def router_scores(u, p):
    """Float32 scores [.., E], whatever precision the rest runs in."""
    return jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32), p["router"].astype(jnp.float32),
                                     precision="highest"))


def route(scores, bias, cfg: dict):
    """(chosen [.., k], gates [.., k]), by sorting: the groups' scores (the
    sum of a group's two largest ``scores + bias``), the ``topk_group``
    largest groups, the k largest biased scores inside them (the earlier of
    two equal ones first); the chosen scores over their sum, scaled."""
    biased = scores + bias.astype(jnp.float32)
    groups = 1 if cfg.get("reference_ungrouped_router") else cfg.get("n_group", 1)
    if groups > 1:
        by_group = biased.reshape(*biased.shape[:-1], groups, -1)
        group_score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)
        kept = jnp.argsort(-group_score, axis=-1, stable=True)[..., :cfg["topk_group"]]
        keep = jnp.any(kept[..., None] == jnp.arange(groups), axis=-2)           # [.., groups]
        biased = jnp.where(jnp.repeat(keep, by_group.shape[-1], axis=-1), biased, -jnp.inf)
    chosen = jnp.argsort(-biased, axis=-1, stable=True)[..., :cfg["num_experts_per_tok"]]
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


def layer(h, p, kinds, cfg, dtype, act):
    """(the layer's output, its expert loads [E]: zeros under a dense FFN)."""
    op, ffn = kinds
    eps = cfg["rms_norm_eps"]
    u = rms_norm(h, p["operator_norm"], eps, dtype)
    h = h + (linear_attention if op == "linear_attention" else latent_attention)(
        u, p, cfg, dtype, act)
    u = rms_norm(h, p["ffn_norm"], eps, dtype)
    if ffn == "dense":
        outputs = cfg.get("router_outputs", cfg["num_experts"])
        return h + swiglu(u, p["w1"], p["w3"], p["w2"], dtype, act), jnp.zeros((outputs,))
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
    """The program's parameter tree (``models/ling_hybrid.LingHybridQ``)
    holding these weights: experts' W_1 and W_3 side by side as ``w13``, a
    run of layers of one kind stacked; the router, its bias, ``A_log`` and
    ``dt_bias`` float32 in every copy."""
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    p = {"w_tok": cast(weights["w_tok"]), "final_norm": {"weight": cast(weights["final_norm"])}}
    for name, flax in {**_FLAX_STEM, **_FLAX_HEAD}.items():
        p[flax] = {"kernel": cast(weights[name]["w"]), "bias": cast(weights[name]["b"])}
    layers = []
    for i, (op, ffn) in enumerate(layer_kinds(cfg)):
        w = weights[f"layer_{i}"]
        out = {"operator_norm": {"weight": cast(w["operator_norm"])},
               "ffn_norm": {"weight": cast(w["ffn_norm"])},
               op: {n: w[n].astype(jnp.float32) if n in FLOAT32_ALWAYS else cast(w[n])
                    for n in (_LINEAR if op == "linear_attention" else _LATENT)}}
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
               **{n: f32(q[op][n]) for n in (_LINEAR if op == "linear_attention" else _LATENT)}}
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

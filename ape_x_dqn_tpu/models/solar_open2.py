"""Solar-Open2-250B's block (upstage/Solar-Open2-250B, ``config.json``,
``model_type`` ``solar_open2``) as a Q-network's torso over a history of
frames: its two mixers and the spec made from the published keys.  Three
layers of four are gated delta-rule linear attention (Kimi Delta Attention,
arXiv:2510.26692, as ``kda_*`` and ``linear_attn_config`` name it): q, k and
v each through a causal depthwise convolution of ``short_conv_kernel_size``
taps and a SiLU, q and k L2-normalised a head, a log decay per key channel
from a low-rank projection (``kda_use_full_proj`` false), a write strength
``beta`` up to 2 (``kda_allow_neg_eigval``), the recurrence in chunks
(``ops/chunked_delta.py``), an RMSNorm over a head's 128 under a low-rank
sigmoid gate, the output projection.  The layers ``gqa_layers`` names are
grouped-query causal softmax attention with no positional rule (``use_rope``
false) in blocked kernels (``ops/pallas/blocked_attention.py``), a sigmoid
gate per element on the output (``use_gqa_gate``).  Every layer's FFN routes
over ``n_routed_experts`` sigmoid scores with a balancing bias and adds
``n_shared_experts`` shared ones ungated.  The expert layer, the block and
the Q-network around them are ``models/expert_torso.py``'s.

Both mixers divide by heads (``TorsoSpec.heads_held``): one chip of a
tensor-parallel group holds the heads ``[lo, hi)`` of the published 64, with
``W_q``, ``W_k``, ``W_v``, the convolutions, ``W_f2``, ``A_log``,
``dt_bias``, ``W_b``, ``W_g2`` and ``W_g`` by columns and ``W_o`` by rows
(``W_f1``, ``W_g1`` and the head norm alike on every chip), the softmax
layer with the key-value heads its query heads read, and returns the held
heads' part of ``W_o``'s sum.

The convolution and the gated norm are plain XLA here and not
``ops/pallas/scan_layout.py``'s kernels: those write the state-space scan's
layout (a chunk's tokens in the lanes, for a head of 64) and norm over all
4,096 channels under a SiLU gate, where this layer's heads of 128 fill the
lanes as they lie and its norm is over one head under a sigmoid.  The
softmax mixer stands apart from ``granite_hybrid.NopeAttention`` (no gate,
every head, an ``attention_multiplier``) and ``laguna_moe.GatedAttention`` (a
RoPE rule, a gate per head, a window): what is left in common is four
projections into ``blocked_attention``.

``A_log`` and ``dt_bias`` stay float32 in a target network of a lower type
(the spec's ``float32_leaves``), as the state-space layers'.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ape_x_dqn_tpu.models.expert_torso import TorsoQ, TorsoSpec, _bias_init, _lecun
from ape_x_dqn_tpu.models.granite_hybrid import _a_log_init, _dt_bias_init
from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta
from ape_x_dqn_tpu.ops.chunked_scan import chunks_of
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.utils.profiling import part

CHUNK = 64            # assumed: the published config names no chunk
L2_EPS = 1e-6         # assumed: under the square root of q's and k's norms
LAYER_TYPES = ("linear_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class LinearSizes:
    """``linear_attn_config`` and the ``kda_*`` keys."""

    heads: int                        # published, before any share
    head_dim: int
    conv: int                         # the convolutions' taps
    gate_rank: Optional[int]          # of W_f1, W_g1 (kda_use_full_proj false); None: W_f, W_g
    #                                   full rank, the output gate without its bias
    beta_scale: float                 # 2 with kda_allow_neg_eigval
    chunk: int = CHUNK
    gate: str = "softplus"            # the log decay's rule, one of GATES
    gate_bound: float = 0.0           # ``bounded``: the log decay lies in (gate_bound, 0)


# The log decay a key channel, from f = W_f u + dt_bias and a = exp(A_log):
# ``softplus``: -a softplus(f);  ``bounded``: gate_bound sigmoid(a f)
# (``kda_safe_gate`` with ``kda_lower_bound``).
GATES = ("softplus", "bounded")


def held_heads(spec: TorsoSpec, heads: int) -> tuple:
    """[lo, hi) of a layer's ``heads`` that this chip holds."""
    lo, hi = spec.heads_held or (0, heads)
    if hi > heads:
        raise ValueError(f"heads_held {spec.heads_held} is no range of {heads} heads")
    return lo, hi


def _heads_of(u, w, n: int):
    """[B, T, d] x [d, n x k] -> [B, n, T, k]."""
    return jnp.einsum("btd,dnk->bntk", u, w.astype(u.dtype).reshape(w.shape[0], n, -1))


def _short_conv(x, kernel):
    """silu of the causal depthwise convolution over the tokens of ``x`` [B,
    n, T, k], zeros before t = 0; ``kernel`` [n x k, taps], the last tap the
    token's own; sums in float32."""
    taps, t = kernel.shape[-1], x.shape[2]
    w = kernel.astype(jnp.float32).reshape(x.shape[1], x.shape[3], taps)   # [n, k, taps]
    padded = jnp.pad(x, ((0, 0), (0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    out = sum(padded[:, :, j:j + t] * w[None, :, None, :, j] for j in range(taps))
    return jax.nn.silu(out)


def _l2(x, scale: float = 1.0):
    return x * (jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS) * scale)


class DeltaAttention(nn.Module):
    """``W_o (norm(delta(conv q, conv k, conv v, g, beta)) sigmoid(gate))``:
    module docstring."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype
    divides_heads = True

    @nn.compact
    def __call__(self, u):
        sp, cd, pd, f32 = self.spec, self.compute_dtype, self.param_dtype, jnp.float32
        m: LinearSizes = sp.arg("linear")
        lo, hi = held_heads(sp, m.heads)
        d, n, hd, r = sp.hidden_size, hi - lo, m.head_dim, m.gate_rank
        w = {name: self.param(name, _lecun(), (d, n * hd), pd) for name in ("w_q", "w_k", "w_v")}
        conv = {name: self.param(name, _lecun(-1), (n * hd, m.conv), pd)
                for name in ("conv_q", "conv_k", "conv_v")}
        if m.gate not in GATES:
            raise ValueError(f"unknown gate rule {m.gate!r}; {GATES}")
        if r is None:
            w_f = self.param("w_f", _lecun(), (d, n * hd), pd)
        else:
            w_f1 = self.param("w_f1", _lecun(), (d, r), pd)
            w_f2 = self.param("w_f2", _lecun(), (r, n * hd), pd)
        a_log = self.param("A_log", _a_log_init, (n,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (n * hd,), f32)
        w_b = self.param("w_b", _lecun(), (d, n), pd)
        if r is None:
            w_g, b_g = self.param("w_g", _lecun(), (d, n * hd), pd), None
        else:
            w_g1 = self.param("w_g1", _lecun(), (d, r), pd)
            w_g2 = self.param("w_g2", _lecun(), (r, n * hd), pd)
            b_g = self.param("b_g", _bias_init, (n * hd,), pd)
        norm = self.param("norm", nn.initializers.ones, (hd,), pd)
        w_o = self.param("w_o", _lecun(), (n * hd, d), pd)

        # Between the projections and the scan everything is float32 a head
        # and token: computed again in the backward pass from what the
        # projections wrote, in the compute type.
        @jax.checkpoint
        def operands(raw, f, b, kernels, a_log, dt_bias):
            q, k, v = (_short_conv(x, kernel) for x, kernel in zip(raw, kernels))
            q, k, v = _l2(q, 1.0 / math.sqrt(hd)).astype(cd), _l2(k).astype(cd), v.astype(cd)
            if m.gate == "softplus":
                g = -jnp.exp(a_log)[:, None, None] * jax.nn.softplus(
                    f.astype(f32) + dt_bias.reshape(n, 1, hd))
            else:
                g = m.gate_bound * jax.nn.sigmoid(
                    jnp.exp(a_log)[:, None, None] * (f.astype(f32) + dt_bias.reshape(n, 1, hd)))
            return q, k, v, g, m.beta_scale * jax.nn.sigmoid(b.astype(f32))

        @jax.checkpoint
        def gated(o, z, b_g, norm):
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + sp.norm_eps)
            z = z.astype(f32)
            gate = jax.nn.sigmoid(z if b_g is None else z + b_g.astype(f32).reshape(n, 1, hd))
            return (o * norm.astype(f32) * gate).astype(cd)

        # And the scan between them keeps each chunk's incoming state and its
        # cut operands (0.5 GB a layer at the cell's shapes), which would
        # stand through the expert layer's backward pass: the block's
        # backward pass keeps the projections' outputs alone and walks the
        # chunks forward once more when it reaches the mixer.
        @jax.checkpoint
        def mixed(raw, f, b, z, kernels, a_log, dt_bias, b_g, norm):
            q, k, v, g, beta = operands(raw, f, b, kernels, a_log, dt_bias)
            return gated(chunked_delta(q, k, v, g, beta, m.chunk), z, b_g, norm)

        y = mixed(tuple(_heads_of(u, w["w_" + x], n) for x in "qkv"),
                  _heads_of(u, w_f, n) if r is None else _heads_of(u @ w_f1.astype(cd), w_f2, n),
                  jnp.einsum("btd,dn->bnt", u, w_b.astype(cd)),
                  _heads_of(u, w_g, n) if r is None else _heads_of(u @ w_g1.astype(cd), w_g2, n),
                  tuple(conv["conv_" + x] for x in "qkv"), a_log, dt_bias, b_g, norm)
        return jnp.einsum("bntk,nkd->btd", y, w_o.astype(cd).reshape(n, hd, d))

    @staticmethod
    def delta_count(spec: TorsoSpec, op: str, rows: int, tokens: int) -> dict:
        """One layer's forward over ``rows`` sequences of ``tokens``: the
        chunks the recurrence walks, the tokens it walks them over and those
        that are the sequences' own (a sequence's, whatever heads are held)."""
        chunks, padded = chunks_of(tokens, spec.arg("linear").chunk)
        return {"chunks": float(rows * chunks), "tokens_padded": float(rows * padded),
                "tokens": float(rows * tokens)}


class GatedNopeAttention(nn.Module):
    """Grouped-query causal attention over the held heads, no positional
    rule, no bias: scores over the square root of the head, a sigmoid gate
    per element of the output, before ``W_o``."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype
    divides_heads = True

    @staticmethod
    def held(spec: TorsoSpec) -> tuple:
        """(query heads held, key-value heads held: those the held query
        heads read).  The held heads are whole groups, or lie inside one (a
        key-value head that several chips of the tensor-parallel group hold)."""
        heads, kv = spec.arg("num_attention_heads"), spec.arg("num_key_value_heads")
        lo, hi = held_heads(spec, heads)
        group = heads // kv
        first, last = lo // group, (hi - 1) // group
        if first != last and (lo % group or hi % group):
            raise ValueError(f"heads_held {(lo, hi)} cuts a group of {group} query heads")
        return hi - lo, last - first + 1

    @nn.compact
    def __call__(self, u):
        sp, cd, pd = self.spec, self.compute_dtype, self.param_dtype
        d, hd = sp.hidden_size, sp.arg("head_dim")
        h, kv = self.held(sp)
        wq = self.param("w_q", _lecun(), (d, h * hd), pd)
        wk = self.param("w_k", _lecun(), (d, kv * hd), pd)
        wv = self.param("w_v", _lecun(), (d, kv * hd), pd)
        wo = self.param("w_o", _lecun(), (h * hd, d), pd)
        q = (_heads_of(u, wq, h).astype(jnp.float32) / math.sqrt(hd)).astype(cd)
        with part("attn_full"):
            a = blocked.blocked_attention(q, _heads_of(u, wk, kv), _heads_of(u, wv, kv))
        if sp.arg("use_gqa_gate"):
            wg = self.param("w_g", _lecun(), (d, h * hd), pd)
            a = a * jax.nn.sigmoid(_heads_of(u, wg, h).astype(jnp.float32)).astype(cd)
        return jnp.einsum("bntk,nkd->btd", a, wo.astype(cd).reshape(h, hd, d))

    @staticmethod
    def count(spec: TorsoSpec, op: str, rows: int, tokens: int) -> dict:
        """One layer's forward over ``rows`` sequences of ``tokens``, under
        ``laguna_moe.GatedAttention.count``'s names for a causal layer, over
        the heads held."""
        heads, kv = GatedNopeAttention.held(spec)
        group = heads // kv
        visited, total = blocked.blocks_visited(tokens, None, group)
        return {"pairs_in_mask_full": float(rows * blocked.pairs_in_mask(tokens, None)),
                "pairs_computed_full": float(rows * blocked.pairs_computed(tokens, None, group)),
                "blocks_visited_full": float(rows * heads * visited),
                "blocks_total_full": float(rows * heads * total)}


MIXERS = {"linear_attention": DeltaAttention, "full_attention": GatedNopeAttention}


def layer_types(cfg: Mapping) -> list:
    """The published layer pattern: ``full_attention`` on ``gqa_layers``,
    ``linear_attention`` elsewhere, over the published depth."""
    depth = int(cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"]))
    gqa = set(cfg["gqa_layers"])
    return [LAYER_TYPES[i in gqa] for i in range(depth)]


def spec_from_config(cfg: Mapping) -> TorsoSpec:
    """A ``TorsoSpec`` from the published ``config.json``'s keys, plus what a
    cut states: ``layers_held`` (indices into the published pattern, default
    the first ``num_hidden_layers``), ``router_outputs`` and ``experts_held``
    (default every one of ``n_routed_experts``), ``heads_held`` (default every
    head), and the published counts under ``published`` where a key holds
    the cut's (``num_attention_heads`` then counts the heads held, as
    ``n_routed_experts`` the experts).  A ``layer_types`` key, if the file carries one, must be the
    pattern ``gqa_layers`` gives.  Assumed, as the benchmark's configuration
    file says: a sigmoid router with a balancing bias, the shared expert
    ungated, the attention gate per element, the gates' rank, the chunk."""
    published = cfg.get("published", {})
    types = layer_types(cfg)
    if list(cfg.get("layer_types", types)) != types:
        raise ValueError("layer_types disagrees with gqa_layers")
    held = list(cfg.get("layers_held", range(int(cfg["num_hidden_layers"]))))
    linear = cfg["linear_attn_config"]
    heads = int(published.get("num_attention_heads", cfg["num_attention_heads"]))
    kv = int(published.get("num_key_value_heads", cfg["num_key_value_heads"]))
    linear_heads = int(published.get("linear_attn_config", linear)["num_heads"])
    outputs = int(cfg.get("router_outputs", published.get(
        "n_routed_experts", cfg["n_routed_experts"])))
    if cfg.get("use_rope") or cfg.get("kda_use_full_proj") or int(cfg.get("first_k_dense_replace", 0)):
        raise ValueError("this family's spec: use_rope false, kda_use_full_proj false, no leading "
                         "dense layer (models/ling_hybrid.py builds full-rank gates and a "
                         "leading dense layer under a linear mixer)")
    if linear.get("num_kv_heads") not in (None, linear_heads) or linear_heads != heads or heads % kv:
        raise ValueError("the linear layers' keys and values have a head each, the two layer "
                         f"kinds one head count: {linear_heads}, {heads} over {kv}")
    share = tuple(cfg["heads_held"]) if cfg.get("heads_held") else None
    if share and "num_attention_heads" in published and (
            share[1] - share[0] != int(cfg["num_attention_heads"])):
        raise ValueError(f"heads_held {share} is not the {cfg['num_attention_heads']} heads "
                         "num_attention_heads counts")
    ops = sorted({types[i] for i in held})
    sizes = LinearSizes(heads=linear_heads, head_dim=int(linear["head_dim"]),
                        conv=int(linear["short_conv_kernel_size"]),
                        gate_rank=int(cfg.get("kda_gate_rank", linear["head_dim"])),
                        beta_scale=2.0 if cfg.get("kda_allow_neg_eigval") else 1.0,
                        chunk=int(cfg.get("kda_chunk_size", CHUNK)))
    return TorsoSpec(
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        router_outputs=outputs,
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        experts_held=tuple(cfg.get("experts_held", (0, outputs))),
        layers=tuple((types[i], "moe") for i in held),
        mixers=tuple((op, MIXERS[op]) for op in ops),
        mixer_args=(("linear", sizes), ("num_attention_heads", heads),
                    ("num_key_value_heads", kv), ("head_dim", int(cfg["head_dim"])),
                    ("use_gqa_gate", bool(cfg.get("use_gqa_gate", False)))),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
        gate_norm_eps=0.0,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        use_expert_bias=True,
        score_function="sigmoid",
        shared_expert_intermediate_size=(int(cfg.get("n_shared_experts", 0))
                                         * int(cfg["moe_intermediate_size"])),
        frame_history=True,
        float32_leaves=("A_log", "dt_bias"),
        heads_held=share,
    )


class SolarOpen2Q(TorsoQ):
    """Stem, a frame at a time -> a history's tokens -> Solar-Open2 layers ->
    norm, mean over tokens -> dueling head."""

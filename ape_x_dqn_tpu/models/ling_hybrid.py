"""Ling-3.0-flash's block (inclusionAI/Ling-3.0-flash, ``config.json``,
``model_type`` ``bailing_hybrid``) as a Q-network's torso over a history of
frames: the spec made from the published keys, and the latent-attention mixer
(``LatentAttention``), which this family shares with ``models/kanana_moe.py``
(``deepseek_v3``: the same mixer in every layer, without the head gate).
Five layers of six (``layer_group_size``) are gated delta-rule linear
attention, ``solar_open2.DeltaAttention`` told this model's gate: full-rank
``W_f`` and ``W_g`` (``no_kda_lora``), a log decay bounded below
(``kda_safe_gate``: ``g = kda_lower_bound sigmoid(exp(A_log) (W_f u +
dt_bias))``, in (-5, 0)), a write strength up to 1.  Every sixth layer is
multi-head latent attention (``LatentAttention``): keys and values expanded
from one ``kv_lora_rank``-wide latent a token, a query and key of
``qk_nope_head_dim`` a head with no positional rule beside a rotary part of
``qk_rope_head_dim`` whose key is one for every head, values of
``v_head_dim``, a sigmoid gate a head (``LatentSizes.gated``: this family's;
``deepseek_v3`` has none).  The first ``first_k_dense_replace``
layers carry the dense SwiGLU, every other layer routes over ``num_experts``
sigmoid scores with a balancing bias, ``n_group`` groups of which a token
keeps ``topk_group`` before it chooses (``expert_torso.route``), and adds a
shared expert ungated.  The expert layer, the block and the Q-network around
them are ``models/expert_torso.py``'s.

Both mixers divide by heads (``TorsoSpec.heads_held``): of the latent layer
one chip of a tensor-parallel group holds ``W_q``, ``W_ukv`` and the head
gate by columns and ``W_o`` by rows; the down-projection ``W_dkv`` and the
latent's norm are alike on every chip of the group, which therefore all
compute the same latent and the same shared rotary key.

The learner has no cache, so the latent is expanded to a key and a value a
head (the non-absorbed form).  The two parts of a score are two products in
one kernel (``blocked_attention``'s shared operands): the rotary key crosses
HBM once, [B, 1, T, 64], and is not laid out a head at a time, and no head is
padded from 192 to 256.  RoPE turns the pairs ``(2j, 2j + 1)``
(``rope_interleave``) by the token's index in the time-major order.

It stands beside ``laguna_moe.GatedAttention`` (grouped keys, a window, RoPE
in halves over the whole head), ``granite_hybrid.NopeAttention`` and
``solar_open2.GatedNopeAttention``: what the four have in common is the call
into ``blocked_attention``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping

import jax
import jax.numpy as jnp
from flax import linen as nn

from ape_x_dqn_tpu.models.expert_torso import TorsoQ, TorsoSpec, _lecun
from ape_x_dqn_tpu.models.solar_open2 import (
    CHUNK, DeltaAttention, LinearSizes, _heads_of, held_heads,
)
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.utils.profiling import part

LAYER_TYPES = ("linear_attention", "latent_attention")


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """``kv_lora_rank``, the ``qk_*`` and ``v_head_dim`` keys, ``rope_theta``."""

    heads: int                        # published, before any share
    kv_rank: int                      # the latent's width
    nope: int                         # a head's query and key without a positional rule
    rope: int                         # the rotary part: a query a head, one key for all
    v: int                            # a head's value
    theta: float
    gated: bool = True                # a head's output times sigmoid(w_g . u) (Ling); False: no gate


def rope_pairs(x, theta: float, scale: float = 1.0):
    """Rotary embedding of ``x`` [B, n, T, R], positions 0..T-1, every
    dimension rotating, in the pairs ``(2j, 2j + 1)`` at ``theta^(-2j / R)``;
    ``scale`` multiplies before the one rounding to ``x``'s type.  ``x cos +
    partner(x) sin``, the partner taken by a product with a fixed 0/+-1
    matrix (``laguna_moe.rope``'s way, exact in any type)."""
    r = x.shape[-1]
    j = jnp.arange(r // 2)
    ang = (jnp.arange(x.shape[2], dtype=jnp.float32)[:, None]
           * theta ** (-2.0 * j.astype(jnp.float32) / r)[None, :])
    cos, sin = jnp.repeat(jnp.cos(ang), 2, axis=-1), jnp.repeat(jnp.sin(ang), 2, axis=-1)
    partner = (jnp.zeros((r, r), x.dtype).at[2 * j + 1, 2 * j].set(-1)   # column 2j takes -x[2j + 1]
               .at[2 * j, 2 * j + 1].set(1))                            # column 2j + 1 takes x[2j]
    turned = jnp.einsum("bntr,re->bnte", x, partner, precision=jax.lax.Precision.HIGHEST)
    out = x.astype(jnp.float32) * cos + turned.astype(jnp.float32) * sin
    return (out * scale).astype(x.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention over the held heads, causal: module
    docstring.  Two families use it: ``ling_hybrid`` (one layer in six, a
    share of the heads, a sigmoid gate a head) and ``kanana_moe`` (every
    layer, every head, no gate: ``LatentSizes.gated`` false, and the module
    has no ``w_g``)."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype
    divides_heads = True

    @nn.compact
    def __call__(self, u):
        sp, cd, pd, f32 = self.spec, self.compute_dtype, self.param_dtype, jnp.float32
        m: LatentSizes = sp.arg("latent")
        lo, hi = held_heads(sp, m.heads)
        d, h = sp.hidden_size, hi - lo
        w_q = self.param("w_q", _lecun(), (d, h * (m.nope + m.rope)), pd)
        w_dkv = self.param("w_dkv", _lecun(), (d, m.kv_rank + m.rope), pd)
        kv_norm = self.param("kv_norm", nn.initializers.ones, (m.kv_rank,), pd)
        w_ukv = self.param("w_ukv", _lecun(), (m.kv_rank, h * (m.nope + m.v)), pd)
        w_g = self.param("w_g", _lecun(), (d, h), pd) if m.gated else None
        w_o = self.param("w_o", _lecun(), (h * m.v, d), pd)
        # a head's columns are [nope; rope] of W_q and [nope; value] of W_ukv:
        # the weights are cut, no activation is sliced along the lanes
        w_q = w_q.reshape(d, h, m.nope + m.rope)
        w_ukv = w_ukv.reshape(m.kv_rank, h, m.nope + m.v)
        scale = 1.0 / math.sqrt(m.nope + m.rope)
        q = (_heads_of(u, w_q[..., :m.nope], h).astype(f32) * scale).astype(cd)
        q_rope = rope_pairs(_heads_of(u, w_q[..., m.nope:], h), m.theta, scale)
        c = (u @ w_dkv[:, :m.kv_rank].astype(cd)).astype(f32)
        c = c * jax.lax.rsqrt(jnp.mean(jnp.square(c), -1, keepdims=True) + sp.norm_eps)
        c = (c * kv_norm.astype(f32)).astype(cd)
        k_rope = rope_pairs((u @ w_dkv[:, m.kv_rank:].astype(cd))[:, None], m.theta)
        k, v = _heads_of(c, w_ukv[..., :m.nope], h), _heads_of(c, w_ukv[..., m.nope:], h)
        with part("attn_latent"):
            a = blocked.blocked_attention(q, k, v, q_shared=q_rope, k_shared=k_rope)
        if m.gated:
            gate = jax.nn.sigmoid(jnp.einsum("btd,dn->bnt", u, w_g.astype(cd)).astype(f32))
            a = a * gate[..., None].astype(cd)
        return jnp.einsum("bntk,nkd->btd", a, w_o.astype(cd).reshape(h, m.v, d))

    @staticmethod
    def count(spec: TorsoSpec, op: str, rows: int, tokens: int) -> dict:
        """One layer's forward over ``rows`` sequences of ``tokens``, under
        ``laguna_moe.GatedAttention.count``'s names for the kind ``latent``,
        over the heads held; every head has keys of its own, a group of 1."""
        visited, total = blocked.blocks_visited(tokens, None, 1)
        lo, hi = held_heads(spec, spec.arg("latent").heads)
        return {"pairs_in_mask_latent": float(rows * blocked.pairs_in_mask(tokens, None)),
                "pairs_computed_latent": float(rows * blocked.pairs_computed(tokens, None, 1)),
                "blocks_visited_latent": float(rows * (hi - lo) * visited),
                "blocks_total_latent": float(rows * (hi - lo) * total)}


MIXERS = {"linear_attention": DeltaAttention, "latent_attention": LatentAttention}


def layer_types(cfg: Mapping) -> list:
    """The published layer pattern over the published depth: latent attention
    where ``(i + 1) % layer_group_size == 0``, the delta rule elsewhere."""
    depth = int(cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"]))
    period = int(cfg["layer_group_size"])
    return [LAYER_TYPES[(i + 1) % period == 0] for i in range(depth)]


def spec_from_config(cfg: Mapping) -> TorsoSpec:
    """A ``TorsoSpec`` from the published ``config.json``'s keys, plus what a
    cut states: ``layers_held`` (indices into the published pattern, default
    the first ``num_hidden_layers``), ``router_outputs`` and ``experts_held``
    (default every one of ``num_experts``), ``heads_held`` (default every
    head), and the published counts under ``published`` where a key holds
    the cut's (``first_k_dense_replace`` then counts the dense layers held:
    the layers before the published count are the dense ones).  A
    ``layer_types`` key, if the file carries one, must be the pattern
    ``layer_group_size`` gives.  A held layer whose entry of
    ``expert_swiglu_limit_list`` or ``share_expert_swiglu_limit_list`` is not
    0 is refused: no clamp is guessed.  Assumed, as the benchmark's
    configuration file says: the bounded gate's form, the group's score, the
    ungated shared expert, the bias rule, the latent's norm as all of
    ``use_qk_norm``, the chunk."""
    published = cfg.get("published", {})
    types = layer_types(cfg)
    if list(cfg.get("layer_types", types)) != types:
        raise ValueError("layer_types disagrees with layer_group_size")
    held = list(cfg.get("layers_held", range(int(cfg["num_hidden_layers"]))))
    heads = int(published.get("num_attention_heads", cfg["num_attention_heads"]))
    dense = int(published.get("first_k_dense_replace", cfg.get("first_k_dense_replace", 0)))
    outputs = int(cfg.get("router_outputs", published.get("num_experts", cfg["num_experts"])))
    if not (cfg.get("no_kda_lora") and cfg.get("kda_safe_gate")) or cfg.get("q_lora_rank") \
            or int(cfg.get("num_kv_heads_for_linear_attn") or 0) \
            or cfg.get("score_function", "sigmoid") != "sigmoid":
        raise ValueError("this family's spec: no_kda_lora and kda_safe_gate true, q_lora_rank "
                         "null, a key and a value head a query head in the linear layers, "
                         "sigmoid scores")
    for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        clamped = [i for i in held if i < len(cfg.get(name, ())) and cfg[name][i]]
        if clamped:
            raise ValueError(f"{name} is not 0 on the held layers {clamped}: no clamp is built")
    share = tuple(cfg["heads_held"]) if cfg.get("heads_held") else None
    if share and "num_attention_heads" in published and (
            share[1] - share[0] != int(cfg["num_attention_heads"])):
        raise ValueError(f"heads_held {share} is not the {cfg['num_attention_heads']} heads "
                         "num_attention_heads counts")
    ops = sorted({types[i] for i in held})
    linear = LinearSizes(heads=heads, head_dim=int(cfg["head_dim"]),
                         conv=int(cfg["short_conv_kernel_size"]), gate_rank=None,
                         beta_scale=1.0, chunk=int(cfg.get("kda_chunk_size", CHUNK)),
                         gate="bounded", gate_bound=float(cfg["kda_lower_bound"]))
    latent = LatentSizes(heads=heads, kv_rank=int(cfg["kv_lora_rank"]),
                         nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
                         v=int(cfg["v_head_dim"]), theta=float(cfg["rope_theta"]))
    return TorsoSpec(
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        router_outputs=outputs,
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        experts_held=tuple(cfg.get("experts_held", (0, outputs))),
        layers=tuple((types[i], "dense" if i < dense else "moe") for i in held),
        mixers=tuple((op, MIXERS[op]) for op in ops),
        mixer_args=(("linear", linear), ("latent", latent)),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
        gate_norm_eps=0.0,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        use_expert_bias=bool(cfg.get("moe_router_enable_expert_bias", True)),
        score_function="sigmoid",
        shared_expert_intermediate_size=(int(cfg.get("num_shared_experts", 0))
                                         * int(cfg["moe_shared_expert_intermediate_size"])),
        frame_history=True,
        float32_leaves=("A_log", "dt_bias"),
        heads_held=share,
        router_groups=int(cfg.get("n_group", 1)),
        router_groups_kept=int(cfg.get("topk_group", 1)),
    )


class LingHybridQ(TorsoQ):
    """Stem, a frame at a time -> a history's tokens -> Ling-3.0 layers ->
    norm, mean over tokens -> dueling head."""

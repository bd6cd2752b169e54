"""LFM2-MoE's block (LiquidAI/LFM2-24B-A2B, ``config.json``) as a Q-network's
torso: its two mixers, a gated short convolution and grouped-query attention
with q/k norms and RoPE over one frame's 49 positions, and the spec made from
the published keys (a sigmoid router with a balancing bias, 4 of 64 experts a
token, no shared expert).  The expert layer, the block, the walk over the
held pairs and the Q-network around them are ``models/expert_torso.py``'s,
shared with every torso of blocks."""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
from flax import linen as nn

from ape_x_dqn_tpu.models.expert_torso import (  # noqa: F401  (this kind's public names)
    BIAS_UPDATE_RATE, KERNEL_ROWS, Block, ExpertShare, RMSNorm, SwiGLU, TorsoQ, TorsoSpec,
    _lecun, cut_from_config, held_experts, layer_runs, route, tile_rows,
)


def spec_from_config(cfg: Mapping) -> TorsoSpec:
    """A ``TorsoSpec`` from the published ``config.json``'s keys, plus what a
    cut states (``expert_torso.cut_from_config``)."""
    types = list(cfg["layer_types"])
    held, outputs, experts = cut_from_config(cfg)
    dense = int(cfg["num_dense_layers"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    if heads % kv:
        raise ValueError("num_attention_heads must divide by num_key_value_heads")
    return TorsoSpec(
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        norm_eps=float(cfg["norm_eps"]),
        router_outputs=outputs,
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        experts_held=experts,
        layers=tuple((types[i], "dense" if i < dense else "moe") for i in held),
        mixers=(("conv", ShortConv), ("full_attention", Attention)),
        mixer_args=(
            ("num_attention_heads", heads), ("num_key_value_heads", kv),
            ("head_dim", int(cfg.get("head_dim") or cfg["hidden_size"] // heads)),
            ("conv_L_cache", int(cfg["conv_L_cache"])),
            ("rope_theta", float(cfg["rope_parameters"]["rope_theta"]))),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        use_expert_bias=bool(cfg.get("use_expert_bias", True)),
    )


def rope(x, theta: float):
    """Rotary embedding over the whole head, positions 0..S-1; ``x`` is
    [B, S, H, D], rotated in halves as ``transformers`` does."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


class ShortConv(nn.Module):
    """``W_out (c * conv(b * x))``: a gated depthwise causal convolution."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        d, k = self.spec.hidden_size, self.spec.arg("conv_L_cache")
        w_in = self.param("w_in", _lecun(), (d, 3 * d), self.param_dtype)
        kernel = self.param("kernel", _lecun(-1), (d, k), self.param_dtype)
        w_out = self.param("w_out", _lecun(), (d, d), self.param_dtype)
        cd = self.compute_dtype
        b, c, x = jnp.split(u @ w_in.astype(cd), 3, axis=-1)
        z = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))  # zero left padding: causal
        s = sum(z[:, j:j + u.shape[1], :] * kernel[:, j].astype(cd) for j in range(k))
        return (c * s) @ w_out.astype(cd)


class Attention(nn.Module):
    """Grouped-query causal attention, q/k RMSNorm per head, RoPE."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        sp, cd = self.spec, self.compute_dtype
        d, theta = sp.hidden_size, sp.arg("rope_theta")
        h, kv, hd = (sp.arg(n) for n in ("num_attention_heads", "num_key_value_heads", "head_dim"))
        wq = self.param("w_q", _lecun(), (d, h * hd), self.param_dtype)
        wk = self.param("w_k", _lecun(), (d, kv * hd), self.param_dtype)
        wv = self.param("w_v", _lecun(), (d, kv * hd), self.param_dtype)
        wo = self.param("w_o", _lecun(), (h * hd, d), self.param_dtype)
        bsz, s, _ = u.shape
        norm = lambda name: RMSNorm(sp.norm_eps, cd, self.param_dtype, name=name)  # noqa: E731
        q = norm("q_norm")((u @ wq.astype(cd)).reshape(bsz, s, h, hd))
        k = norm("k_norm")((u @ wk.astype(cd)).reshape(bsz, s, kv, hd))
        v = (u @ wv.astype(cd)).reshape(bsz, s, kv, hd)
        q, k = rope(q, theta), rope(k, theta)
        q = q.reshape(bsz, s, kv, h // kv, hd)  # each key-value head serves h/kv query heads
        scores = jnp.einsum("bsgrd,btgd->bgrst", q, k,
                            preferred_element_type=jnp.float32) / jnp.sqrt(float(hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1).astype(cd)
        out = jnp.einsum("bgrst,btgd->bsgrd", probs, v).reshape(bsz, s, h * hd)
        return out @ wo.astype(cd)


class Lfm2MoeQ(TorsoQ):
    """Stem -> tokens -> LFM2-MoE layers -> norm, mean over tokens -> dueling head."""

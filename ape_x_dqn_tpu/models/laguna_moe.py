"""Laguna-S-2.1's block (poolside/Laguna-S-2.1, ``config.json``) as a
Q-network's torso over a history of frames: its one mixer, grouped-query
attention whose head count, RoPE rule and window are the layer type's
(``full_attention``: 48 heads, YaRN on half of each head, causal;
``sliding_attention``: 72 heads, plain RoPE, causal inside the last 512
keys), a sigmoid gate per head on its output, computed in blocks
(``ops/pallas/blocked_attention.py``); and the spec made from the published
keys (a softmax router with no bias, 10 of 256 experts a token scaled by
2.5, one shared expert).  The expert layer, the block, the walk over the
held pairs and the Q-network around them are ``models/expert_torso.py``'s.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ape_x_dqn_tpu.models.expert_torso import TorsoQ, TorsoSpec, _lecun, cut_from_config
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.utils.profiling import part


@dataclasses.dataclass(frozen=True)
class RopeRule:
    """One entry of the published ``rope_parameters``."""

    theta: float
    rotary_dim: int                   # the head's leading dimensions that rotate
    kind: str = "default"             # "default" | "yarn"
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def of(cls, params: Mapping, head_dim: int) -> "RopeRule":
        rule = cls(theta=float(params["rope_theta"]),
                   rotary_dim=int(head_dim * float(params.get("partial_rotary_factor", 1.0))),
                   kind=str(params.get("rope_type", "default")))
        if rule.kind == "default":
            return rule
        if rule.kind != "yarn":
            raise ValueError(f"unknown rope_type {rule.kind!r}")
        return dataclasses.replace(
            rule, factor=float(params["factor"]),
            original_max_position_embeddings=int(params["original_max_position_embeddings"]),
            beta_fast=float(params.get("beta_fast", 32)), beta_slow=float(params.get("beta_slow", 1)),
            attention_factor=float(params["attention_factor"]))


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """What a layer type tells the attention module."""

    heads: int
    window: Optional[int]             # None: causal alone
    rope: RopeRule

    @property
    def name(self) -> str:            # of its scope and its counters
        return "full" if self.window is None else "window"


def kind_of(spec: TorsoSpec, op: str) -> AttentionKind:
    return dict(spec.arg("attention"))[op]


def inverse_frequencies(rule: RopeRule):
    """float32 [rotary_dim / 2].  YaRN as transformers'
    ``_compute_yarn_parameters`` blends them: interpolated (divided by
    ``factor``) below the dimension ``beta_slow`` rotations give, as they are
    above the one ``beta_fast`` gives, a linear ramp between."""
    dim = rule.rotary_dim
    pos = rule.theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rule.kind == "default":
        return 1.0 / pos

    def correction_dim(rotations):
        return (dim * math.log(rule.original_max_position_embeddings / (rotations * 2 * math.pi))
                / (2 * math.log(rule.theta)))

    low = max(math.floor(correction_dim(rule.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rule.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return (1.0 / (rule.factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def rope(x, rule: RopeRule, scale: float = 1.0):
    """Rotary embedding of ``x`` [B, H, T, D], positions 0..T-1: the leading
    ``rotary_dim`` dimensions rotate in halves, as ``transformers`` does, with
    cos and sin times ``attention_factor``; the rest pass.  ``scale``
    multiplies the whole head before the one rounding to ``x``'s type.

    ``x cos + partner(x) sin`` over the whole head: the partner of a
    dimension (the other half's, signed; none past ``rotary_dim``) is taken
    by a product with a fixed 0/+-1 matrix, exact in any type, so that
    nothing is sliced along the lanes and the float32 arithmetic stays inside
    one fusion (sliced and concatenated, five float32 copies of ``x`` were
    live at once: 2.3 GB at 72 heads of a batch of 8 histories)."""
    d, rot = x.shape[-1], rule.rotary_dim
    half = rot // 2
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inverse_frequencies(rule)[None, :]
    still, af = jnp.zeros((x.shape[2], d - rot), jnp.float32), rule.attention_factor
    cos = jnp.concatenate([jnp.cos(ang) * af, jnp.cos(ang) * af, still + 1.0], -1)
    sin = jnp.concatenate([jnp.sin(ang) * af, jnp.sin(ang) * af, still], -1)
    i = jnp.arange(half)
    partner = (jnp.zeros((d, d), x.dtype).at[i + half, i].set(-1)   # column i takes -x[i + half]
               .at[i, i + half].set(1))                            # column i + half takes x[i]
    turned = jnp.einsum("bhtd,de->bhte", x, partner, precision=jax.lax.Precision.HIGHEST)
    out = x.astype(jnp.float32) * cos + turned.astype(jnp.float32) * sin
    return (out * scale).astype(x.dtype)


class GatedAttention(nn.Module):
    """Grouped-query attention of the layer type ``op``: RoPE by its rule,
    its mask, a sigmoid gate per head on the output, before ``W_o``."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        sp, cd = self.spec, self.compute_dtype
        kind = kind_of(sp, self.op)
        d, h, kv, hd = sp.hidden_size, kind.heads, sp.arg("num_key_value_heads"), sp.arg("head_dim")
        wq = self.param("w_q", _lecun(), (d, h * hd), self.param_dtype)
        wk = self.param("w_k", _lecun(), (d, kv * hd), self.param_dtype)
        wv = self.param("w_v", _lecun(), (d, kv * hd), self.param_dtype)
        wg = self.param("w_g", _lecun(), (d, h), self.param_dtype)
        wo = self.param("w_o", _lecun(), (h * hd, d), self.param_dtype)
        heads_of = lambda w, n: jnp.einsum(  # noqa: E731  [B, n, T, hd]
            "btd,dnk->bntk", u, w.astype(cd).reshape(d, n, hd))
        q = rope(heads_of(wq, h), kind.rope, scale=1.0 / math.sqrt(hd))
        k = rope(heads_of(wk, kv), kind.rope)
        with part("attn_" + kind.name):
            a = blocked.blocked_attention(q, k, heads_of(wv, kv), kind.window)
        gate = jax.nn.sigmoid(jnp.einsum("btd,dn->bnt", u, wg.astype(cd)).astype(jnp.float32))
        a = a * gate[..., None].astype(cd)
        return jnp.einsum("bntk,nkd->btd", a, wo.astype(cd).reshape(h, hd, d))

    @staticmethod
    def count(spec: TorsoSpec, op: str, rows: int, tokens: int) -> dict:
        """One layer's forward over ``rows`` sequences of ``tokens``: the
        pairs in the mask and the pairs the kernel computes a score for (a
        head's), and the blocks of the kernel's grid that it visits and that
        exist (a head's, times the heads)."""
        kind = kind_of(spec, op)
        group = kind.heads // spec.arg("num_key_value_heads")
        visited, total = blocked.blocks_visited(tokens, kind.window, group)
        return {f"pairs_in_mask_{kind.name}":
                float(rows * blocked.pairs_in_mask(tokens, kind.window)),
                f"pairs_computed_{kind.name}":
                float(rows * blocked.pairs_computed(tokens, kind.window, group)),
                f"blocks_visited_{kind.name}": float(rows * kind.heads * visited),
                f"blocks_total_{kind.name}": float(rows * kind.heads * total)}


def spec_from_config(cfg: Mapping) -> TorsoSpec:
    """A ``TorsoSpec`` from the published ``config.json``'s keys, plus what a
    cut states (``expert_torso.cut_from_config``).  Assumed, as the benchmark's
    configuration file says: a softmax router with no bias, the shared expert
    ungated, the head gate a projection of the layer's normed input."""
    types, ffns = list(cfg["layer_types"]), list(cfg["mlp_layer_types"])
    heads = list(cfg["num_attention_heads_per_layer"])
    held, outputs, experts = cut_from_config(cfg)
    hd, kv = int(cfg["head_dim"]), int(cfg["num_key_value_heads"])
    kinds = []
    for op in sorted({types[i] for i in held}):
        if op not in ("full_attention", "sliding_attention"):
            raise ValueError(f"unknown layer type {op!r}")
        counts = {heads[i] for i in range(len(types)) if types[i] == op}
        if len(counts) != 1 or next(iter(counts)) % kv:
            raise ValueError(f"{op}: head counts {sorted(counts)} over {kv} key-value heads")
        kinds.append((op, AttentionKind(
            heads=counts.pop(),
            window=int(cfg["sliding_window"]) if op == "sliding_attention" else None,
            rope=RopeRule.of(cfg["rope_parameters"][op], hd))))
    return TorsoSpec(
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        router_outputs=outputs,
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        experts_held=experts,
        layers=tuple((types[i], "dense" if ffns[i] == "dense" else "moe") for i in held),
        mixers=tuple((op, GatedAttention) for op, _ in kinds),
        mixer_args=(("attention", tuple(kinds)), ("num_key_value_heads", kv), ("head_dim", hd)),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
        gate_norm_eps=0.0,
        routed_scaling_factor=float(cfg.get("moe_routed_scaling_factor", 1.0)),
        use_expert_bias=False,
        score_function="softmax",
        shared_expert_intermediate_size=int(cfg.get("shared_expert_intermediate_size", 0)),
        frame_history=True,
    )


class LagunaMoeQ(TorsoQ):
    """Stem, a frame at a time -> a history's tokens -> Laguna layers ->
    norm, mean over tokens -> dueling head."""

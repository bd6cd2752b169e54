"""Granite 4.0-H's hybrid block (ibm-granite/granite-4.0-h-micro,
``config.json``, ``model_type`` ``granitemoehybrid``) as a Q-network's torso
over a history of frames: its two mixers and the spec made from the published
keys.  ``mamba`` layers are Mamba-2's (Dao & Gu 2024, arXiv:2405.21060): one
input projection into a gate, the scan's input with its ``B`` and ``C``, and
a step size per head; a causal depthwise convolution; the selective scan
(``ops/chunked_scan.py``, in chunks of ``mamba_chunk_size``); a gated
RMSNorm; the output projection.  Between the two projections an activation
crosses HBM once a pass, in the compute type: the scan reads ``x`` and
writes ``y`` as ``[chunks, B, H x P, chunk]``, a chunk's tokens in the lanes
(a head of 64 does not fill them), so the convolution writes that layout
and the gate and norm read it (``ops/pallas/scan_layout.py``).  This family
has one group (``mamba_n_groups`` 1: ``B`` and ``C`` are shared by all heads
and the norm is over all channels); the mixer itself is told its groups
(``MambaSizes.groups``: ``B`` and ``C`` a group of consecutive heads, the norm
over a group's channels) and the heads a chip holds of them
(``MambaSizes.held``, whole groups), which ``models/nemotron_h.py`` states.
``attention`` layers are grouped-query
causal attention with no positional rule (``position_embedding_type``
``nope``), scores scaled by ``attention_multiplier``, in blocked kernels
(``ops/pallas/blocked_attention.py``).  Every layer's FFN is the dense SwiGLU
(``shared_intermediate_size``; ``num_local_experts`` 0: no expert layer),
both branches of a block are scaled by ``residual_multiplier`` and the
projected tokens by ``embedding_multiplier``.  The block and the Q-network
around it are ``models/expert_torso.py``'s.

The attention module stands apart from ``laguna_moe.GatedAttention``: of
what that one is (a RoPE rule by layer type, a sigmoid gate per head, a
window, scores over the square root of the head) this layer has none, and
what is left in common is four projections into ``blocked_attention``.

A Mamba-2 layer's ``A_log``, ``dt_bias`` and ``D`` stay float32 in a target
network of a lower type (the spec's ``float32_leaves``): they decide
``exp(dt A)`` over every token of the history, and rounded to bfloat16 the
decay's exponent is off by up to 0.4%.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ape_x_dqn_tpu.models.expert_torso import (
    TorsoQ, TorsoSpec, _bias_init, _lecun, cut_from_config,
)
from ape_x_dqn_tpu.ops.chunked_scan import chunks_of, cut, scan_chunks
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.ops.pallas.scan_layout import conv_to_chunks, gated_norm
from ape_x_dqn_tpu.utils.profiling import part

# Mamba-2's published initialisation: the decay -A ~ U[1, 16], the step size
# softplus(dt_bias) log-uniform in [1e-3, 1e-1], the skip 1.
A_RANGE, DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)


@dataclasses.dataclass(frozen=True)
class MambaSizes:
    """The published ``mamba_*`` keys, and what a chip holds of the heads."""

    heads: int                        # published, before any share
    head_dim: int
    state: int
    conv: int                         # the convolution's taps
    chunk: int
    groups: int = 1                   # B, C and the norm's mean square: one a group of heads
    held: Optional[Tuple[int, int]] = None   # [lo, hi) of the heads, whole groups; None: all

    @property
    def inner(self) -> int:           # the scan's width, mamba_expand x hidden
        return self.heads * self.head_dim

    def __post_init__(self):
        lo, hi = self.held or (0, self.heads)
        per = self.heads // self.groups
        if self.heads % self.groups or not 0 <= lo < hi <= self.heads or lo % per or hi % per:
            raise ValueError(f"heads {(lo, hi)} of {self.heads} are no whole groups of {per}")

    @property
    def share(self) -> tuple:
        """(heads held, groups held)."""
        lo, hi = self.held or (0, self.heads)
        return hi - lo, (hi - lo) * self.groups // self.heads


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, *A_RANGE))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))          # softplus's inverse


class Mamba2(nn.Module):
    """``W_out norm(scan(conv(W_in u)) silu(z))``: module docstring.  It
    divides by heads: a chip holds the whole groups ``MambaSizes.held`` names,
    ``W_in``'s and the convolution's columns, ``A_log``, ``dt_bias``, ``D``
    and the norm's weight of those heads and ``W_out``'s rows, and returns
    their part of ``W_out``'s sum."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype
    divides_heads = True

    @nn.compact
    def __call__(self, u):
        sp, cd, pd, f32 = self.spec, self.compute_dtype, self.param_dtype, jnp.float32
        m: MambaSizes = sp.arg("mamba")
        heads, groups = m.share
        d, inner, n, k = sp.hidden_size, heads * m.head_dim, groups * m.state, m.conv
        mixed = inner + 2 * n                        # x | B | C: what the convolution sees
        w_in = self.param("w_in", _lecun(), (d, inner + mixed + heads), pd)
        kernel = self.param("conv_kernel", _lecun(-1), (mixed, k), pd)
        conv_bias = self.param("conv_bias", _bias_init, (mixed,), pd)
        a_log = self.param("A_log", _a_log_init, (heads,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), f32)
        skip = self.param("D", nn.initializers.ones, (heads,), f32)
        norm = self.param("norm", nn.initializers.ones, (inner,), pd)
        w_out = self.param("w_out", _lecun(), (inner, d), pd)

        # one parameter, three products: each output is made where it is used
        w_z, w_x, w_rest = jnp.split(w_in.astype(cd), (inner, 2 * inner), axis=1)
        bc, dt = jnp.split(u @ w_rest, (2 * n,), axis=-1)
        # the convolution and its SiLU write x, B, C as the scan reads them
        x = conv_to_chunks(u @ w_x, kernel[:inner], conv_bias[:inner], m.chunk, True)
        b, c = jnp.split(conv_to_chunks(bc, kernel[inner:], conv_bias[inner:], m.chunk, False), 2, -1)
        if groups > 1:    # [chunks, B, Q, G x N] -> [chunks, B, G, Q, N]
            b, c = (jnp.moveaxis(v.reshape(*v.shape[:3], groups, m.state), 3, 2) for v in (b, c))
        dt = cut(jax.nn.softplus(dt.astype(f32) + dt_bias), m.chunk, True)
        y = scan_chunks(x.reshape(*x.shape[:2], heads, m.head_dim, -1), dt, -jnp.exp(a_log),
                        b, c, skip)
        return gated_norm(y.reshape(x.shape), u @ w_z, norm, sp.norm_eps,
                          groups) @ w_out.astype(cd)

    @staticmethod
    def scan_count(spec: TorsoSpec, op: str, rows: int, tokens: int) -> dict:
        """One layer's forward over ``rows`` sequences of ``tokens``: the
        chunks the scan walks, the tokens it walks them over and those that
        are the sequences' own."""
        chunks, padded = chunks_of(tokens, spec.arg("mamba").chunk)
        return {"chunks": float(rows * chunks), "tokens_padded": float(rows * padded),
                "tokens": float(rows * tokens)}


class NopeAttention(nn.Module):
    """Grouped-query causal attention, no positional rule, no bias: scores
    ``q k^T`` times the spec's ``attention_multiplier``."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        sp, cd = self.spec, self.compute_dtype
        d, h, kv, hd = (sp.hidden_size, sp.arg("num_attention_heads"),
                        sp.arg("num_key_value_heads"), sp.arg("head_dim"))
        wq = self.param("w_q", _lecun(), (d, h * hd), self.param_dtype)
        wk = self.param("w_k", _lecun(), (d, kv * hd), self.param_dtype)
        wv = self.param("w_v", _lecun(), (d, kv * hd), self.param_dtype)
        wo = self.param("w_o", _lecun(), (h * hd, d), self.param_dtype)
        heads_of = lambda w, n: jnp.einsum(  # noqa: E731  [B, n, T, hd]
            "btd,dnk->bntk", u, w.astype(cd).reshape(d, n, hd))
        q = (heads_of(wq, h).astype(jnp.float32) * sp.arg("attention_multiplier")).astype(cd)
        with part("attn_full"):
            a = blocked.blocked_attention(q, heads_of(wk, kv), heads_of(wv, kv))
        return jnp.einsum("bntk,nkd->btd", a, wo.astype(cd).reshape(h, hd, d))

    @staticmethod
    def count(spec: TorsoSpec, op: str, rows: int, tokens: int) -> dict:
        """One layer's forward over ``rows`` sequences of ``tokens``, under
        ``laguna_moe.GatedAttention.count``'s names for a causal layer."""
        heads = spec.arg("num_attention_heads")
        group = heads // spec.arg("num_key_value_heads")
        visited, total = blocked.blocks_visited(tokens, None, group)
        return {"pairs_in_mask_full": float(rows * blocked.pairs_in_mask(tokens, None)),
                "pairs_computed_full": float(rows * blocked.pairs_computed(tokens, None, group)),
                "blocks_visited_full": float(rows * heads * visited),
                "blocks_total_full": float(rows * heads * total)}


MIXERS = {"mamba": Mamba2, "attention": NopeAttention}


def spec_from_config(cfg: Mapping) -> TorsoSpec:
    """A ``TorsoSpec`` from the published ``config.json``'s keys, plus what a
    cut states (``expert_torso.cut_from_config``: ``layers_held``)."""
    types = list(cfg["layer_types"])
    held, outputs, experts = cut_from_config(cfg)
    if outputs or int(cfg.get("num_local_experts", 0)):
        raise ValueError("this family's expert layers are not built here: num_local_experts "
                         "must be 0")
    if int(cfg["mamba_n_groups"]) != 1:
        raise ValueError("this family's published configs have one group of heads: "
                         "mamba_n_groups must be 1 (models/nemotron_h.py builds groups)")
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise ValueError("the attention layers have no positional rule: "
                         "position_embedding_type must be nope")
    d, heads, kv = (int(cfg[k]) for k in ("hidden_size", "num_attention_heads",
                                          "num_key_value_heads"))
    mamba = MambaSizes(heads=int(cfg["mamba_n_heads"]), head_dim=int(cfg["mamba_d_head"]),
                       state=int(cfg["mamba_d_state"]), conv=int(cfg["mamba_d_conv"]),
                       chunk=int(cfg["mamba_chunk_size"]))
    if mamba.inner != int(cfg["mamba_expand"]) * d or heads % kv:
        raise ValueError(f"mamba_n_heads x mamba_d_head is not mamba_expand x {d}, or "
                         f"{heads} heads do not divide by {kv} key-value heads")
    ops = sorted({types[i] for i in held})
    if not set(ops) <= set(MIXERS):
        raise ValueError(f"unknown layer types {ops}; {sorted(MIXERS)}")
    return TorsoSpec(
        hidden_size=d,
        intermediate_size=int(cfg["shared_intermediate_size"]),
        moe_intermediate_size=0,
        norm_eps=float(cfg["rms_norm_eps"]),
        router_outputs=0,
        num_experts_per_tok=0,
        experts_held=experts,
        layers=tuple((types[i], "dense") for i in held),
        mixers=tuple((op, MIXERS[op]) for op in ops),
        mixer_args=(("mamba", mamba), ("num_attention_heads", heads),
                    ("num_key_value_heads", kv), ("head_dim", int(cfg.get("head_dim") or d // heads)),
                    ("attention_multiplier", float(cfg["attention_multiplier"]))),
        use_expert_bias=False,
        frame_history=True,
        residual_multiplier=float(cfg.get("residual_multiplier", 1.0)),
        token_multiplier=float(cfg.get("embedding_multiplier", 1.0)),
        float32_leaves=("A_log", "dt_bias", "['D']"),
    )


class GraniteHybridQ(TorsoQ):
    """Stem, a frame at a time -> a history's tokens -> Granite 4.0-H layers
    -> norm, mean over tokens -> dueling head."""

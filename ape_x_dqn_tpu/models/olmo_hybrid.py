"""Olmo-Hybrid-7B's block (allenai/Olmo-Hybrid-7B, ``config.json``,
``model_type`` ``olmo_hybrid``) as a Q-network's torso over a history of
frames: its two mixers and the spec made from the published keys.  Three
layers of four (``layer_types``) are Gated DeltaNet (arXiv:2412.06464, as the
``linear_*`` keys name it): q, k and v each through a causal depthwise
convolution of ``linear_conv_kernel_dim`` taps and a SiLU, q and k
L2-normalised a head, **one log decay a head and token**, ``g = -exp(A_log)
softplus(w_a . u + dt_bias)``, a write strength ``beta`` up to 2
(``linear_allow_neg_eigval``), the recurrence in chunks in its scalar-gate
form (``ops/chunked_delta.py``) over **keys of ``linear_key_head_dim`` and
values of ``linear_value_head_dim``** (96 and 192: the state is not square),
an RMSNorm over a head's values under a full-rank SiLU gate, the output
projection.  ``full_attention`` layers are causal softmax attention, a
key-value head a query head, with no positional rule
(``rope_parameters.rope_theta`` null) in blocked kernels
(``ops/pallas/blocked_attention.py``); **queries and keys are RMS-normalised
over the layer's whole width before they are cut into heads** (the Olmo
family's QK-norm, OLMo 2, arXiv:2501.00656), so a head's scale hangs on every
other head and no share of the heads is a part of the layer: the mixers hold
every head (no ``divides_heads``).  Every layer's FFN is the dense SwiGLU, and
the block norms a sublayer's output, not its input (``TorsoSpec.post_norm``).
The block and the Q-network around it are ``models/expert_torso.py``'s.

The convolutions, the L2 norm and the token-major head layout are
``solar_open2``'s helpers; the layer stands apart from its ``DeltaAttention``
(a decay a key channel from a projection of the head's width, one head size,
a sigmoid gate with a bias, a share of heads): what the two have in common is
those helpers and the call into ``chunked_delta``.

``A_log`` and ``dt_bias`` stay float32 in a target network of a lower type
(the spec's ``float32_leaves``), as the other recurrent layers'.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping

import jax
import jax.numpy as jnp
from flax import linen as nn

from ape_x_dqn_tpu.models.expert_torso import TorsoQ, TorsoSpec, _lecun, cut_from_config
from ape_x_dqn_tpu.models.granite_hybrid import NopeAttention, _a_log_init, _dt_bias_init
from ape_x_dqn_tpu.models.solar_open2 import CHUNK, DeltaAttention, _heads_of, _l2, _short_conv
from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.utils.profiling import part

@dataclasses.dataclass(frozen=True)
class DeltaNetSizes:
    """The published ``linear_*`` keys."""

    heads: int                        # key heads = value heads
    key_dim: int
    value_dim: int
    conv: int                         # the convolutions' taps
    beta_scale: float                 # 2 with linear_allow_neg_eigval
    chunk: int = CHUNK


def _whole_width_norm(x, weight, eps: float):
    """RMSNorm of ``x`` [B, T, n x k] over all of its last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * weight.astype(
        jnp.float32)


class GatedDeltaNet(nn.Module):
    """``W_o (norm(delta(conv q, conv k, conv v, g, beta)) silu(W_g u))``:
    module docstring."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        sp, cd, pd, f32 = self.spec, self.compute_dtype, self.param_dtype, jnp.float32
        m: DeltaNetSizes = sp.arg("linear")
        d, n, kd, vd = sp.hidden_size, m.heads, m.key_dim, m.value_dim
        width = {"q": kd, "k": kd, "v": vd}
        w = {x: self.param("w_" + x, _lecun(), (d, n * width[x]), pd) for x in "qkv"}
        conv = {x: self.param("conv_" + x, _lecun(-1), (n * width[x], m.conv), pd) for x in "qkv"}
        w_a = self.param("w_a", _lecun(), (d, n), pd)
        a_log = self.param("A_log", _a_log_init, (n,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (n,), f32)
        w_b = self.param("w_b", _lecun(), (d, n), pd)
        w_g = self.param("w_g", _lecun(), (d, n * vd), pd)
        norm = self.param("norm", nn.initializers.ones, (vd,), pd)
        w_o = self.param("w_o", _lecun(), (n * vd, d), pd)

        # ``solar_open2.DeltaAttention``'s three recomputations: float32 a head
        # and token between the projections and the scan; the scan's kept
        # states; both computed again when the block's backward pass reaches
        # the mixer.
        @jax.checkpoint
        def operands(raw, a, b, kernels, a_log, dt_bias):
            q, k, v = (_short_conv(x, kernel) for x, kernel in zip(raw, kernels))
            q, k, v = _l2(q, 1.0 / math.sqrt(kd)).astype(cd), _l2(k).astype(cd), v.astype(cd)
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(a.astype(f32) + dt_bias[:, None])
            return q, k, v, g, m.beta_scale * jax.nn.sigmoid(b.astype(f32))

        @jax.checkpoint
        def gated(o, z, norm):
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + sp.norm_eps)
            return (o * norm.astype(f32) * jax.nn.silu(z.astype(f32))).astype(cd)

        @jax.checkpoint
        def mixed(raw, a, b, z, kernels, a_log, dt_bias, norm):
            q, k, v, g, beta = operands(raw, a, b, kernels, a_log, dt_bias)
            return gated(chunked_delta(q, k, v, g, beta, m.chunk), z, norm)

        by_head = lambda w: jnp.einsum("btd,dn->bnt", u, w.astype(cd))   # noqa: E731
        y = mixed(tuple(_heads_of(u, w[x], n) for x in "qkv"), by_head(w_a), by_head(w_b),
                  _heads_of(u, w_g, n), tuple(conv[x] for x in "qkv"), a_log, dt_bias, norm)
        return jnp.einsum("bntk,nkd->btd", y, w_o.astype(cd).reshape(n, vd, d))

    # chunks walked, tokens with and without their padding: the spec's ``linear`` sizes' chunk
    delta_count = staticmethod(DeltaAttention.delta_count)


class QkNormAttention(nn.Module):
    """Causal attention, a key-value head a query head, no positional rule,
    no bias: queries and keys normed over the whole width, then cut into
    heads; scores over the square root of the head."""

    spec: TorsoSpec
    op: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        sp, cd, pd = self.spec, self.compute_dtype, self.param_dtype
        d, h, hd = sp.hidden_size, sp.arg("num_attention_heads"), sp.arg("head_dim")
        wq, wk, wv = (self.param(name, _lecun(), (d, h * hd), pd) for name in ("w_q", "w_k", "w_v"))
        q_norm = self.param("q_norm", nn.initializers.ones, (h * hd,), pd)
        k_norm = self.param("k_norm", nn.initializers.ones, (h * hd,), pd)
        wo = self.param("w_o", _lecun(), (h * hd, d), pd)
        heads = lambda x: jnp.moveaxis(x.reshape(*x.shape[:2], h, hd), 2, 1)   # noqa: E731
        q = _whole_width_norm(u @ wq.astype(cd), q_norm, sp.norm_eps) / math.sqrt(hd)
        k = _whole_width_norm(u @ wk.astype(cd), k_norm, sp.norm_eps)
        with part("attn_full"):
            a = blocked.blocked_attention(heads(q.astype(cd)), heads(k.astype(cd)),
                                          _heads_of(u, wv, h))
        return jnp.einsum("bntk,nkd->btd", a, wo.astype(cd).reshape(h, hd, d))

    # a causal layer's pairs and blocks over ``num_attention_heads``, under the ``full`` names
    count = staticmethod(NopeAttention.count)


MIXERS = {"linear_attention": GatedDeltaNet, "full_attention": QkNormAttention}


def spec_from_config(cfg: Mapping) -> TorsoSpec:
    """A ``TorsoSpec`` from the published ``config.json``'s keys, plus what a
    cut states (``expert_torso.cut_from_config``: ``layers_held``).  Assumed,
    as the benchmark's configuration file says: the post-norm block and the
    QK-norm over the whole width (the family's), the Gated DeltaNet layer's
    lay-out, no positional rule, the chunk."""
    types = list(cfg["layer_types"])
    held, outputs, experts = cut_from_config(cfg)
    if outputs:
        raise ValueError("this family is dense: no expert layer is built here")
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None or cfg.get("attention_bias"):
        raise ValueError("this family's spec: rope_parameters.rope_theta null (no positional "
                         "rule on the full layers) and attention_bias false")
    d, heads, kv = (int(cfg[k]) for k in ("hidden_size", "num_attention_heads",
                                          "num_key_value_heads"))
    n = int(cfg["linear_num_key_heads"])
    if kv != heads or int(cfg["linear_num_value_heads"]) != n or d % heads:
        raise ValueError(f"a key-value head a query head ({heads}, {kv}), a value head a key head "
                         f"in the linear layers, and {d} in whole heads")
    ops = sorted({types[i] for i in held})
    if not set(ops) <= set(MIXERS):
        raise ValueError(f"unknown layer types {ops}; {sorted(MIXERS)}")
    sizes = DeltaNetSizes(heads=n, key_dim=int(cfg["linear_key_head_dim"]),
                          value_dim=int(cfg["linear_value_head_dim"]),
                          conv=int(cfg["linear_conv_kernel_dim"]),
                          beta_scale=2.0 if cfg.get("linear_allow_neg_eigval") else 1.0,
                          chunk=int(cfg.get("linear_chunk_size", CHUNK)))
    return TorsoSpec(
        hidden_size=d,
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=0,
        norm_eps=float(cfg["rms_norm_eps"]),
        router_outputs=0,
        num_experts_per_tok=0,
        experts_held=experts,
        layers=tuple((types[i], "dense") for i in held),
        mixers=tuple((op, MIXERS[op]) for op in ops),
        mixer_args=(("linear", sizes), ("num_attention_heads", heads), ("num_key_value_heads", kv),
                    ("head_dim", int(cfg.get("head_dim") or d // heads))),
        use_expert_bias=False,
        frame_history=True,
        float32_leaves=("A_log", "dt_bias"),
        post_norm=True,
    )


class OlmoHybridQ(TorsoQ):
    """Stem, a frame at a time -> a history's tokens -> Olmo-Hybrid layers ->
    norm, mean over tokens -> dueling head."""

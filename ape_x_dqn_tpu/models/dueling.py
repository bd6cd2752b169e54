"""Dueling Q-networks as Flax modules.

Capability parity with reference duelling_network.py:3-28 (the 28-line torch
module), TPU-first:
  * Conv torso Conv(8×8/4) → Conv(4×4/2) → Conv(3×3/1) → flatten → two
    512-unit streams → value head (1) + advantage head (A).  Default channel
    widths 64/64/64 match the reference (NOT the Nature-DQN 32/64/64 —
    SURVEY §2 component 5); ``channels=(32, 64, 64)`` gives the Nature stack.
  * Aggregation is the *intended* per-row mean:  Q = V + (A − mean_a A)
    (the reference's ``advantage.sum()`` reduces over the whole batch —
    duelling_network.py:27, defect register SURVEY §2.8).
  * ``forward`` returns ``(value, advantage, q)`` matching the reference's
    triple return (duelling_network.py:28); callers that only need Q use
    ``.q_values()``.
  * Compute dtype is configurable (bfloat16 by default on TPU — MXU-native);
    params stay float32.  uint8 inputs are normalized inside the module so
    frames travel HBM as bytes.
  * NHWC layout (TPU conv-friendly), vs the reference's NCHW.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ape_x_dqn_tpu.utils.profiling import launch_span, part


class DuelingOutput(NamedTuple):
    """(value, advantage, q) — index [2] for Q, as reference callers do.

    A NamedTuple so it is a registered JAX pytree: network outputs can cross
    jit/vmap/scan boundaries intact (e.g. returned from a jitted rollout).
    """

    value: jax.Array
    advantage: jax.Array
    q: jax.Array


def _dueling_aggregate(value: jax.Array, advantage: jax.Array) -> jax.Array:
    return value + advantage - jnp.mean(advantage, axis=-1, keepdims=True)


# The stem's three convolutions, VALID: (kernel, stride) a side.
STEM_WINDOWS = ((8, 4), (4, 2), (3, 1))


def _observations(x: jax.Array, compute_dtype) -> jax.Array:
    """NHWC uint8 or float observations in the compute type, bytes as [0, 1]."""
    # Guard against the reference's NCHW layout, which otherwise fails deep
    # inside flax.
    if x.ndim != 4:
        raise ValueError(f"expected NHWC [B, H, W, C] observations, got shape {x.shape}")
    if x.shape[1] <= 4 and x.shape[3] > 4 and x.shape[2] == x.shape[3]:
        # A tiny axis-1 extent with a large *square* trailing pair is the
        # NCHW frame signature (B, C, H, W); square spatial dims keep
        # legitimate small-H NHWC inputs like (B, 4, 4, 8) usable.
        raise ValueError(
            f"observations look NCHW (shape {x.shape}); this framework uses "
            "NHWC [B, H, W, C] — transpose with x.transpose(0, 2, 3, 1)"
        )
    with part("stem"):
        if x.dtype == jnp.uint8:
            return x.astype(compute_dtype) / 255.0
        return x.astype(compute_dtype)


def conv_stem(x: jax.Array, channels: Sequence[int], compute_dtype, param_dtype,
              out_dtype=None, after_first: bool = False) -> jax.Array:
    """Conv(8x8/4) -> Conv(4x4/2) -> Conv(3x3/1), VALID, ReLU, on NHWC uint8
    or float observations: [B, H, W, C] -> [B, h, w, channels[-1]].  Called
    inside a module's ``@nn.compact`` method; the convolutions are that
    module's ``Conv_0..2``.  ``out_dtype`` is the type the last convolution
    sums and returns in (default ``compute_dtype``).  ``after_first``: ``x``
    is ``Conv_0``'s output with its ReLU taken (``first_conv_of_two`` computes
    it), and the layers after it run."""
    if len(channels) != len(STEM_WINDOWS):
        raise ValueError(
            f"channels must have exactly {len(STEM_WINDOWS)} entries, got {channels}"
        )
    if not after_first:
        x = _observations(x, compute_dtype)
    dtypes = [compute_dtype] * (len(channels) - 1) + [out_dtype or compute_dtype]
    with part("stem"):
        for i in range(int(after_first), len(channels)):
            k, s = STEM_WINDOWS[i]
            x = nn.Conv(channels[i], (k, k), (s, s), padding="VALID", dtype=dtypes[i],
                        param_dtype=param_dtype, name=f"Conv_{i}")(x)
            x = nn.relu(x)
    return x


def first_conv_of_two(params_a, params_b, x: jax.Array, compute_dtype
                      ) -> Tuple[jax.Array, jax.Array]:
    """``Conv_0`` and its ReLU of two parameter trees on the same
    observations, as one convolution: the two filter banks side by side along
    the output channels, so ``x`` is cast and unfolded once and the product is
    ``2 x channels[0]`` wide.  Per output channel the sum is ``nn.Conv``'s own:
    operands cast as it casts them, its dimension numbers, stride and
    padding."""
    x = _observations(x, compute_dtype)
    _, stride = STEM_WINDOWS[0]
    with part("stem"):
        convs = [p["params"]["Conv_0"] for p in (params_a, params_b)]
        kernel, bias = (
            jnp.concatenate([c[leaf].astype(compute_dtype) for c in convs], axis=-1)
            for leaf in ("kernel", "bias"))
        y = jax.lax.conv_general_dilated(
            x, kernel, (stride, stride), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y_a, y_b = jnp.split(y + bias, 2, axis=-1)
        return nn.relu(y_a), nn.relu(y_b)


def dueling_head(x: jax.Array, num_actions: int, hidden: int, compute_dtype,
                 param_dtype) -> DuelingOutput:
    """Two ``hidden``-unit streams on flat features [B, F], value (1) and
    advantage (A) heads in float32, Q = V + A - mean(A).  Called inside a
    module's ``@nn.compact`` method; the layers are its ``Dense_0..3``."""
    with part("head"):
        v = nn.relu(nn.Dense(hidden, dtype=compute_dtype, param_dtype=param_dtype)(x))
        a = nn.relu(nn.Dense(hidden, dtype=compute_dtype, param_dtype=param_dtype)(x))
        value = nn.Dense(1, dtype=jnp.float32, param_dtype=param_dtype)(v)
        advantage = nn.Dense(num_actions, dtype=jnp.float32, param_dtype=param_dtype)(a)
        value = value.astype(jnp.float32)
        advantage = advantage.astype(jnp.float32)
        return DuelingOutput(value, advantage, _dueling_aggregate(value, advantage))


class DuelingDQN(nn.Module):
    """Convolutional dueling Q-network for image observations.

    Attributes:
      num_actions: size of the action space.
      channels: conv channel widths (reference parity default (64, 64, 64)).
      hidden: width of each dueling stream's hidden layer (reference: 512).
      compute_dtype: activation dtype — bfloat16 rides the MXU natively.
      param_dtype: parameter storage dtype.  bfloat16 halves the param HBM
        read per forward/backward (the fused step is bandwidth-bound); pair
        it with ``train_step.with_float32_master`` so updates accumulate in
        float32.
    """

    num_actions: int
    channels: Sequence[int] = (64, 64, 64)
    hidden: int = 512
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, after_first: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """``after_first``: ``x`` is ``Conv_0``'s output with its ReLU taken
        (``q_of_two`` computes it) and not an observation."""
        x = conv_stem(x, self.channels, self.compute_dtype, self.param_dtype,
                      after_first=after_first)
        return dueling_head(x.reshape((x.shape[0], -1)), self.num_actions,
                            self.hidden, self.compute_dtype, self.param_dtype)

    def q_values(self, x: jax.Array) -> jax.Array:
        return self(x)[2]

    def q_of_two(self, params_a, params_b, obs: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Q of two parameter trees on the same observations (the double-Q
        bootstrap's online and target nets on ``next_obs``): one first
        convolution for both (``first_conv_of_two``), then each net's own
        layers on its half.  What ``apply(params_a, obs)[2]`` and
        ``apply(params_b, obs)[2]`` compute; no gradient is meant to flow."""
        firsts = first_conv_of_two(params_a, params_b, obs, self.compute_dtype)
        return tuple(self.apply(p, y, after_first=True)[2]
                     for p, y in zip((params_a, params_b), firsts))


class DuelingMLP(nn.Module):
    """Dueling Q-network for flat/vector observations (small envs, unit tests,
    chain-MDP learning tests — SURVEY §4 level 3)."""

    num_actions: int
    hidden_sizes: Sequence[int] = (256, 256)
    compute_dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        if x.dtype == jnp.uint8:
            x = x.astype(self.compute_dtype) / 255.0
        else:
            x = x.astype(self.compute_dtype)
        x = x.reshape((x.shape[0], -1))
        for h in self.hidden_sizes:
            x = nn.relu(nn.Dense(h, dtype=self.compute_dtype,
                                 param_dtype=self.param_dtype)(x))
        value = nn.Dense(1, dtype=jnp.float32, param_dtype=self.param_dtype)(x)
        advantage = nn.Dense(self.num_actions, dtype=jnp.float32,
                             param_dtype=self.param_dtype)(x)
        q = _dueling_aggregate(value.astype(jnp.float32), advantage.astype(jnp.float32))
        return DuelingOutput(value, advantage, q)

    def q_values(self, x: jax.Array) -> jax.Array:
        return self(x)[2]


def build_greedy_apply(network: nn.Module):
    """Jitted serving entry: ``(params, obs[B]) -> (actions[B], q[B, A])``.

    The inference twin of actors/pool.build_policy_step with the ε-greedy
    draw removed: pure greedy ``argmax Q(s, .)`` per row, no RNG threading —
    the compute kernel the serving batcher amortizes across clients
    (serving/batcher.py).  Q comes back float32 so clients can audit the
    argmax (tests pin padded-row independence through it).
    """

    @jax.jit
    def greedy_apply(params, obs):
        q = network.apply(params, obs)[2]
        return jnp.argmax(q, axis=-1).astype(jnp.int32), q

    return greedy_apply


# Network kinds whose torso is a stack of blocks: module under models/ -> class.
TORSO_KINDS = {"lfm2_moe": "Lfm2MoeQ", "laguna_moe": "LagunaMoeQ",
               "granite_hybrid": "GraniteHybridQ", "solar_open2": "SolarOpen2Q",
               "ling_hybrid": "LingHybridQ", "olmo_hybrid": "OlmoHybridQ",
               "kanana_moe": "KananaMoeQ", "nemotron_h": "NemotronHQ"}


@launch_span("network")
def build_network(kind: str, num_actions: int, **kwargs) -> nn.Module:
    """Factory keyed by config string: {"conv", "nature", "mlp", "lfm2_moe",
    "laguna_moe", "granite_hybrid", "solar_open2", "ling_hybrid", "olmo_hybrid", "kanana_moe",
    "nemotron_h"}.
    The last eight are torsos of blocks (``models/expert_torso.py``) and take ``torso``:
    the published config's keys and the cut (``spec_from_config`` of
    ``models/lfm2_moe.py``, one frame's positions as tokens; of
    ``models/laguna_moe.py``, ``models/granite_hybrid.py``,
    ``models/solar_open2.py``, ``models/ling_hybrid.py``,
    ``models/olmo_hybrid.py``, ``models/kanana_moe.py`` and
    ``models/nemotron_h.py``, a history of
    single frames: attention layers with sparse experts, state-space layers
    without, delta-rule layers with sparse experts and a share of heads, the
    same under a bounded gate beside a latent-attention layer and experts
    chosen by groups, scalar-gate delta-rule layers in post-norm dense blocks,
    latent attention in every layer over sparse experts and shared ones,
    one-sublayer layers of grouped state-space mixers, latent experts of two
    matrices and an attention layer, each a share of heads and columns)."""
    if kind == "conv":
        return DuelingDQN(num_actions=num_actions, **kwargs)
    if kind == "nature":
        kwargs.setdefault("channels", (32, 64, 64))
        return DuelingDQN(num_actions=num_actions, **kwargs)
    if kind == "mlp":
        return DuelingMLP(num_actions=num_actions, **kwargs)
    if kind in TORSO_KINDS:
        import importlib

        family = importlib.import_module("ape_x_dqn_tpu.models." + kind)
        if not kwargs.get("torso"):
            raise ValueError(f"network kind {kind} needs torso=<the block's config>")
        return getattr(family, TORSO_KINDS[kind])(
            num_actions=num_actions, spec=family.spec_from_config(kwargs.pop("torso")), **kwargs)
    raise ValueError(f"unknown network kind: {kind}")

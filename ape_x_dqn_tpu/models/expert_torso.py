"""What every Q-network with a torso of blocks shares: the spec of the widths
and layers, the expert layer, the block and the wrapper.  A torso of blocks
need not have experts: a spec whose router has no outputs holds none, every
layer's FFN is the dense SwiGLU, and nothing is routed, sown or counted.
A spec may hold a share of a layer's heads as well as of its experts
(``heads_held``, one chip's part of a tensor-parallel mixer): a mixer that
divides computes its held heads' part of ``W_o``'s sum and that partial
result goes on, as an expert layer's does; ``None`` holds every head, and
only a family whose mixers divide (``models/solar_open2.py``) may state one.

The convolutional stem and the dueling head are ``dueling.py``'s; between
them the positions of the stem's output are tokens (raster order,
Obando-Ceron et al. 2024, arXiv:2402.08609, "PerConv"), projected to the
torso's width and run through the layers the spec names, each

    h <- h + m Op(RMSNorm(h))     Op  = one of the spec's mixers, by layer type
    h <- h + m FFN(RMSNorm(h))    FFN = SwiGLU | mixture of experts (+ a shared expert) | none

(the FFN kinds are ``FFNS``: ``dense``, ``moe`` and ``none``, a layer that is
its mixer alone, for a model whose published layers are one sublayer each and
do not all pair up; an expert's and the shared expert's rule is one of
``RULES``: ``swiglu``, three matrices, ``silu(x W_1) * (x W_3)`` into ``W_2``,
or ``relu2``, two, ``relu(x W_1)^2`` into ``W_2``)
(``m`` the spec's ``residual_multiplier``, 1 unless a model states one; the
projected tokens are likewise multiplied by its ``token_multiplier``)
or, where the spec says ``post_norm`` (the Olmo family's reordered norm, OLMo
2, arXiv:2501.00656), a sublayer reads the stream as it is and its output is
normed, ``h <- h + m RMSNorm(Op(h))``, ``h <- h + m RMSNorm(FFN(h))``, under
the same two parameter names;
then RMSNorm, the mean over the tokens and the dueling head.  What is a
model's is the spec's (``TorsoSpec``): the mixers and their head counts, RoPE
rules and windows, the router's score function, count and bias, the shared
expert, whether an observation's tokens are one frame's positions or those
of a history of frames.  ``models/lfm2_moe.py``, ``models/laguna_moe.py``,
``models/granite_hybrid.py``, ``models/solar_open2.py``,
``models/ling_hybrid.py``, ``models/olmo_hybrid.py`` and
``models/kanana_moe.py`` and ``models/nemotron_h.py`` make a spec from a
published ``config.json``'s keys and bring their mixers (the last but one
takes ``ling_hybrid``'s latent mixer, told that no head is gated; the last
``granite_hybrid``'s Mamba-2, told its groups, and ``solar_open2``'s softmax
mixer, told that nothing is gated); everything else is here, once.

The expert layer is one chip's share of an expert-parallel layer: it is told
how many experts exist (the router's outputs), how many a token takes and
which range ``[lo, hi)`` it holds.  It routes over all of them, normalises
the gates over all the chosen ones, and computes its own experts' part of
the sum; nothing stands in for the others.  Where the spec names a latent
(``moe_latent_size``, LatentMoE) the routed experts work in it, between two
projections that all experts of the layer share and every chip holds whole
(``w_down``, ``w_up``: no norm, activation or bias), while the router and the
shared expert read the layer's input at its own width; the shared expert may
be held by columns (``shared_expert_held``: a chip's slice of its width, whose
part of ``W_2``'s sum goes on as it is).  A token's experts are chosen by
selection, not by sorting (``choose``, ``ops/router_choice.py``): k rounds of
"the largest biased score not yet taken, the first output that holds it",
after the same rounds over the groups' scores where the router keeps groups;
the order, the ties and the gates are a stable ``top_k``'s and a
``take_along_axis``'s to the bit, and the gates' gradient is a one-hot
select.  The token-expert pairs are sorted
by expert, those on held experts first, and the held ones are walked a tile
of rows at a time (``held_experts``): gather the tile's tokens, multiply by
groups (``jax.lax.ragged_dot``: on the TPU a grouped kernel that walks the
tiles the group sizes name and skips the rows past the last group), add each
row, by its gate, into its token's float32 sum (``_combined``: the sum lies
in blocks of 512 columns and every block takes its own scatter-add).  The
tiles walked are those the held pairs fill, a loop whose count the routing
of the call gives: every pair on a held expert is multiplied, none is ever
dropped, and nothing of the worst case's ``tokens x k`` rows by a width is
built.  A tile's rows come from the shapes (``tile_rows``).  The walk's
backward pass is written by hand
(``jax.custom_vjp``): it walks the same tiles, computes each again and keeps
nothing of a tile, where reverse-mode differentiation of the loop would stack
every tile's residuals.  Every layer is recomputed in the backward pass
(``nn.remat``), and consecutive layers of one kind run as one scanned body
over their stacked parameters (``layers_<first>_<last>``): one copy of the
layer's kernels in the executable for the run, not one a layer.

The tokens are centred before they are projected: the mean over a frame's
positions is taken off the stem's output, in float32 (the stem's last
convolution returns float32 here).  A fresh stem's positions share most of
their direction (its ReLU outputs are positive), and a router fed that
direction sends nearly every token to the same few experts, whatever its
bias.  With ``frame_history`` an observation ``[B, H, W, F]`` is a history of
``F`` single frames, oldest first: each goes through the stem alone
(``[B x F, H, W, 1]``), is centred over its own positions, and the tokens
are the frames' positions in time-major raster order.

Per call an expert layer sows, in the collection ``types.ROUTING``, ``load``:
the pairs on each of the router's outputs, held or not.  The network reads
it for the train step: ``routing_metrics`` (the counters of the held range)
and ``rebalanced`` (the balancing rule).  ``attention_metrics`` gives what
the spec's mixers count from the shapes alone (blocked attention's pairs in
the mask and blocks visited; ``None`` where no mixer counts anything), and
``scan_metrics`` what its state-space mixers do (chunks walked, tokens with
and without their padding), ``delta_metrics`` the same of its delta-rule
mixers.

The expert bias is a model's load-balancing buffer (``use_expert_bias``): a
parameter no gradient reaches, which the train step moves after every update
against each output's load error, ``bias -= BIAS_UPDATE_RATE * clip(load /
mean(load) - 1, -1, 1)`` over the step's online forwards.  A spec without one
has no such parameter and ``rebalanced`` returns the parameters as they are.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ape_x_dqn_tpu.models.dueling import STEM_WINDOWS, conv_stem, dueling_head
from ape_x_dqn_tpu.ops.router_choice import router_choice
from ape_x_dqn_tpu.types import ROUTING
from ape_x_dqn_tpu.utils.profiling import part, pass_

FFNS = ("dense", "moe", "none")
RULES = ("swiglu", "relu2")   # an expert's, and the shared expert's
SCORES = ("sigmoid", "softmax")
# The balancing rule's rate (LFM2's published config has ``use_expert_bias``
# and names no rule).  A fresh
# router's scores put about 0.8 of the tokens per unit of score at the top-k
# threshold, so a sixteenth of the tokens an output moves its load by about
# 13 times the bias's move, as a share of the mean: 0.05 takes two thirds of a
# load error off a step, and under 0.15 the rule cannot overshoot.
BIAS_UPDATE_RATE = 0.05


@dataclasses.dataclass(frozen=True)
class TorsoSpec:
    """The widths and layers of the torso, under the published configs' names.
    ``mixers`` maps a layer type to the module that mixes tokens there,
    ``mixer(spec, op, compute_dtype, param_dtype, name=op)``; a family's own
    sizes (a convolution's taps, an attention's heads, RoPE rule and window)
    ride in ``mixer_args``, which only its mixers read.  What a chip holds of
    a layer is the spec's too: ``experts_held`` of the router's outputs, and
    ``heads_held`` of the published heads where every mixer divides by heads
    (``divides_heads`` on the mixer's class)."""

    hidden_size: int
    intermediate_size: int            # the leading dense layers' SwiGLU
    moe_intermediate_size: int        # one expert's SwiGLU
    norm_eps: float
    router_outputs: int               # experts that exist
    num_experts_per_tok: int
    experts_held: Tuple[int, int]     # [lo, hi) of the router's outputs
    layers: Tuple[Tuple[str, str], ...]  # (op, ffn) per layer run here
    mixers: Tuple[Tuple[str, Callable], ...]
    mixer_args: Tuple[Tuple[str, object], ...] = ()
    norm_topk_prob: bool = True
    gate_norm_eps: float = 1e-6       # added to the chosen scores' sum
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    score_function: str = "sigmoid"   # of the router's outputs, before the top-k
    shared_expert_intermediate_size: int = 0   # 0: no shared expert
    frame_history: bool = False       # an observation is F single frames
    residual_multiplier: float = 1.0  # on both branches of a block
    token_multiplier: float = 1.0     # on the projected tokens
    float32_leaves: Tuple[str, ...] = ()   # a family's, beside the router's (TorsoQ)
    heads_held: Optional[Tuple[int, int]] = None   # [lo, hi) of the published heads; None: all
    router_groups: int = 1            # the router's outputs lie in so many groups of consecutive ones
    router_groups_kept: int = 1       # and a token chooses among the experts of so many (``route``)
    post_norm: bool = False           # a block norms a sublayer's output, not its input
    expert_rule: str = "swiglu"       # one of RULES: the routed experts' and the shared expert's
    moe_latent_size: int = 0          # the routed experts' width (LatentMoE); 0: hidden_size
    shared_expert_held: Optional[Tuple[int, int]] = None   # [lo, hi) of its columns; None: all

    def __post_init__(self):
        lo, hi = self.experts_held
        if self.router_outputs == 0:      # no expert layer at all
            if (lo, hi) != (0, 0) or any(ffn == "moe" for _, ffn in self.layers):
                raise ValueError("a router without outputs holds no expert and has no moe layer")
        elif not 0 <= lo < hi <= self.router_outputs:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of the router's "
                f"{self.router_outputs} outputs")
        if self.score_function not in SCORES:
            raise ValueError(f"unknown score function {self.score_function!r}; {SCORES}")
        if self.expert_rule not in RULES:
            raise ValueError(f"unknown expert rule {self.expert_rule!r}; {RULES}")
        if self.shared_expert_held is not None and not (
                0 <= self.shared_expert_held[0] < self.shared_expert_held[1]
                <= self.shared_expert_intermediate_size):
            raise ValueError(f"shared_expert_held {self.shared_expert_held} is no range of the "
                             f"shared expert's {self.shared_expert_intermediate_size} columns")
        groups, kept = self.router_groups, self.router_groups_kept
        if not 1 <= kept <= groups or (groups > 1 and (
                self.router_outputs % groups
                or self.num_experts_per_tok > kept * (self.router_outputs // groups))):
            raise ValueError(f"router_groups {groups}, router_groups_kept {kept}: no choice of "
                             f"{self.num_experts_per_tok} among {self.router_outputs} outputs")
        ops = tuple(op for op, _ in self.mixers)
        for op, ffn in self.layers:
            if op not in ops or ffn not in FFNS:
                raise ValueError(f"unknown layer ({op!r}, {ffn!r}); ops {ops}, ffns {FFNS}")
        if self.post_norm and any(ffn == "moe" for _, ffn in self.layers):
            raise ValueError("post_norm is built for dense layers: no family here norms an "
                             "expert layer's output")
        if self.heads_held is not None:
            whole = [op for op, mixer in self.mixers if not getattr(mixer, "divides_heads", False)]
            if whole or not 0 <= self.heads_held[0] < self.heads_held[1]:
                raise ValueError(f"heads_held {self.heads_held}: no range of heads, or the "
                                 f"mixers {whole} hold every head")

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    def arg(self, name: str):
        return dict(self.mixer_args)[name]


def cut_from_config(cfg) -> tuple:
    """What a cut states beside a published config's keys: (``layers_held``,
    indices into ``layer_types``, default the first ``num_hidden_layers``;
    ``router_outputs``, default ``num_experts``, 0 for a config that names no
    experts; ``experts_held``, default all)."""
    types = list(cfg["layer_types"])
    held = list(cfg.get("layers_held", range(int(cfg.get("num_hidden_layers", len(types))))))
    outputs = int(cfg.get("router_outputs", cfg.get("num_experts", 0)))
    return held, outputs, tuple(cfg.get("experts_held", (0, outputs)))


def layer_runs(layers: Sequence[Tuple[str, str]]) -> list:
    """[(first index, count, (op, ffn))]: the consecutive layers of one kind."""
    runs: list = []
    for i, kind in enumerate(layers):
        if runs and runs[-1][2] == kind:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1, kind)
        else:
            runs.append((i, 1, kind))
    return runs


def _lecun(fan_in_axis: int = -2, batch_axis=()):
    return nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", in_axis=fan_in_axis, batch_axis=batch_axis)


def _bias_init(key, shape, dtype=jnp.float32):
    return 0.01 * jax.random.normal(key, shape, dtype)


class RMSNorm(nn.Module):
    eps: float
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + self.eps)
        return (x32 * w.astype(jnp.float32)).astype(self.compute_dtype)


class SwiGLU(nn.Module):
    width: int
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        d, cd = u.shape[-1], self.compute_dtype
        w1 = self.param("w1", _lecun(), (d, self.width), self.param_dtype)
        w3 = self.param("w3", _lecun(), (d, self.width), self.param_dtype)
        w2 = self.param("w2", _lecun(), (self.width, d), self.param_dtype)
        return (jax.nn.silu(u @ w1.astype(cd)) * (u @ w3.astype(cd))) @ w2.astype(cd)


class Relu2(nn.Module):
    """``relu(u W_1)^2 W_2``: the ``relu2`` rule's dense form (the shared expert)."""

    width: int
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        d, cd = u.shape[-1], self.compute_dtype
        w1 = self.param("w1", _lecun(), (d, self.width), self.param_dtype)
        w2 = self.param("w2", _lecun(), (self.width, d), self.param_dtype)
        return jnp.square(jax.nn.relu(u @ w1.astype(cd))) @ w2.astype(cd)


def _lane_sum(x):
    """``jnp.sum(x, -1)`` of [T, k] in one order, written out: the order in
    which the TPU sums the lanes of a row, halves folded onto each other
    from the widest down (k = 8: ``((x0 + x4) + (x2 + x6)) + ((x1 + x5) +
    (x3 + x7))``).  That is how the gates' sum was rounded while the gates
    came from a gather, k in the lanes; the selection hands them over with
    the tokens in the lanes, where the compiler's own reduction adds one
    after another, and a third of the rows' sums would move in their last
    bit (read on the chip, ``PERF.md`` section 6, PR 43).  Its pull-back is
    ``_over_lanes``."""
    return _folded(x, x.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _folded(x, k: int):
    cols = [x[:, i] for i in range(k)]
    half = 1 << (k - 1).bit_length() >> 1
    while half:
        cols = [c + cols[i + half] if i + half < len(cols) else c for i, c in enumerate(cols[:half])]
        half >>= 1
    return cols[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _over_lanes(s, k: int):
    """[T] -> [T, k], every column ``s``: ``_lane_sum``'s transpose, and
    ``_lane_sum`` its own: a broadcast's pull-back leaves the order of its
    sum to the layout."""
    return jnp.broadcast_to(s[:, None], (s.shape[0], k))


_folded.defvjp(lambda x, k: (_folded(x, k), None), lambda k, _, ct: (_over_lanes(ct, k),))
_over_lanes.defvjp(lambda s, k: (_over_lanes(s, k), None), lambda k, _, ct: (_lane_sum(ct),))


def choose(scores, bias, spec: TorsoSpec, kept=None):
    """(chosen experts [T, k], gates [T, k], the groups kept [T, groups] or
    None) from float32 scores [T, E]: a selection, not a sort
    (``ops/router_choice.py``): k rounds of "the largest of ``scores +
    bias`` not yet taken, the first output that holds it", the gate that
    output's score; with ``router_groups`` over 1, among the groups the token
    keeps (``kept``, default the ``router_groups_kept`` largest by the sum
    of a group's two largest, the earlier of two equal ones first).  The
    gates are normalised over all k (``norm_topk_prob``), times
    ``routed_scaling_factor``."""
    chosen, gates, kept = router_choice(scores, bias, kept, spec.num_experts_per_tok,
                                        spec.router_groups, spec.router_groups_kept)
    if spec.norm_topk_prob:
        gates = gates / _over_lanes(_lane_sum(gates) + spec.gate_norm_eps, gates.shape[1])
    return chosen, gates * spec.routed_scaling_factor, kept


def groups_kept(biased, spec: TorsoSpec):
    """[T, groups] bool: the ``router_groups_kept`` groups a token keeps of
    the ``router_groups`` its biased scores [T, E] lie in (``noaux_tc``): a
    group's score is the sum of its two largest, the largest groups are
    kept, the earlier of two equal ones first."""
    return choose(biased, jnp.zeros(biased.shape[-1:], biased.dtype), spec)[2]


def route(scores, bias, spec: TorsoSpec, kept=None):
    """(chosen experts [T, k], gates [T, k]) from float32 scores [T, E]:
    ``choose``'s, for a caller that does not ask which groups were kept."""
    return choose(scores, bias, spec, kept)[:2]


# The grouped kernel's own tile: a walk's tile is a whole number of these.
KERNEL_ROWS = 512


def tile_rows(rows, num_held: int, router_outputs: int):
    """Rows of one tile of the walk over the held pairs, from the shapes:
    the fill even loads give (``rows`` pairs over the router's outputs,
    ``num_held`` of them here) plus a third, in whole kernel tiles, and never
    more than ``rows``.  One tile then covers a layer's held pairs unless the
    held experts draw a third over their share; a chip that holds every
    expert gets one tile of ``rows`` rows.  ``rows`` may be traced
    (``routing_metrics``)."""
    fill = rows * num_held // router_outputs
    whole = -(-(fill + fill // 3) // KERNEL_ROWS) * KERNEL_ROWS
    return min(rows, whole) if isinstance(rows, int) else jnp.minimum(rows, whole)


_DW_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _tile(t, tile: int, k: int, order, starts, ends, gates):
    """Tile ``t`` of the sorted pairs: (its rows' pairs, their tokens, which
    rows hold a held pair, their gates, the rows of each expert in it)."""
    first = t * tile
    pair = jax.lax.dynamic_slice(order, (first,), (tile,))
    live = first + jnp.arange(tile) < ends[-1]
    gate = jnp.where(live, gates.reshape(-1)[pair], 0.0)
    sizes = jnp.clip(ends, first, first + tile) - jnp.clip(starts, first, first + tile)
    return pair, pair // k, live, gate, sizes


def _walk(order, sizes, tile: int):
    """(``order`` padded to whole tiles, the experts' first rows and ends,
    the tiles that hold a held pair)."""
    ends = jnp.cumsum(sizes)
    order = jnp.pad(order, (0, -order.shape[0] % tile))
    return order, ends - sizes, ends, -(-ends[-1] // tile)


# The columns of one block of a token sum (``_zero_blocks``).
BLOCK_COLUMNS = 512


def _zero_blocks(u) -> tuple:
    """A zero token sum for ``u`` [tokens, d], float32, as the column blocks
    ``_combined`` adds into: blocks of ``BLOCK_COLUMNS`` and what is left of
    ``d``."""
    whole, rest = divmod(u.shape[1], BLOCK_COLUMNS)
    return tuple(jnp.zeros((u.shape[0], w), jnp.float32)
                 for w in [BLOCK_COLUMNS] * whole + [rest] * (rest > 0))


def _combined(y, token, rows):
    """The token sum ``y`` (``_zero_blocks``) with the float32 ``rows`` [R, d]
    added, each into the sum of its ``token``: one scatter-add a column
    block.  The sums and the order of their terms are those of one
    scatter-add of whole rows; what the compiler's scatter-add costs a row
    hangs on the width it adds into (``PERF.md`` section 6, PR 45)."""
    with jax.named_scope("combine"):
        lo, out = 0, []
        for block in y:
            out.append(block.at[token].add(rows[:, lo:lo + block.shape[1]]))
            lo += block.shape[1]
        return tuple(out)


def _products(xs, w13, w2, sizes, rule: str):
    """(h, a, ys) of a tile's rows ``xs``: ``ys = a @ w2`` by groups, ``a =
    silu(h1) * h3`` of ``[h1, h3] = xs @ w13`` (``swiglu``) or ``relu(h)^2``
    of ``h = xs @ w13`` (``relu2``: ``w13`` holds ``W_1`` alone)."""
    f = w2.shape[1]
    h = jax.lax.ragged_dot(xs, w13, sizes)
    a = jnp.square(jax.nn.relu(h)) if rule == "relu2" else jax.nn.silu(h[:, :f]) * h[:, f:]
    return h, a, jax.lax.ragged_dot(a, w2, sizes)


def _pulled(h, da, f: int, rule: str):
    """``da`` (float32) pulled back through ``_products``' rule to ``h``, in
    ``h``'s type."""
    if rule == "relu2":
        return (da * 2.0 * jax.nn.relu(h.astype(jnp.float32))).astype(h.dtype)
    h1, h3 = h[:, :f].astype(jnp.float32), h[:, f:].astype(jnp.float32)
    s = jax.nn.sigmoid(h1)
    return jnp.concatenate([da * h3 * s * (1.0 + h1 * (1.0 - s)), da * h1 * s],
                           axis=-1).astype(h.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def held_experts(u, w13, w2, gates, order, sizes, tile: int, rule: str = "swiglu"):
    """The held experts' part of the layer's sum, [tokens, d] in ``u``'s type.

    ``u`` [tokens, d]; ``w13`` [n, d, 2f] (``rule`` ``relu2``: [n, d, f], an
    expert's one input matrix), ``w2`` [n, f, d]; ``gates``
    [tokens, k], zero off the held range; ``order`` [tokens * k]: the pairs
    sorted by expert, the held ones first; ``sizes`` [n]: the pairs on each
    held expert.  The sorted pairs are walked ``tile`` rows at a time over the
    tiles that hold a held pair: gather the rows' tokens, multiply by groups,
    add ``gate * row`` into the token's float32 sum, which the walk carries
    as column blocks (``_zero_blocks``) and adds into a block at a time
    (``_combined``: the terms and their order are one scatter-add's over
    whole rows; the blocks are joined before the cast to ``u``'s type).  The
    backward pass walks the same tiles and computes each again, the tokens'
    gradient summed the same way: nothing of a tile is kept."""
    return _held_experts_fwd(u, w13, w2, gates, order, sizes, tile, rule)[0]


def _held_experts_fwd(u, w13, w2, gates, order, sizes, tile: int, rule: str):
    cd, k = u.dtype, gates.shape[1]
    with part("router"):
        padded, starts, ends, tiles = _walk(order, sizes, tile)
    with part("experts"):
        w13c, w2c = w13.astype(cd), w2.astype(cd)

    def body(t, y):
        with part("router"):
            _, token, live, gate, group = _tile(t, tile, k, padded, starts, ends, gates)
            xs = u[token]
        with part("experts"):
            _, _, ys = _products(xs, w13c, w2c, group, rule)
            # Rows past the last group are the kernel's to leave unwritten.
            ys = jnp.where(live[:, None], ys.astype(jnp.float32) * gate[:, None], 0.0)
        with part("router"):
            return _combined(y, token, ys)

    with part("router"):
        y = jax.lax.fori_loop(0, tiles, body, _zero_blocks(u))
        return jnp.concatenate(y, axis=1).astype(cd), (u, w13, w2, gates, order, sizes)


def _held_experts_bwd(tile: int, rule: str, kept, dy):
    u, w13, w2, gates, order, sizes = kept
    cd, k, f = u.dtype, gates.shape[1], w2.shape[1]
    # What the forward walk made and did not keep is made again, under a pass
    # of its own: the walk's bounds, the cast weights, a tile's rows and products.
    with part("router"), pass_("again"):
        padded, starts, ends, tiles = _walk(order, sizes, tile)
    with part("experts"):
        with pass_("again"):
            w13c, w2c = w13.astype(cd), w2.astype(cd)
        w13t, w2t = jnp.swapaxes(w13c, 1, 2), jnp.swapaxes(w2c, 1, 2)

    def body(t, carry):
        du, dw13, dw2, dgates = carry
        with part("router"):
            with pass_("again"):
                pair, token, live, gate, group = _tile(t, tile, k, padded, starts, ends, gates)
                xs = u[token]
            dy_rows = dy[token]
        with part("experts"):
            with pass_("again"):
                h, a, ys = _products(xs, w13c, w2c, group, rule)
            dy_rows = dy_rows.astype(jnp.float32)
            dgate = jnp.where(live, jnp.sum(dy_rows * ys.astype(jnp.float32), -1), 0.0)
            dys = jnp.where(live[:, None], dy_rows * gate[:, None], 0.0).astype(cd)
            da = jax.lax.ragged_dot(dys, w2t, group).astype(jnp.float32)
            dh = _pulled(h, da, f, rule)
            # The rows that ``h`` and ``da`` leave unwritten reach no sum: a
            # ragged contraction reads its groups' rows alone.
            dw2 = dw2 + jax.lax.ragged_dot_general(
                a, dys, group, _DW_DIMS, preferred_element_type=jnp.float32)
            dw13 = dw13 + jax.lax.ragged_dot_general(
                xs, dh, group, _DW_DIMS, preferred_element_type=jnp.float32)
            dxs = jax.lax.ragged_dot(dh, w13t, group)
            dxs = jnp.where(live[:, None], dxs, 0).astype(jnp.float32)
        with part("router"):
            return _combined(du, token, dxs), dw13, dw2, dgates.at[pair].add(dgate)

    with part("router"):
        zeros = lambda x: jnp.zeros(x.shape, jnp.float32)  # noqa: E731
        du, dw13, dw2, dgates = jax.lax.fori_loop(
            0, tiles, body, (_zero_blocks(u), zeros(w13), zeros(w2), zeros(gates.reshape(-1))))
        return (jnp.concatenate(du, axis=1).astype(cd), dw13.astype(w13.dtype),
                dw2.astype(w2.dtype), dgates.reshape(gates.shape).astype(gates.dtype), None, None)


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class ExpertShare(nn.Module):
    """One chip's share of a mixture-of-experts layer (module docstring)."""

    spec: TorsoSpec
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, u):
        sp, cd = self.spec, self.compute_dtype
        d, f, n, k = sp.hidden_size, sp.moe_intermediate_size, sp.num_held, sp.num_experts_per_tok
        lo, hi = sp.experts_held
        w_r = self.param("router", _lecun(), (d, sp.router_outputs), jnp.float32)
        # A buffer, not trained: it rides in the parameter tree (so that it
        # is saved, published and copied to the target network with the
        # rest) and no gradient reaches it.
        bias = (jax.lax.stop_gradient(self.param(
            "expert_bias", _bias_init, (sp.router_outputs,), jnp.float32))
                if sp.use_expert_bias else jnp.zeros((sp.router_outputs,), jnp.float32))
        # the experts' width: the layer's, or a latent between two shared projections
        width, relu2 = sp.moe_latent_size or d, sp.expert_rule == "relu2"
        w13 = self.param("w1" if relu2 else "w13", _lecun(batch_axis=(0,)),
                         (n, width, f if relu2 else 2 * f), self.param_dtype)
        w2 = self.param("w2", _lecun(batch_axis=(0,)), (n, f, width), self.param_dtype)
        if sp.moe_latent_size:
            w_down = self.param("w_down", _lecun(), (d, width), self.param_dtype)
            w_up = self.param("w_up", _lecun(), (width, d), self.param_dtype)
        shape = u.shape
        u = u.reshape(-1, d)
        rows = u.shape[0] * k  # every pair of every token

        with part("router"):
            logits = jnp.dot(u.astype(jnp.float32), w_r, precision=jax.lax.Precision.HIGHEST)
            scores = (jax.nn.sigmoid(logits) if sp.score_function == "sigmoid"
                      else jax.nn.softmax(logits, axis=-1))
            chosen, gates, kept = choose(scores, bias, sp)
            held = (chosen >= lo) & (chosen < hi)
            load = jnp.sum(jax.nn.one_hot(chosen.reshape(-1), sp.router_outputs,
                                          dtype=jnp.int32), axis=0)
            # Pairs on held experts first, by expert; the others after them.
            order = jnp.argsort(jnp.where(held, chosen - lo, n).reshape(-1), stable=True)
            gates = jnp.where(held, gates, 0.0)
        x = u.astype(cd)
        if sp.moe_latent_size:
            with part("latent_proj"):
                x = x @ w_down.astype(cd)
        y = held_experts(x, w13, w2, gates, order, load[lo:hi],
                         tile_rows(rows, n, sp.router_outputs), sp.expert_rule)
        if sp.moe_latent_size:
            with part("latent_proj"):
                y = y @ w_up.astype(cd)
        if not self.is_initializing():  # ``init`` returns parameters alone
            self.sow(ROUTING, "load", load)
            if kept is not None:    # tokens that keep a group with a held expert in it
                size = sp.router_outputs // sp.router_groups
                self.sow(ROUTING, "kept", jnp.sum(jnp.any(
                    kept[:, lo // size:(hi - 1) // size + 1], axis=-1).astype(jnp.int32)))
        return y.reshape(shape)


def _added(h, y, multiplier: float):
    """``h + multiplier y``, the product in float32; no operation at 1."""
    if multiplier == 1.0:
        return h + y
    return h + (multiplier * y.astype(jnp.float32)).astype(h.dtype)


class Block(nn.Module):
    spec: TorsoSpec
    op: str
    ffn: str
    compute_dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, h, _=None):
        """(h, None) -> (the layer's output, None): a ``scan``'s body."""
        sp, cd, pd = self.spec, self.compute_dtype, self.param_dtype
        m = sp.residual_multiplier
        mixer = dict(sp.mixers)[self.op](sp, self.op, cd, pd, name=self.op)
        if sp.post_norm:      # dense layers alone (the spec)
            with part("mixer"):
                h = _added(h, RMSNorm(sp.norm_eps, cd, pd, name="operator_norm")(mixer(h)), m)
            with part("dense_ffn"):
                y = SwiGLU(sp.intermediate_size, cd, pd, name="dense")(h)
                return _added(h, RMSNorm(sp.norm_eps, cd, pd, name="ffn_norm")(y), m), None
        with part("mixer"):
            u = RMSNorm(sp.norm_eps, cd, pd, name="operator_norm")(h)
            h = _added(h, mixer(u), m)
        if self.ffn == "none":
            return h, None
        if self.ffn == "dense":
            with part("dense_ffn"):
                u = RMSNorm(sp.norm_eps, cd, pd, name="ffn_norm")(h)
                return _added(h, SwiGLU(sp.intermediate_size, cd, pd, name="dense")(u), m), None
        with part("router"):
            u = RMSNorm(sp.norm_eps, cd, pd, name="ffn_norm")(h)
        y = ExpertShare(sp, cd, pd, name="moe")(u)
        if sp.shared_expert_intermediate_size:
            # Every chip of the layer computes it alike; it is added ungated.
            with part("shared_expert"):
                lo, hi = sp.shared_expert_held or (0, sp.shared_expert_intermediate_size)
                shared = Relu2 if sp.expert_rule == "relu2" else SwiGLU
                y = y + shared(hi - lo, cd, pd, name="shared_expert")(u)
        return _added(h, y, m), None


def _sown(sown, name: str) -> dict:
    """{the path of an expert layer: what it sowed under ``name``}."""
    return {jax.tree_util.keystr(path[:-2]): v
            for path, v in jax.tree_util.tree_leaves_with_path(sown[ROUTING])
            if getattr(path[-2], "key", None) == name}


class TorsoQ(nn.Module):
    """Stem -> tokens -> the spec's layers -> norm, mean over tokens -> dueling head."""

    num_actions: int
    spec: TorsoSpec
    channels: Sequence[int] = (32, 64, 64)
    hidden: int = 512
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @property
    def float32_leaves(self) -> tuple:
        """A target network in a lower type keeps these leaves in float32:
        the router's scores and bias decide a top-k; a family adds its own
        (a state-space layer's decay: the spec's)."""
        return ("router", "expert_bias") + tuple(self.spec.float32_leaves)

    @nn.compact
    def __call__(self, x):
        sp, cd, pd = self.spec, self.compute_dtype, self.param_dtype
        rows = x.shape[0]
        if sp.frame_history:  # [B, H, W, F] -> [B x F, H, W, 1], oldest frame first
            x = jnp.moveaxis(x, -1, 1).reshape(-1, *x.shape[1:3], 1)
        z = conv_stem(x, self.channels, cd, pd, out_dtype=jnp.float32)  # [B, h, w, C]
        with part("stem"):
            z = z.reshape(z.shape[0], -1, z.shape[-1])             # raster order
            z = (z - jnp.mean(z, axis=1, keepdims=True)).astype(cd)
            z = z.reshape(rows, -1, z.shape[-1])                   # time-major over a history
            w_tok = self.param("w_tok", _lecun(), (z.shape[-1], sp.hidden_size), pd)
            h = z @ w_tok.astype(cd)
            if sp.token_multiplier != 1.0:
                h = (sp.token_multiplier * h.astype(jnp.float32)).astype(cd)
        block = nn.remat(Block)
        for first, count, (op, ffn) in layer_runs(sp.layers):
            if count == 1:
                h, _ = block(sp, op, ffn, cd, pd, name=f"layer_{first}")(h)
            else:
                run = nn.scan(nn.remat(Block, prevent_cse=False),  # a scan's body is never merged
                              variable_axes={"params": 0, ROUTING: 0},
                              split_rngs={"params": True}, length=count)
                h, _ = run(sp, op, ffn, cd, pd,
                           name=f"layers_{first}_{first + count - 1}")(h, None)
        with part("head"):
            h = RMSNorm(sp.norm_eps, cd, pd, name="final_norm")(h)
            pooled = jnp.mean(h.astype(jnp.float32), axis=1).astype(cd)
        return dueling_head(pooled, self.num_actions, self.hidden, cd, pd)

    def tokens_of(self, obs_shape) -> int:
        """Tokens an observation of ``obs_shape`` ([.., H, W, C]) becomes."""
        h, w = obs_shape[-3], obs_shape[-2]
        for k, s in STEM_WINDOWS:
            h, w = (h - k) // s + 1, (w - k) // s + 1
        return h * w * (obs_shape[-1] if self.spec.frame_history else 1)

    def _counted(self, obs_shape, method: str) -> Optional[dict]:
        """The sum over the layers of what their mixers' static ``method``
        (spec, op, rows, tokens) gives for one forward of ``obs_shape``
        ([B, H, W, C]), from the shapes alone ({name: float}); None where no
        mixer has it."""
        out: dict = {}
        for op, _ in self.spec.layers:
            count = getattr(dict(self.spec.mixers)[op], method, None)
            if count is not None:
                for key, v in count(self.spec, op, obs_shape[0], self.tokens_of(obs_shape)).items():
                    out[key] = out.get(key, 0.0) + v
        return out or None

    def attention_metrics(self, obs_shape) -> Optional[dict]:
        """What the blocked attention mixers count of one forward (``count``:
        pairs in the mask, blocks visited and in all)."""
        return self._counted(obs_shape, "count")

    def scan_metrics(self, obs_shape) -> Optional[dict]:
        """What the state-space mixers count of one forward (``scan_count``:
        chunks walked, tokens with and without the padding to whole chunks)."""
        return self._counted(obs_shape, "scan_count")

    def delta_metrics(self, obs_shape) -> Optional[dict]:
        """What the delta-rule mixers count of one forward (``delta_count``:
        chunks walked, tokens with and without the padding to whole chunks)."""
        return self._counted(obs_shape, "delta_count")

    def q_values(self, x):
        return self(x)[2]

    def routing_metrics(self, sown) -> Optional[dict]:
        """What one ``apply(..., mutable=[ROUTING])`` sowed, as the train
        step's counters, summed over the expert layers (float32 [] each;
        None for a torso without experts):
        the pairs on held experts, the largest and the mean load of a held
        expert, and the rows the layers walked for them (tiles by the rows
        of a tile; every pair of a token is on one of the router's outputs,
        so a layer's loads add up to its ``tokens x k``)."""
        lo, hi = self.spec.experts_held
        if hi == lo:
            return None
        loads = jnp.concatenate([v.reshape(-1, v.shape[-1]) for v in _sown(sown, "load").values()])
        held = loads[:, lo:hi].astype(jnp.float32)
        tile = tile_rows(jnp.sum(loads, -1), hi - lo, self.spec.router_outputs)
        walked = -(-jnp.sum(loads[:, lo:hi], -1) // tile) * tile
        out = {"held_pairs": jnp.sum(held), "load_max": jnp.sum(jnp.max(held, -1)),
               "load_mean": jnp.sum(jnp.mean(held, -1)),
               "rows_walked": jnp.sum(walked).astype(jnp.float32)}
        if self.spec.router_groups > 1:
            # a forward's; the train step takes the mean of a name that ends in ``_share``
            tokens = jnp.sum(loads, -1) / self.spec.num_experts_per_tok
            kept = jnp.concatenate([v.reshape(-1) for v in _sown(sown, "kept").values()])
            out["groups_kept_hold_share"] = jnp.mean(kept / tokens)
        return out

    def rebalanced(self, params, sown):
        """``params`` after the balancing rule (module docstring) on the
        loads ``sown`` holds: every expert layer's bias moved against the
        load error of each of the router's outputs."""
        if not (self.spec.use_expert_bias and self.spec.num_held):
            return params
        # .../moe/load/0 in the collection is .../moe/expert_bias in params
        loads = {path: v.astype(jnp.float32) for path, v in _sown(sown, "load").items()}

        def leaf(path, x):
            if getattr(path[-1], "key", None) != "expert_bias":
                return x
            load = loads[jax.tree_util.keystr(path[1:-1])]
            error = load / jnp.mean(load, -1, keepdims=True) - 1.0
            return x - BIAS_UPDATE_RATE * jnp.clip(error, -1.0, 1.0)

        return jax.tree_util.tree_map_with_path(leaf, params)

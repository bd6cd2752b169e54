"""Operations and bytes a learner step of the Nemotron 3 Super Q-network over a
history of frames needs, from the configuration's shapes and the count of
token-expert pairs a run really routed to held experts.

Lower bounds, as ``ops_count.py``'s docstring sets out: three forwards and one
backward at twice a forward less the first convolution's input gradient; the
recomputation of every block and of every chunk in the backward pass, the
padding of a sequence to whole chunks or blocks, the tiles' rows past the held
pairs, the blocks' pairs outside the mask, the second visit of a block by the
backward kernels and the router's choice do not count.  Matrix products and
convolutions only: the projections (the two latent ones among them), the
shared expert's two, an expert's two a routed pair (``2 x moe_latent_size x
moe_intermediate_size`` multiply-adds: no gate matrix), attention's two
products over the pairs the causal mask lets through, and the scan **in its
chunked form at the published chunk size**: the scores ``C B^T`` over the pairs
``j <= i`` inside a chunk, **once a group held** and not once for all heads,
the product of those pairs with ``dt x`` a head, and the two products with the
state a token (``C S`` and ``x B^T``).  The depthwise convolution's four taps,
the decays, norms, gates, activations and softmax count nothing.  The count
reads the same work whatever implements it.

What is counted is what the chip holds (``ops_count_solar2_q.py`` counts a
share of heads the same way): ``mamba_num_heads``, ``num_attention_heads`` and
``num_key_value_heads`` are the held counts, the groups held are
``mamba_num_heads`` over the published heads a group, the shared expert's
columns ``shared_expert_held``, the experts ``experts_held`` from
``held_pairs_per_step``.
"""

from __future__ import annotations

import ops_count as dueling_count
# What is the same arithmetic whatever the layers are: the tokens of a history,
# the pairs in a causal mask, the stem's and the head's.
from ops_count_solar2_q import (  # noqa: F401  (re-exported under the names the readers call)
    _DTYPE_BYTES,
    pairs_in_mask,
    stem_and_head_flops,
    tokens_per_sample,
)

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def layer_kinds(cfg: dict) -> list:
    """The kinds of the one-sublayer layers run."""
    pattern = cfg["hybrid_override_pattern"]
    return [KINDS[pattern[i]] for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def layers_of(cfg: dict, kind: str) -> int:
    return sum(1 for k in layer_kinds(cfg) if k == kind)


def mamba_sizes(cfg: dict) -> tuple:
    """(inner width held, groups held x state, heads held)."""
    heads = cfg["mamba_num_heads"]
    per_group = cfg["published"]["mamba_num_heads"] // cfg["n_groups"]
    return heads * cfg["mamba_head_dim"], heads // per_group * cfg["ssm_state_size"], heads


def shared_columns(cfg: dict) -> int:
    lo, hi = cfg.get("shared_expert_held") or (
        0, cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"])
    return hi - lo


def pairs_in_chunks(cfg: dict) -> int:
    """Pairs ``j <= i`` a sample with both tokens in one chunk."""
    q = cfg["chunk_size"]
    whole, last = divmod(tokens_per_sample(cfg), q)
    return whole * (q * (q + 1) // 2) + last * (last + 1) // 2


def attention_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of q k^T and p v in the attention
    layers: ``2 x head_dim`` a held query head and in-mask pair."""
    return (layers_of(cfg, "attention") * 2 * cfg["head_dim"] * cfg["num_attention_heads"]
            * pairs_in_mask(cfg))


def scan_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of the chunked scan's products in
    the state-space layers (module docstring): ``groups x state`` a pair for
    the scores of every group held, ``inner`` for the pairs' product with
    ``dt x``, ``2 x inner x state`` a token with the state."""
    inner, gn, _ = mamba_sizes(cfg)
    n = cfg["ssm_state_size"]
    return layers_of(cfg, "mamba") * (
        pairs_in_chunks(cfg) * (gn + inner) + tokens_per_sample(cfg) * 2 * inner * n)


def expert_macs_per_pair(cfg: dict) -> int:
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_step_flops(cfg: dict, held_pairs_per_step: float) -> float:
    """The grouped products' FLOPs a step: every counted pair forward, the
    third of them that is differentiated twice more."""
    return 2.0 * expert_macs_per_pair(cfg) * held_pairs_per_step * (1.0 + 2.0 / 3.0)


def expected_pairs_per_step(cfg: dict) -> float:
    """Pairs on held experts a step if every expert drew the same load."""
    lo, hi = cfg["experts_held"]
    return (3.0 * cfg["batch_size"] * tokens_per_sample(cfg) * cfg["num_experts_per_tok"]
            * (hi - lo) / cfg["router_outputs"] * layers_of(cfg, "moe"))


def macs_per_token(cfg: dict) -> dict:
    """{part: multiply-adds a token a forward}: everything a token costs
    whatever the others are (the products over pairs and the experts left
    out)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    inner, gn, heads = mamba_sizes(cfg)
    routing = layers_of(cfg, "moe")
    return dict(
        tokens=cfg["channels"][-1] * d,
        mixer=(layers_of(cfg, "mamba") * (d * (2 * inner + 2 * gn + heads) + inner * d)
               + layers_of(cfg, "attention") * (2 * d * h * hd + 2 * d * kv * hd)),
        router=routing * d * cfg["router_outputs"],
        latent_proj=routing * 2 * d * cfg["moe_latent_size"],
        shared_expert=routing * 2 * d * shared_columns(cfg))


def dense_flops_per_sample(cfg: dict) -> tuple:
    """(forward, backward) FLOPs a sample of everything but the experts."""
    stem, head, first = stem_and_head_flops(cfg)
    forward = (stem + head + 2 * tokens_per_sample(cfg) * sum(macs_per_token(cfg).values())
               + 2 * attention_macs_per_sample(cfg) + 2 * scan_macs_per_sample(cfg))
    return forward, 2 * forward - first


def step_flops(cfg: dict, held_pairs_per_step: float) -> float:
    forward, backward = dense_flops_per_sample(cfg)
    return cfg["batch_size"] * (3 * forward + backward) + expert_step_flops(
        cfg, held_pairs_per_step)


def flops_per_sample(cfg: dict, held_pairs_per_step: float) -> float:
    return step_flops(cfg, held_pairs_per_step) / cfg["batch_size"]


def layer_param_count(cfg: dict, kind: str) -> int:
    """One published layer's parameters held here, its norm among them."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    if kind == "attention":
        return d + 2 * d * cfg["num_attention_heads"] * hd + 2 * d * cfg["num_key_value_heads"] * hd
    if kind == "mamba":
        inner, gn, heads = mamba_sizes(cfg)
        mixed = inner + 2 * gn
        return (d + d * (inner + mixed + heads) + mixed * cfg["conv_kernel"] + mixed
                + 3 * heads + inner + inner * d)
    lo, hi = cfg["experts_held"]
    return (d + d * cfg["router_outputs"] + cfg["router_outputs"] + 2 * d * cfg["moe_latent_size"]
            + 2 * d * shared_columns(cfg) + (hi - lo) * expert_macs_per_pair(cfg))


def param_count(cfg: dict) -> int:
    d = cfg["hidden_size"]
    h, w, _ = cfg["obs_shape"]
    rows = dueling_count.layer_table(dict(cfg, obs_shape=[h, w, 1]))
    hid, a = cfg["hidden"], cfg["num_actions"]
    n = sum(p for _, _, p, _ in rows[:3]) + cfg["channels"][-1] * d + d
    n += 2 * (d * hid + hid) + hid + 1 + hid * a + a
    return n + sum(layer_param_count(cfg, kind) for kind in layer_kinds(cfg))


def expert_param_count(cfg: dict) -> int:
    lo, hi = cfg["experts_held"]
    return layers_of(cfg, "moe") * (hi - lo) * expert_macs_per_pair(cfg)


def _longer(t_flops: float, t_bytes: float) -> tuple:
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def attention_floor_s(cfg: dict, peaks: dict, kind: str = "full") -> tuple:
    """Least seconds a step's masked products of the attention layer can take,
    at 8 query heads on one key-value head: ``4 x head_dim`` FLOPs a held
    query head, in-mask pair and forward, three forwards and a backward at
    twice a forward, over the peak; or the reads of q, k and v and the write
    of the output a forward, and for the backward the reads of q, k, v, the
    output and its gradient and the writes of the three gradients, in the
    compute type (a key and a value once a key-value head, not a query head),
    whichever is longer."""
    if kind != "full":
        raise ValueError(f"this network's attention layers are causal alone, not {kind!r}")
    b, t, hd = cfg["batch_size"], tokens_per_sample(cfg), cfg["head_dim"]
    t_flops = 5 * 2 * attention_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    layers = layers_of(cfg, "attention")
    heads, kv = layers * cfg["num_attention_heads"], layers * cfg["num_key_value_heads"]
    forward = (2 * heads + 2 * kv) * t * hd * size
    backward = (4 * heads + 4 * kv) * t * hd * size
    return _longer(t_flops, b * (3 * forward + backward) / peaks["hbm_bytes_per_s"])


def scan_floor_s(cfg: dict, peaks: dict) -> tuple:
    """Least seconds a step's scans can take: the chunked form's products
    (``scan_macs_per_sample``: chunks of 128, the scores a group), three
    forwards and a backward at twice a forward, over the peak; or, a pass, the
    reads of ``x``, ``B`` and ``C`` in the compute type and of ``dt`` in
    float32 and the write of ``y``, the backward pass at twice a forward's,
    over the bandwidth; whichever is longer."""
    b, t = cfg["batch_size"], tokens_per_sample(cfg)
    inner, gn, heads = mamba_sizes(cfg)
    t_flops = 5 * 2 * scan_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    a_pass = layers_of(cfg, "mamba") * t * ((2 * inner + 2 * gn) * size + heads * 4)
    return _longer(t_flops, 5 * b * a_pass / peaks["hbm_bytes_per_s"])


def step_floor_s(cfg: dict, peaks: dict, held_pairs_per_step: float) -> tuple:
    """Least seconds a whole step can take: its FLOPs over the peak, or one
    read of every parameter in the compute type for each of the three
    forwards and two for the backward, whichever is longer."""
    t_flops = step_flops(cfg, held_pairs_per_step) / peaks["flops_per_s_bf16"]
    return _longer(t_flops, 5 * param_count(cfg) * _DTYPE_BYTES[cfg["precision"]["compute"]]
                   / peaks["hbm_bytes_per_s"])


def expert_floor_s(cfg: dict, peaks: dict, held_pairs_per_step: float) -> tuple:
    """Least seconds a step's grouped products can take: their FLOPs (two
    matrices of ``moe_latent_size x moe_intermediate_size`` a pair) over the
    peak, or one read of the held experts' weights in the compute type for
    each of the three forwards and two for the backward, whichever is larger."""
    t_flops = expert_step_flops(cfg, held_pairs_per_step) / peaks["flops_per_s_bf16"]
    return _longer(t_flops, 5 * expert_param_count(cfg) * _DTYPE_BYTES[cfg["precision"]["compute"]]
                   / peaks["hbm_bytes_per_s"])

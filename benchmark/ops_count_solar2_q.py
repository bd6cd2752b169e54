"""Operations and bytes a learner step of the Solar-Open2 Q-network over a
history of frames needs, from the configuration's shapes and the count of
token-expert pairs a run really routed to held experts.

Lower bounds, as ``ops_count.py``'s docstring sets out: three forwards and
one backward at twice a forward less the first convolution's input gradient;
the recomputation of every layer in the backward pass, the padding of a
sequence to whole chunks or blocks, the blocks' pairs outside the mask, the
triangular solve's own substitutions, the sorts and the gathers do not
count.  Matrix products and convolutions only; the softmax layer's two
products over the pairs the causal mask lets through; the delta-rule layers'
chunked form over the in-chunk pairs ``j <= i`` (``delta_macs_per_sample``);
elementwise work, norms, softmax and the router's top-k count nothing.  The
count reads the same work whatever implements it.

The heads and experts counted are those the configuration holds
(``num_attention_heads``, ``num_key_value_heads``, ``linear_attn_config``'s
``num_heads`` and ``experts_held`` give this chip's share).  The experts are
counted from ``held_pairs_per_step``, as ``ops_count_laguna_q.py`` counts
them.
"""

from __future__ import annotations

import ops_count as dueling_count

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
CHUNK = 64
OPS = ("linear_attention", "full_attention")


def layer_kinds(cfg: dict) -> list:
    """The layer types run: ``layers_held`` of the pattern ``gqa_layers`` gives."""
    gqa = set(cfg["gqa_layers"])
    return [OPS[i in gqa] for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def layers_of(cfg: dict, op: str) -> int:
    return sum(1 for kind in layer_kinds(cfg) if kind == op)


def tokens_per_sample(cfg: dict) -> int:
    return dueling_count.conv_output_sizes(cfg["obs_shape"][0])[-1] ** 2 * cfg["obs_shape"][2]


def linear_sizes(cfg: dict) -> tuple:
    """(heads held, a head's width, the convolutions' taps, the gates' rank)."""
    lin = cfg["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            cfg.get("kda_gate_rank", lin["head_dim"]))


def pairs_in_mask(cfg: dict) -> int:
    """(query, key) pairs a sample that the causal mask lets through."""
    t = tokens_per_sample(cfg)
    return t * (t + 1) // 2


def pairs_in_chunks(cfg: dict) -> int:
    """Pairs ``j <= i`` a sample inside the chunks of the delta-rule scan:
    whole chunks and the last one's own tokens, no padding."""
    c = cfg.get("kda_chunk_size", CHUNK)
    whole, rest = divmod(tokens_per_sample(cfg), c)
    return whole * c * (c + 1) // 2 + rest * (rest + 1) // 2


def delta_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of the chunked delta rule in all
    the linear layers, a head of K = V: over the in-chunk pairs ``A`` (K), the
    q-k scores (K), ``T`` applied to ``W`` and ``U`` (K + V) and the scores to
    ``V'`` (V); over the tokens the two products with the incoming state and
    the state's update (K V each)."""
    heads, hd, _, _ = linear_sizes(cfg)
    a_head = 5 * hd * pairs_in_chunks(cfg) + 3 * hd * hd * tokens_per_sample(cfg)
    return layers_of(cfg, "linear_attention") * heads * a_head


def attention_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of q k^T and p v in the softmax
    layers: ``2 x head_dim x heads`` an in-mask pair."""
    return (layers_of(cfg, "full_attention") * 2 * cfg["head_dim"] * cfg["num_attention_heads"]
            * pairs_in_mask(cfg))


def expected_pairs_per_step(cfg: dict) -> float:
    """Pairs on held experts a step if every expert drew the same load."""
    lo, hi = cfg["experts_held"]
    return (3.0 * cfg["batch_size"] * tokens_per_sample(cfg) * cfg["num_experts_per_tok"]
            * (hi - lo) / cfg["router_outputs"] * len(layer_kinds(cfg)))


def expert_macs_per_pair(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def mixer_macs_per_token(cfg: dict, op: str) -> int:
    """A layer's projections (and convolutions) a token a forward."""
    d = cfg["hidden_size"]
    if op == "full_attention":
        h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        return 2 * d * h * hd + 2 * d * kv * hd + (d * h * hd if cfg.get("use_gqa_gate") else 0)
    n, hd, taps, rank = linear_sizes(cfg)
    return (4 * d * n * hd + 3 * n * hd * taps          # q, k, v, o; their convolutions
            + 2 * (d * rank + rank * n * hd) + d * n)   # the two low-rank gates; beta


def macs_per_token(cfg: dict) -> dict:
    """{part: multiply-adds a token a forward}: everything a token costs
    whatever the others are (the two mixers' products over pairs and the
    experts left out)."""
    d = cfg["hidden_size"]
    layers = len(layer_kinds(cfg))
    return dict(tokens=cfg["channels"][-1] * d,
                mixer=sum(mixer_macs_per_token(cfg, op) for op in layer_kinds(cfg)),
                router=layers * d * cfg["router_outputs"],
                shared_expert=layers * 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"])


def stem_and_head_flops(cfg: dict) -> tuple:
    """(forward FLOPs a sample of the three convolutions over the history's
    frames, each alone; of the two streams and heads; of the first
    convolution alone)."""
    h, w, frames = cfg["obs_shape"]
    rows = dueling_count.layer_table(dict(cfg, obs_shape=[h, w, 1]))
    hid, a, d = cfg["hidden"], cfg["num_actions"], cfg["hidden_size"]
    head = 2 * (2 * d * hid + hid + hid * a)
    return frames * sum(f for _, f, _, _ in rows[:3]), head, frames * rows[0][1]


def dense_flops_per_sample(cfg: dict) -> tuple:
    """(forward, backward) FLOPs a sample of everything but the experts."""
    stem, head, first = stem_and_head_flops(cfg)
    forward = (stem + head + 2 * tokens_per_sample(cfg) * sum(macs_per_token(cfg).values())
               + 2 * attention_macs_per_sample(cfg) + 2 * delta_macs_per_sample(cfg))
    return forward, 2 * forward - first


def expert_step_flops(cfg: dict, held_pairs_per_step: float) -> float:
    """The grouped products' FLOPs a step: every counted pair forward, the
    third of them that is differentiated twice more."""
    return 2.0 * expert_macs_per_pair(cfg) * held_pairs_per_step * (1.0 + 2.0 / 3.0)


def step_flops(cfg: dict, held_pairs_per_step: float) -> float:
    forward, backward = dense_flops_per_sample(cfg)
    return cfg["batch_size"] * (3 * forward + backward) + expert_step_flops(
        cfg, held_pairs_per_step)


def flops_per_sample(cfg: dict, held_pairs_per_step: float) -> float:
    return step_flops(cfg, held_pairs_per_step) / cfg["batch_size"]


def mixer_param_count(cfg: dict, op: str) -> int:
    if op == "full_attention":
        return mixer_macs_per_token(cfg, op)              # matrices alone
    n, hd, _, _ = linear_sizes(cfg)
    return mixer_macs_per_token(cfg, op) + n + 2 * n * hd + hd   # A_log, dt_bias, b_g, the norm


def expert_layer_param_count(cfg: dict) -> int:
    """Router and its bias, the shared experts, the held experts."""
    d, lo_hi = cfg["hidden_size"], cfg["experts_held"]
    return (d * cfg["router_outputs"] + cfg["router_outputs"]
            + 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
            + (lo_hi[1] - lo_hi[0]) * expert_macs_per_pair(cfg))


def layers_param_count(cfg: dict) -> int:
    """The layers run, each with its two norms."""
    return sum(mixer_param_count(cfg, op) + expert_layer_param_count(cfg) + 2 * cfg["hidden_size"]
               for op in layer_kinds(cfg))


def param_count(cfg: dict) -> int:
    d = cfg["hidden_size"]
    h, w, _ = cfg["obs_shape"]
    rows = dueling_count.layer_table(dict(cfg, obs_shape=[h, w, 1]))
    hid, a = cfg["hidden"], cfg["num_actions"]
    n = sum(p for _, _, p, _ in rows[:3]) + cfg["channels"][-1] * d + d
    n += 2 * (d * hid + hid) + hid + 1 + hid * a + a
    return n + layers_param_count(cfg)


def expert_param_count(cfg: dict) -> int:
    lo, hi = cfg["experts_held"]
    return len(layer_kinds(cfg)) * (hi - lo) * expert_macs_per_pair(cfg)


def attention_floor_s(cfg: dict, peaks: dict, kind: str = "full") -> tuple:
    """Least seconds a step's masked products of the softmax layers can take
    (``ops_count_laguna_q.attention_floor_s``, over the heads held): their
    FLOPs, three forwards and a backward at twice a forward, over the peak; or
    the reads of q, k and v and the write of the output a forward, and for
    the backward the reads of q, k, v, the output and its gradient and the
    writes of the three gradients, in the compute type, whichever is longer."""
    b, t, hd = cfg["batch_size"], tokens_per_sample(cfg), cfg["head_dim"]
    layers = layers_of(cfg, "full_attention")
    heads, kv = layers * cfg["num_attention_heads"], layers * cfg["num_key_value_heads"]
    t_flops = 5 * 2 * attention_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    forward = (2 * heads + 2 * kv) * t * hd * size
    backward = (4 * heads + 4 * kv) * t * hd * size
    t_bytes = b * (3 * forward + backward) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def delta_floor_s(cfg: dict, peaks: dict) -> tuple:
    """Least seconds a step's delta-rule scans can take: the chunked form's
    products (``delta_macs_per_sample``), three forwards and a backward at
    twice a forward, over the peak; or, a pass, the reads of q, k and v in the
    compute type and of g and beta in float32 and the write of o, the
    backward pass at twice a forward's, over the bandwidth; whichever is
    longer."""
    b, t = cfg["batch_size"], tokens_per_sample(cfg)
    heads, hd, _, _ = linear_sizes(cfg)
    t_flops = 5 * 2 * delta_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    a_pass = layers_of(cfg, "linear_attention") * t * heads * (4 * hd * size + hd * 4 + 4)
    t_bytes = 5 * b * a_pass / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def expert_floor_s(cfg: dict, peaks: dict, held_pairs_per_step: float) -> tuple:
    """Least seconds a step's grouped products can take: their FLOPs over the
    peak, or one read of the held experts' weights in the compute type for
    each of the three forwards and two for the backward, whichever is larger."""
    t_flops = expert_step_flops(cfg, held_pairs_per_step) / peaks["flops_per_s_bf16"]
    t_bytes = (5 * expert_param_count(cfg) * _DTYPE_BYTES[cfg["precision"]["compute"]]
               / peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")

"""Operations and bytes a learner step of the Laguna Q-network over a history
of frames needs, from the configuration's shapes and the count of
token-expert pairs a run really routed to held experts.

Lower bounds, as ``ops_count.py``'s docstring sets out: three forwards and
one backward at twice a forward less the first convolution's input gradient;
the recomputation of every layer in the backward pass, the padding of a
sequence to whole blocks, the blocks' pairs outside the mask, RoPE's
products, the sorts and the gathers do not count.  Matrix products and
convolutions only; attention's two products over the pairs the mask lets
through (``pairs_in_mask``: key ``j`` for query ``i`` if ``j <= i`` and, on
sliding layers, ``j > i - sliding_window``); elementwise work, norms, softmax
and the router's top-k count nothing.

The experts are counted from ``held_pairs_per_step``: the pairs on held
experts summed over a step's three forwards, which the fused call's metrics
carry.  A third of them belong to the forward that is differentiated.
"""

from __future__ import annotations

import ops_count as dueling_count

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
KINDS = ("full", "window")    # causal alone (full_attention), inside the window (sliding_attention)


def layer_kinds(cfg: dict) -> list:
    """[(op, ffn, heads)] of the layers run."""
    held = cfg.get("layers_held", range(cfg["num_hidden_layers"]))
    return [(cfg["layer_types"][i],
             "dense" if cfg["mlp_layer_types"][i] == "dense" else "moe",
             cfg["num_attention_heads_per_layer"][i]) for i in held]


def tokens_per_sample(cfg: dict) -> int:
    return dueling_count.conv_output_sizes(cfg["obs_shape"][0])[-1] ** 2 * cfg["obs_shape"][2]


def pairs_in_mask(cfg: dict, kind: str) -> int:
    """(query, key) pairs a sample that a layer of ``kind`` lets through."""
    t = tokens_per_sample(cfg)
    w = t if kind == "full" else min(cfg["sliding_window"], t)
    return w * (w + 1) // 2 + (t - w) * w


def _kind_of(op: str) -> str:
    return "full" if op == "full_attention" else "window"


def attention_macs_per_sample(cfg: dict, kind: str) -> int:
    """Multiply-adds a sample and forward of q k^T and p v in the layers of
    ``kind``: ``2 x head_dim x heads`` an in-mask pair."""
    return sum(2 * cfg["head_dim"] * heads * pairs_in_mask(cfg, kind)
               for op, _, heads in layer_kinds(cfg) if _kind_of(op) == kind)


def expected_pairs_per_step(cfg: dict) -> float:
    """Pairs on held experts a step if every expert drew the same load."""
    lo, hi = cfg["experts_held"]
    n_moe = sum(1 for _, ffn, _ in layer_kinds(cfg) if ffn == "moe")
    return (3.0 * cfg["batch_size"] * tokens_per_sample(cfg) * cfg["num_experts_per_tok"]
            * (hi - lo) / cfg["router_outputs"] * n_moe)


def expert_macs_per_pair(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def macs_per_token(cfg: dict) -> dict:
    """{part: multiply-adds a token a forward}: everything a token costs
    whatever the others are (attention's products and the experts left out)."""
    d, kv, hd = cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"]
    out = dict(tokens=cfg["channels"][-1] * d, mixer=0, router=0, dense_ffn=0, shared_expert=0)
    for _, ffn, heads in layer_kinds(cfg):
        out["mixer"] += 2 * d * heads * hd + 2 * d * kv * hd + d * heads   # q, o; k, v; head gate
        if ffn == "dense":
            out["dense_ffn"] += 3 * d * cfg["intermediate_size"]
        else:
            out["router"] += d * cfg["router_outputs"]
            out["shared_expert"] += 3 * d * cfg["shared_expert_intermediate_size"]
    return out


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
               + 2 * sum(attention_macs_per_sample(cfg, k) for k in KINDS))
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


def param_count(cfg: dict) -> int:
    d, kv, hd = cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"]
    lo, hi = cfg["experts_held"]
    h, w, _ = cfg["obs_shape"]
    rows = dueling_count.layer_table(dict(cfg, obs_shape=[h, w, 1]))
    hid, a = cfg["hidden"], cfg["num_actions"]
    n = sum(p for _, _, p, _ in rows[:3]) + cfg["channels"][-1] * d + d
    n += 2 * (d * hid + hid) + hid + 1 + hid * a + a
    for _, ffn, heads in layer_kinds(cfg):
        n += 2 * d + 2 * d * heads * hd + 2 * d * kv * hd + d * heads
        if ffn == "dense":
            n += 3 * d * cfg["intermediate_size"]
        else:
            n += (d * cfg["router_outputs"] + 3 * d * cfg["shared_expert_intermediate_size"]
                  + (hi - lo) * expert_macs_per_pair(cfg))
    return n


def expert_param_count(cfg: dict) -> int:
    lo, hi = cfg["experts_held"]
    return sum((hi - lo) * expert_macs_per_pair(cfg)
               for _, ffn, _ in layer_kinds(cfg) if ffn == "moe")


def attention_floor_s(cfg: dict, peaks: dict, kind: str) -> tuple:
    """Least seconds a step's masked products of the layers of ``kind`` can
    take: ``4 x head_dim x heads`` FLOPs an in-mask pair and forward, three
    forwards and a backward at twice a forward, over the peak; or the reads of
    q, k and v and the write of the output a forward, and for the backward the
    reads of q, k, v, the output and its gradient and the writes of the three
    gradients, in the compute type, whichever is longer."""
    b, t, hd, kv = cfg["batch_size"], tokens_per_sample(cfg), cfg["head_dim"], cfg["num_key_value_heads"]
    t_flops = 5 * 2 * attention_macs_per_sample(cfg, kind) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    heads = sum(h for op, _, h in layer_kinds(cfg) if _kind_of(op) == kind)
    layers = sum(1 for op, _, _ in layer_kinds(cfg) if _kind_of(op) == kind)
    forward = (2 * heads + 2 * kv * layers) * t * hd * size
    backward = (4 * heads + 4 * kv * layers) * t * hd * size
    t_bytes = b * (3 * forward + backward) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def expert_floor_s(cfg: dict, peaks: dict, held_pairs_per_step: float) -> tuple:
    """Least seconds a step's grouped products can take: their FLOPs over the
    peak, or one read of the held experts' weights in the compute type for
    each of the three forwards and two for the backward, whichever is larger."""
    t_flops = expert_step_flops(cfg, held_pairs_per_step) / peaks["flops_per_s_bf16"]
    t_bytes = (5 * expert_param_count(cfg) * _DTYPE_BYTES[cfg["precision"]["compute"]]
               / peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")

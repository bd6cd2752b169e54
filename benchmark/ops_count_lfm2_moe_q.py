"""Operations and bytes a learner step of the LFM2-MoE Q-network needs, from
the configuration's shapes and the count of token-expert pairs a run really
routed to held experts.

Lower bounds, as ``ops_count.py``'s docstring sets out: three forwards and
one backward at twice a forward less the first convolution's input gradient;
the recomputation of every layer in the backward pass, the rows of the pair
buffer past the last pair, the sorts and the gathers do not count.  Matrix
products and convolutions only (the attention's two products over the
causal half included); elementwise work, norms, softmax and the router's
top-k count nothing.

The experts are counted from ``held_pairs_per_step``: the pairs on held
experts summed over a step's three forwards, which the fused call's metrics
carry.  A third of them belong to the forward that is differentiated.
"""

from __future__ import annotations

import ops_count as dueling_count

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def layer_kinds(cfg: dict) -> list:
    held = cfg.get("layers_held", range(cfg["num_hidden_layers"]))
    return [(cfg["layer_types"][i], "dense" if i < cfg["num_dense_layers"] else "moe")
            for i in held]


def tokens_per_sample(cfg: dict) -> int:
    return dueling_count.conv_output_sizes(cfg["obs_shape"][0])[-1] ** 2


def expected_pairs_per_step(cfg: dict) -> float:
    """Pairs on held experts a step if every expert drew the same load."""
    lo, hi = cfg["experts_held"]
    n_moe = sum(1 for _, ffn in layer_kinds(cfg) if ffn == "moe")
    return (3.0 * cfg["batch_size"] * tokens_per_sample(cfg) * cfg["num_experts_per_tok"]
            * (hi - lo) / cfg["router_outputs"] * n_moe)


def expert_macs_per_pair(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def macs_per_token(cfg: dict) -> dict:
    """{part: multiply-adds a token a forward}, the experts left out."""
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    s = tokens_per_sample(cfg)
    out = dict(tokens=cfg["channels"][-1] * d, mixer=0, router=0, dense_ffn=0)
    for op, ffn in layer_kinds(cfg):
        if op == "conv":
            out["mixer"] += d * 3 * d + d * d + cfg["conv_L_cache"] * d
        else:  # q, k, v, o, and q k^T and p v over the causal half
            out["mixer"] += 2 * d * heads * hd + 2 * d * kv * hd + heads * hd * (s + 1)
        if ffn == "dense":
            out["dense_ffn"] += 3 * d * cfg["intermediate_size"]
        else:
            out["router"] += d * cfg["router_outputs"]
    return out


def stem_and_head_flops(cfg: dict) -> tuple:
    """(forward FLOPs a sample of the three convolutions, of the two streams
    and heads, of the first convolution alone)."""
    rows = dueling_count.layer_table(cfg)
    hid, a, d = cfg["hidden"], cfg["num_actions"], cfg["hidden_size"]
    head = 2 * (2 * d * hid + hid + hid * a)
    return sum(f for _, f, _, _ in rows[:3]), head, rows[0][1]


def dense_flops_per_sample(cfg: dict) -> tuple:
    """(forward, backward) FLOPs a sample of everything but the experts."""
    stem, head, first = stem_and_head_flops(cfg)
    per_token = macs_per_token(cfg)
    forward = stem + head + 2 * tokens_per_sample(cfg) * sum(per_token.values())
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
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    lo, hi = cfg["experts_held"]
    rows = dueling_count.layer_table(cfg)
    hid, a = cfg["hidden"], cfg["num_actions"]
    n = sum(p for _, _, p, _ in rows[:3]) + cfg["channels"][-1] * d + d
    n += 2 * (d * hid + hid) + hid + 1 + hid * a + a
    for op, ffn in layer_kinds(cfg):
        n += 2 * d
        if op == "conv":
            n += d * 3 * d + d * cfg["conv_L_cache"] + d * d
        else:
            n += 2 * d * heads * hd + 2 * d * kv * hd + 2 * hd
        if ffn == "dense":
            n += 3 * d * cfg["intermediate_size"]
        else:
            n += d * cfg["router_outputs"] + cfg["router_outputs"] + (hi - lo) * expert_macs_per_pair(cfg)
    return n


def expert_param_count(cfg: dict) -> int:
    lo, hi = cfg["experts_held"]
    return sum((hi - lo) * expert_macs_per_pair(cfg)
               for _, ffn in layer_kinds(cfg) if ffn == "moe")


def step_bytes(cfg: dict) -> int:
    """HBM bytes one chip must move a step: the optimizer's pass over the
    parameters (read online, target and moment, write online and moment),
    the gathered rows and the ring's masses, as ``ops_count.step_bytes``."""
    prec = cfg["precision"]
    pb, tb, mb = (_DTYPE_BYTES[prec[k]] for k in ("params", "target_params", "second_moment"))
    h, w, c = cfg["obs_shape"]
    rows = cfg["batch_size"] * (2 * h * w * c + 5 * 4)
    mass = cfg["replay_capacity"] * 4
    if cfg["sample_ahead"]:
        mass //= cfg["steps_per_call"]
    return param_count(cfg) * (2 * pb + tb + 2 * mb) + rows + mass


def step_floor_s(cfg: dict, peaks: dict, held_pairs_per_step: float) -> tuple:
    t_flops = step_flops(cfg, held_pairs_per_step) / peaks["flops_per_s_bf16"]
    t_bytes = step_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def expert_floor_s(cfg: dict, peaks: dict, held_pairs_per_step: float) -> tuple:
    """Least seconds a step's grouped products can take: their FLOPs over the
    peak, or one read of the held experts' weights in the compute type for
    each of the three forwards and two for the backward, whichever is larger."""
    t_flops = expert_step_flops(cfg, held_pairs_per_step) / peaks["flops_per_s_bf16"]
    t_bytes = (5 * expert_param_count(cfg) * _DTYPE_BYTES[cfg["precision"]["compute"]]
               / peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")

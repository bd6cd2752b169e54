"""Operations and bytes a learner step of the Kanana-2 Q-network over a history
of frames needs, from the configuration's shapes and the count of token-expert
pairs a run really routed to held experts.

``ops_count_ling3_q.py``'s rules, for a stack whose every layer is latent
attention: lower bounds, as ``ops_count.py``'s docstring sets out: three
forwards and one backward at twice a forward less the first convolution's
input gradient; the recomputation of every layer in the backward pass, the
padding of a sequence to whole blocks, the blocks' pairs outside the mask, the
second visit of a block by the backward kernels and the router's choice do not
count.  Matrix products and convolutions only; the latent layers' products
over the pairs the causal mask lets through (``2 x (192 + 128)`` FLOPs a pair,
head and forward: the scores' two parts and the values); elementwise work,
norms, softmax and RoPE count nothing.  The count reads the same work whatever
implements it.

Every head is held (``num_attention_heads`` is the published count) and the
experts counted are those the configuration holds (``experts_held``), from
``held_pairs_per_step``, as ``ops_count_laguna_q.py`` counts them.
"""

from __future__ import annotations

import ops_count as dueling_count
# What is the same arithmetic whatever the layers are: the tokens of a history,
# the pairs in a causal mask, an expert's products, the stem's and the head's.
from ops_count_solar2_q import (  # noqa: F401  (re-exported under the names the readers call)
    _DTYPE_BYTES,
    expert_macs_per_pair,
    expert_step_flops,
    pairs_in_mask,
    stem_and_head_flops,
    tokens_per_sample,
)

OP = "latent_attention"


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] of the layers run: ``layers_held`` of the published
    depth, dense before ``first_k_dense_replace``."""
    dense = cfg.get("first_k_dense_replace", 0)
    return [(OP, "dense" if i < dense else "moe")
            for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def layers_of(cfg: dict, kind: str) -> int:
    """Layers whose mixer or FFN is ``kind``."""
    return sum(1 for kinds in layer_kinds(cfg) if kind in kinds)


def attention_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of the scores' two parts and ``p
    v`` in the latent layers: ``qk_nope + qk_rope + v`` a head and in-mask
    pair."""
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return layers_of(cfg, OP) * width * cfg["num_attention_heads"] * pairs_in_mask(cfg)


def expected_pairs_per_step(cfg: dict) -> float:
    """Pairs on held experts a step if every expert drew the same load."""
    lo, hi = cfg["experts_held"]
    return (3.0 * cfg["batch_size"] * tokens_per_sample(cfg) * cfg["num_experts_per_tok"]
            * (hi - lo) / cfg["router_outputs"] * layers_of(cfg, "moe"))


def mixer_macs_per_token(cfg: dict) -> int:
    """A layer's projections a token a forward: W_q, W_dkv, W_ukv, W_o."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def shared_macs_per_token(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]


def macs_per_token(cfg: dict) -> dict:
    """{part: multiply-adds a token a forward}: everything a token costs
    whatever the others are (the products over pairs and the experts left
    out)."""
    d, routing = cfg["hidden_size"], layers_of(cfg, "moe")
    return dict(tokens=cfg["channels"][-1] * d,
                mixer=layers_of(cfg, OP) * mixer_macs_per_token(cfg),
                router=routing * d * cfg["router_outputs"],
                shared_expert=routing * shared_macs_per_token(cfg),
                dense_ffn=layers_of(cfg, "dense") * 3 * d * cfg["intermediate_size"])


def dense_flops_per_sample(cfg: dict) -> tuple:
    """(forward, backward) FLOPs a sample of everything but the experts."""
    stem, head, first = stem_and_head_flops(cfg)
    forward = (stem + head + 2 * tokens_per_sample(cfg) * sum(macs_per_token(cfg).values())
               + 2 * attention_macs_per_sample(cfg))
    return forward, 2 * forward - first


def step_flops(cfg: dict, held_pairs_per_step: float) -> float:
    forward, backward = dense_flops_per_sample(cfg)
    return cfg["batch_size"] * (3 * forward + backward) + expert_step_flops(
        cfg, held_pairs_per_step)


def flops_per_sample(cfg: dict, held_pairs_per_step: float) -> float:
    return step_flops(cfg, held_pairs_per_step) / cfg["batch_size"]


def mixer_param_count(cfg: dict) -> int:
    return mixer_macs_per_token(cfg) + cfg["kv_lora_rank"]        # the latent's norm


def expert_layer_param_count(cfg: dict) -> int:
    """Router and its bias, the shared experts, the held experts."""
    d, lo_hi = cfg["hidden_size"], cfg["experts_held"]
    return (d * cfg["router_outputs"] + cfg["router_outputs"] + shared_macs_per_token(cfg)
            + (lo_hi[1] - lo_hi[0]) * expert_macs_per_pair(cfg))


def layers_param_count(cfg: dict) -> int:
    """The layers run, each with its two norms."""
    dense = 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return sum(mixer_param_count(cfg) + 2 * cfg["hidden_size"]
               + (dense if ffn == "dense" else expert_layer_param_count(cfg))
               for _, ffn in layer_kinds(cfg))


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
    return layers_of(cfg, "moe") * (hi - lo) * expert_macs_per_pair(cfg)


def attention_floor_s(cfg: dict, peaks: dict, kind: str = "latent") -> tuple:
    """Least seconds a step's masked products of the latent layers can take
    (``ops_count_ling3_q.attention_floor_s``, over every head): their FLOPs,
    three forwards and a backward at twice a forward, over the peak; or, a
    forward, the reads of both query parts, the keys, the one shared key and
    the values and the write of the output, and for the backward the reads of
    those, the output and its gradient and the writes of the five gradients
    (the shared key's once, not a head), in the compute type, whichever is
    longer."""
    del kind
    b, t = cfg["batch_size"], tokens_per_sample(cfg)
    layers = layers_of(cfg, OP)
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    t_flops = 5 * 2 * attention_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    operands = h * (dn + dr) + h * dn + dr + h * dv        # q (both parts), k, the shared key, v
    forward = layers * t * (operands + h * dv) * size
    backward = layers * t * (2 * operands + 2 * h * dv) * size
    t_bytes = b * (3 * forward + backward) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def step_floor_s(cfg: dict, peaks: dict, held_pairs_per_step: float) -> tuple:
    """Least seconds a whole step can take: its FLOPs over the peak, or one
    read of every parameter in the compute type for each of the three
    forwards and two for the backward, whichever is longer."""
    t_flops = step_flops(cfg, held_pairs_per_step) / peaks["flops_per_s_bf16"]
    t_bytes = (5 * param_count(cfg) * _DTYPE_BYTES[cfg["precision"]["compute"]]
               / peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def expert_floor_s(cfg: dict, peaks: dict, held_pairs_per_step: float) -> tuple:
    """Least seconds a step's grouped products can take: their FLOPs over the
    peak, or one read of the held experts' weights in the compute type for
    each of the three forwards and two for the backward, whichever is larger."""
    t_flops = expert_step_flops(cfg, held_pairs_per_step) / peaks["flops_per_s_bf16"]
    t_bytes = (5 * expert_param_count(cfg) * _DTYPE_BYTES[cfg["precision"]["compute"]]
               / peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")

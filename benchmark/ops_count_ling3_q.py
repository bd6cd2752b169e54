"""Operations and bytes a learner step of the Ling-3.0 Q-network over a history
of frames needs, from the configuration's shapes and the count of token-expert
pairs a run really routed to held experts.

Lower bounds, as ``ops_count.py``'s docstring sets out: three forwards and
one backward at twice a forward less the first convolution's input gradient;
the recomputation of every layer in the backward pass, the padding of a
sequence to whole chunks or blocks, the blocks' pairs outside the mask, the
second visit of a block by the backward kernels, the triangular solve's own
substitutions, the sorts and the gathers do not count.  Matrix products and
convolutions only; the latent layer's products over the pairs the causal mask
lets through (``2 x (192 + 128)`` FLOPs a pair, head and forward: the scores'
two parts and the values); the delta-rule layers' chunked form over the
in-chunk pairs ``j <= i`` (``ops_count_solar2_q.py``'s count, at this
configuration's heads and layers); elementwise work, norms, softmax, RoPE and
the router's choice of groups and experts count nothing.  The count reads the
same work whatever implements it.

The heads and experts counted are those the configuration holds
(``num_attention_heads`` and ``experts_held`` give this chip's share).  The
experts are counted from ``held_pairs_per_step``, as
``ops_count_laguna_q.py`` counts them.
"""

from __future__ import annotations

import ops_count as dueling_count
# What is the same arithmetic whatever the layers are: the tokens of a history,
# the pairs in a causal mask and in the scan's chunks, an expert's products, the
# stem's and the head's.  What reads the layers' pattern or their keys is here.
from ops_count_solar2_q import (  # noqa: F401  (re-exported under the names the readers call)
    _DTYPE_BYTES,
    expert_macs_per_pair,
    expert_step_flops,
    pairs_in_chunks,
    pairs_in_mask,
    stem_and_head_flops,
    tokens_per_sample,
)

OPS = ("linear_attention", "latent_attention")


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] of the layers run: ``layers_held`` of the pattern
    ``layer_group_size`` gives, dense before the published
    ``first_k_dense_replace``."""
    dense = cfg.get("published", {}).get("first_k_dense_replace",
                                         cfg.get("first_k_dense_replace", 0))
    period = cfg["layer_group_size"]
    return [(OPS[(i + 1) % period == 0], "dense" if i < dense else "moe")
            for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def layers_of(cfg: dict, kind: str) -> int:
    """Layers whose mixer or FFN is ``kind``."""
    return sum(1 for kinds in layer_kinds(cfg) if kind in kinds)


def delta_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of the chunked delta rule in all
    the linear layers, a head of K = V (``ops_count_solar2_q``'s count): over
    the in-chunk pairs ``A`` (K), the q-k scores (K), ``T`` applied to ``W``
    and ``U`` (K + V) and the scores to ``V'`` (V); over the tokens the two
    products with the incoming state and the state's update (K V each)."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    a_head = 5 * hd * pairs_in_chunks(cfg) + 3 * hd * hd * tokens_per_sample(cfg)
    return layers_of(cfg, "linear_attention") * heads * a_head


def attention_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of the scores' two parts and ``p
    v`` in the latent layers: ``qk_nope + qk_rope + v`` a head and in-mask
    pair."""
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return (layers_of(cfg, "latent_attention") * width * cfg["num_attention_heads"]
            * pairs_in_mask(cfg))


def expected_pairs_per_step(cfg: dict) -> float:
    """Pairs on held experts a step if every expert drew the same load."""
    lo, hi = cfg["experts_held"]
    return (3.0 * cfg["batch_size"] * tokens_per_sample(cfg) * cfg["num_experts_per_tok"]
            * (hi - lo) / cfg["router_outputs"] * layers_of(cfg, "moe"))


def mixer_macs_per_token(cfg: dict, op: str) -> int:
    """A layer's projections (and convolutions) a token a forward."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if op == "latent_attention":
        r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                         cfg["v_head_dim"])
        return (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)   # W_q, W_dkv, W_ukv
                + d * h + h * dv * d)                                  # the head gate, W_o
    hd, taps = cfg["head_dim"], cfg["short_conv_kernel_size"]
    return (4 * d * h * hd + 3 * h * hd * taps          # q, k, v, o; their convolutions
            + 2 * d * h * hd + d * h)                   # the two full-rank gates; beta


def shared_macs_per_token(cfg: dict) -> int:
    return (3 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]
            * cfg["num_shared_experts"])


def macs_per_token(cfg: dict) -> dict:
    """{part: multiply-adds a token a forward}: everything a token costs
    whatever the others are (the two mixers' products over pairs and the
    experts left out)."""
    d, routing = cfg["hidden_size"], layers_of(cfg, "moe")
    return dict(tokens=cfg["channels"][-1] * d,
                mixer=sum(mixer_macs_per_token(cfg, op) for op, _ in layer_kinds(cfg)),
                router=routing * d * cfg["router_outputs"],
                shared_expert=routing * shared_macs_per_token(cfg),
                dense_ffn=layers_of(cfg, "dense") * 3 * d * cfg["intermediate_size"])


def dense_flops_per_sample(cfg: dict) -> tuple:
    """(forward, backward) FLOPs a sample of everything but the experts."""
    stem, head, first = stem_and_head_flops(cfg)
    forward = (stem + head + 2 * tokens_per_sample(cfg) * sum(macs_per_token(cfg).values())
               + 2 * attention_macs_per_sample(cfg) + 2 * delta_macs_per_sample(cfg))
    return forward, 2 * forward - first


def step_flops(cfg: dict, held_pairs_per_step: float) -> float:
    forward, backward = dense_flops_per_sample(cfg)
    return cfg["batch_size"] * (3 * forward + backward) + expert_step_flops(
        cfg, held_pairs_per_step)


def flops_per_sample(cfg: dict, held_pairs_per_step: float) -> float:
    return step_flops(cfg, held_pairs_per_step) / cfg["batch_size"]


def mixer_param_count(cfg: dict, op: str) -> int:
    if op == "latent_attention":
        return mixer_macs_per_token(cfg, op) + cfg["kv_lora_rank"]        # the latent's norm
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return mixer_macs_per_token(cfg, op) + h + h * hd + hd                # A_log, dt_bias, the norm


def expert_layer_param_count(cfg: dict) -> int:
    """Router and its bias, the shared experts, the held experts."""
    d, lo_hi = cfg["hidden_size"], cfg["experts_held"]
    return (d * cfg["router_outputs"] + cfg["router_outputs"] + shared_macs_per_token(cfg)
            + (lo_hi[1] - lo_hi[0]) * expert_macs_per_pair(cfg))


def layers_param_count(cfg: dict) -> int:
    """The layers run, each with its two norms."""
    dense = 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return sum(mixer_param_count(cfg, op) + 2 * cfg["hidden_size"]
               + (dense if ffn == "dense" else expert_layer_param_count(cfg))
               for op, ffn in layer_kinds(cfg))


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
    """Least seconds a step's masked products of the latent layers can take,
    over the heads held: their FLOPs, three forwards and a backward at twice a
    forward, over the peak; or, a forward, the reads of both query parts, the
    keys, the one shared key and the values and the write of the output, and
    for the backward the reads of those, the output and its gradient and the
    writes of the five gradients (the shared key's once, not a head), in the
    compute type, whichever is longer."""
    del kind
    b, t = cfg["batch_size"], tokens_per_sample(cfg)
    layers = layers_of(cfg, "latent_attention")
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    t_flops = 5 * 2 * attention_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    operands = h * (dn + dr) + h * dn + dr + h * dv        # q (both parts), k, the shared key, v
    forward = layers * t * (operands + h * dv) * size
    backward = layers * t * (2 * operands + 2 * h * dv) * size
    t_bytes = b * (3 * forward + backward) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def delta_floor_s(cfg: dict, peaks: dict) -> tuple:
    """Least seconds a step's delta-rule scans can take
    (``ops_count_solar2_q.delta_floor_s``): the chunked form's products, three
    forwards and a backward at twice a forward, over the peak; or, a pass, the
    reads of q, k and v in the compute type and of g and beta in float32 and
    the write of o, the backward pass at twice a forward's, over the
    bandwidth; whichever is longer."""
    b, t = cfg["batch_size"], tokens_per_sample(cfg)
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
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

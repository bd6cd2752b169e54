"""Operations and bytes a learner step of the Olmo-Hybrid Q-network over a
history of frames needs, from the configuration's shapes alone: no layer's
work depends on its input.

Lower bounds, as ``ops_count.py``'s docstring sets out: three forwards and
one backward at twice a forward less the first convolution's input gradient;
the recomputation of every layer in the backward pass, the padding of a
sequence to whole chunks or blocks, of a head to whole lanes, the blocks'
pairs outside the mask and the triangular system's own substitutions do not
count.  Matrix products and convolutions only; the full layer's two products
over the pairs the causal mask lets through; the delta-rule layers' chunked
form **in its scalar-gate form at the published head sizes**
(``delta_macs_per_sample``: keys of K, values of V); elementwise work, norms
and softmax count nothing.  The count reads the same work whatever
implements it.
"""

from __future__ import annotations

import ops_count as dueling_count

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
CHUNK = 64


def layer_kinds(cfg: dict) -> list:
    """The layer types run: ``layers_held`` of ``layer_types``."""
    return [cfg["layer_types"][i] for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def layers_of(cfg: dict, op: str) -> int:
    return sum(1 for kind in layer_kinds(cfg) if kind == op)


def tokens_per_sample(cfg: dict) -> int:
    return dueling_count.conv_output_sizes(cfg["obs_shape"][0])[-1] ** 2 * cfg["obs_shape"][2]


def linear_sizes(cfg: dict) -> tuple:
    """(heads, a key head's width, a value head's, the convolutions' taps)."""
    return (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"])


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def pairs_in_mask(cfg: dict) -> int:
    """(query, key) pairs a sample that the causal mask lets through."""
    t = tokens_per_sample(cfg)
    return t * (t + 1) // 2


def pairs_in_chunks(cfg: dict) -> int:
    """Pairs ``j <= i`` a sample inside the chunks of the delta-rule scan:
    whole chunks and the last one's own tokens, no padding."""
    c = cfg.get("linear_chunk_size", CHUNK)
    whole, rest = divmod(tokens_per_sample(cfg), c)
    return whole * c * (c + 1) // 2 + rest * (rest + 1) // 2


def delta_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of the scalar-gate chunked delta
    rule in all the linear layers: an in-chunk pair ``j <= i`` costs K (k.k)
    + K (q.k) + K + V (``T`` on ``W`` and ``U``) + V (the scores on ``V'``); a
    token 3 K V (the two products with the incoming state and its update)."""
    heads, kd, vd, _ = linear_sizes(cfg)
    a_head = (3 * kd + 2 * vd) * pairs_in_chunks(cfg) + 3 * kd * vd * tokens_per_sample(cfg)
    return layers_of(cfg, "linear_attention") * heads * a_head


def attention_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of q k^T and p v in the full
    layers: ``2 x head_dim x heads`` an in-mask pair."""
    return (layers_of(cfg, "full_attention") * 2 * head_dim(cfg) * cfg["num_attention_heads"]
            * pairs_in_mask(cfg))


def mixer_macs_per_token(cfg: dict, op: str) -> int:
    """A layer's projections (and convolutions) a token a forward."""
    d = cfg["hidden_size"]
    if op == "full_attention":
        return 4 * d * d                                     # q, k, v, o: a key-value head a query head
    n, kd, vd, taps = linear_sizes(cfg)
    return (d * n * (2 * kd + 3 * vd)                        # q, k; v, the gate, o
            + n * (2 * kd + vd) * taps + 2 * d * n)          # their convolutions; w_a, w_b


def macs_per_token(cfg: dict) -> dict:
    """{part: multiply-adds a token a forward}: everything a token costs
    whatever the others are (the two mixers' products over pairs left out)."""
    d = cfg["hidden_size"]
    return dict(tokens=cfg["channels"][-1] * d,
                mixer=sum(mixer_macs_per_token(cfg, op) for op in layer_kinds(cfg)),
                dense_ffn=len(layer_kinds(cfg)) * 3 * d * cfg["intermediate_size"])


def stem_and_head_flops(cfg: dict) -> tuple:
    """(forward FLOPs a sample of the three convolutions over the history's
    frames, each alone; of the two streams and heads; of the first
    convolution alone)."""
    h, w, frames = cfg["obs_shape"]
    rows = dueling_count.layer_table(dict(cfg, obs_shape=[h, w, 1]))
    hid, a, d = cfg["hidden"], cfg["num_actions"], cfg["hidden_size"]
    head = 2 * (2 * d * hid + hid + hid * a)
    return frames * sum(f for _, f, _, _ in rows[:3]), head, frames * rows[0][1]


def flops_per_sample(cfg: dict) -> float:
    """FLOPs a sample of a learner step: three forwards and a backward at
    twice a forward less the first convolution's input gradient."""
    stem, head, first = stem_and_head_flops(cfg)
    forward = (stem + head + 2 * tokens_per_sample(cfg) * sum(macs_per_token(cfg).values())
               + 2 * attention_macs_per_sample(cfg) + 2 * delta_macs_per_sample(cfg))
    return float(3 * forward + 2 * forward - first)


def param_count(cfg: dict) -> int:
    d = cfg["hidden_size"]
    h, w, _ = cfg["obs_shape"]
    rows = dueling_count.layer_table(dict(cfg, obs_shape=[h, w, 1]))
    hid, a = cfg["hidden"], cfg["num_actions"]
    n, _, vd, _ = linear_sizes(cfg)
    count = sum(p for _, _, p, _ in rows[:3]) + cfg["channels"][-1] * d + d
    count += 2 * (d * hid + hid) + hid + 1 + hid * a + a
    for op in layer_kinds(cfg):
        count += mixer_macs_per_token(cfg, op) + 3 * d * cfg["intermediate_size"] + 2 * d
        count += 2 * d if op == "full_attention" else 2 * n + vd   # q_norm, k_norm | A_log, dt_bias, norm
    return count


def attention_floor_s(cfg: dict, peaks: dict, kind: str = "full") -> tuple:
    """Least seconds a step's masked products of the full layers can take:
    their FLOPs (``4 x head_dim`` an in-mask pair, head and forward), three
    forwards and a backward at twice a forward, over the peak; or the reads
    of q, k and v and the write of the output a forward, and for the backward
    the reads of q, k, v, the output and its gradient and the writes of the
    three gradients, in the compute type, whichever is longer."""
    b, t, hd = cfg["batch_size"], tokens_per_sample(cfg), head_dim(cfg)
    heads = layers_of(cfg, "full_attention") * cfg["num_attention_heads"]
    t_flops = 5 * 2 * attention_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    forward, backward = 4 * heads * t * hd * size, 8 * heads * t * hd * size
    t_bytes = b * (3 * forward + backward) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def delta_floor_s(cfg: dict, peaks: dict) -> tuple:
    """Least seconds a step's delta-rule scans can take: the scalar form's
    products (``delta_macs_per_sample``), three forwards and a backward at
    twice a forward, over the peak; or, a pass, the reads of q and k (K
    each) and v (V) in the compute type, of g and beta (4 B each) and the
    write of o (V), five passes, over the bandwidth; whichever is longer."""
    b, t = cfg["batch_size"], tokens_per_sample(cfg)
    heads, kd, vd, _ = linear_sizes(cfg)
    t_flops = 5 * 2 * delta_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    a_pass = layers_of(cfg, "linear_attention") * t * heads * ((2 * kd + 2 * vd) * size + 8)
    t_bytes = 5 * b * a_pass / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")

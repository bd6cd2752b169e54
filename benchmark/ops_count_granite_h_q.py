"""Operations and bytes a learner step of the Granite 4.0-H Q-network over a
history of frames needs, from the configuration's shapes alone: no layer's
work depends on its input.

Lower bounds, as ``ops_count.py``'s docstring sets out: three forwards and
one backward at twice a forward less the first convolution's input gradient;
the recomputation of every layer and of every chunk in the backward pass and
the padding of a sequence to whole chunks or blocks do not count.  Matrix
products and convolutions only: the projections, the SwiGLU, attention's two
products over the pairs the causal mask lets through, and the scan **in its
chunked form at the published chunk size**: the scores ``C B^T`` over the
pairs ``j <= i`` inside a chunk, once for all heads (``mamba_n_groups`` 1),
the product of those pairs with ``dt x`` a head, and the two products with
the state a token (``C S`` and ``x B^T``).  The depthwise convolution's four
taps, the decays, norms, gates and softmax count nothing.  (The literal
recurrence needs ``3 x heads x head x state`` multiply-adds a token, 1.57 M a
layer against this form's 1.58 M: the two agree at this chunk size.)
"""

from __future__ import annotations

import ops_count as dueling_count

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def layer_kinds(cfg: dict) -> list:
    return [cfg["layer_types"][i] for i in cfg.get("layers_held", range(cfg["num_hidden_layers"]))]


def tokens_per_sample(cfg: dict) -> int:
    return dueling_count.conv_output_sizes(cfg["obs_shape"][0])[-1] ** 2 * cfg["obs_shape"][2]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def pairs_in_mask(cfg: dict) -> int:
    """(query, key) pairs a sample the causal mask lets through."""
    t = tokens_per_sample(cfg)
    return t * (t + 1) // 2


def pairs_in_chunks(cfg: dict) -> int:
    """Pairs ``j <= i`` a sample with both tokens in one chunk."""
    whole, last = divmod(tokens_per_sample(cfg), cfg["mamba_chunk_size"])
    q = cfg["mamba_chunk_size"]
    return whole * (q * (q + 1) // 2) + last * (last + 1) // 2


def mamba_sizes(cfg: dict) -> tuple:
    """(inner width, state, heads)."""
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"],
            cfg["mamba_d_state"] * cfg["mamba_n_groups"], cfg["mamba_n_heads"])


def layers_of(cfg: dict, op: str) -> int:
    return sum(1 for kind in layer_kinds(cfg) if kind == op)


def attention_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of q k^T and p v in the attention
    layers: ``2 x head_dim x heads`` an in-mask pair."""
    return (layers_of(cfg, "attention") * 2 * head_dim(cfg) * cfg["num_attention_heads"]
            * pairs_in_mask(cfg))


def scan_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds a sample and forward of the chunked scan's products in
    the state-space layers (module docstring)."""
    inner, n, _ = mamba_sizes(cfg)
    return layers_of(cfg, "mamba") * (
        pairs_in_chunks(cfg) * (n + inner) + tokens_per_sample(cfg) * 2 * inner * n)


def macs_per_token(cfg: dict) -> dict:
    """{part: multiply-adds a token a forward}: everything a token costs
    whatever the others are (attention's and the scan's products left out)."""
    d, kv, hd, h = (cfg["hidden_size"], cfg["num_key_value_heads"], head_dim(cfg),
                    cfg["num_attention_heads"])
    inner, n, heads = mamba_sizes(cfg)
    return dict(
        tokens=cfg["channels"][-1] * d,
        mixer=(layers_of(cfg, "mamba") * (d * (2 * inner + 2 * n + heads) + inner * d)
               + layers_of(cfg, "attention") * (2 * d * h * hd + 2 * d * kv * hd)),
        dense_ffn=len(layer_kinds(cfg)) * 3 * d * cfg["shared_intermediate_size"])


def stem_and_head_flops(cfg: dict) -> tuple:
    """(forward FLOPs a sample of the three convolutions over the history's
    frames, each alone; of the two streams and heads; of the first
    convolution alone)."""
    h, w, frames = cfg["obs_shape"]
    rows = dueling_count.layer_table(dict(cfg, obs_shape=[h, w, 1]))
    hid, a, d = cfg["hidden"], cfg["num_actions"], cfg["hidden_size"]
    head = 2 * (2 * d * hid + hid + hid * a)
    return frames * sum(f for _, f, _, _ in rows[:3]), head, frames * rows[0][1]


def forward_flops_per_sample(cfg: dict) -> int:
    stem, head, _ = stem_and_head_flops(cfg)
    return (stem + head + 2 * tokens_per_sample(cfg) * sum(macs_per_token(cfg).values())
            + 2 * attention_macs_per_sample(cfg) + 2 * scan_macs_per_sample(cfg))


def flops_per_sample(cfg: dict, _held_pairs_per_step=None) -> float:
    """FLOPs a learned sample needs: three forwards and a backward at twice a
    forward less the first convolution's input gradient."""
    forward = forward_flops_per_sample(cfg)
    return float(3 * forward + 2 * forward - stem_and_head_flops(cfg)[2])


def step_flops(cfg: dict) -> float:
    return cfg["batch_size"] * flops_per_sample(cfg)


def layer_param_count(cfg: dict, op: str) -> int:
    d, kv, hd, h = (cfg["hidden_size"], cfg["num_key_value_heads"], head_dim(cfg),
                    cfg["num_attention_heads"])
    inner, n, heads = mamba_sizes(cfg)
    shared = 2 * d + 3 * d * cfg["shared_intermediate_size"]         # two norms, the SwiGLU
    if op == "attention":
        return shared + 2 * d * h * hd + 2 * d * kv * hd
    mixed = inner + 2 * n
    return (shared + d * (inner + mixed + heads) + mixed * cfg["mamba_d_conv"] + mixed
            + 3 * heads + inner + inner * d)


def param_count(cfg: dict) -> int:
    h, w, _ = cfg["obs_shape"]
    rows = dueling_count.layer_table(dict(cfg, obs_shape=[h, w, 1]))
    hid, a, d = cfg["hidden"], cfg["num_actions"], cfg["hidden_size"]
    n = sum(p for _, _, p, _ in rows[:3]) + cfg["channels"][-1] * d + d
    n += 2 * (d * hid + hid) + hid + 1 + hid * a + a
    return n + sum(layer_param_count(cfg, op) for op in layer_kinds(cfg))


def attention_floor_s(cfg: dict, peaks: dict, kind: str = "full") -> tuple:
    """Least seconds a step's masked products of the attention layers can
    take, as ``ops_count_laguna_q.attention_floor_s`` at this head size:
    ``4 x head_dim x heads`` FLOPs an in-mask pair and forward, three forwards
    and a backward at twice a forward, over the peak; or the reads of q, k
    and v and the write of the output a forward, and for the backward the
    reads of q, k, v, the output and its gradient and the writes of the three
    gradients, in the compute type, whichever is longer."""
    if kind != "full":
        raise ValueError(f"this network's attention layers are causal alone, not {kind!r}")
    b, t, hd, kv = cfg["batch_size"], tokens_per_sample(cfg), head_dim(cfg), cfg["num_key_value_heads"]
    t_flops = 5 * 2 * attention_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    layers = layers_of(cfg, "attention")
    heads = layers * cfg["num_attention_heads"]
    forward = (2 * heads + 2 * kv * layers) * t * hd * size
    backward = (4 * heads + 4 * kv * layers) * t * hd * size
    t_bytes = b * (3 * forward + backward) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def scan_floor_s(cfg: dict, peaks: dict) -> tuple:
    """Least seconds a step's scans can take: the chunked form's products
    (``scan_macs_per_sample``), three forwards and a backward at twice a
    forward, over the peak; or, a pass, the reads of ``x``, ``B`` and ``C`` in
    the compute type and of ``dt`` in float32 and the write of ``y``, the
    backward pass at twice a forward's (it reads those and ``y``'s gradient
    and writes four gradients), over the bandwidth; whichever is longer."""
    b, t = cfg["batch_size"], tokens_per_sample(cfg)
    inner, n, heads = mamba_sizes(cfg)
    t_flops = 5 * 2 * scan_macs_per_sample(cfg) * b / peaks["flops_per_s_bf16"]
    size = _DTYPE_BYTES[cfg["precision"]["compute"]]
    a_pass = layers_of(cfg, "mamba") * t * ((2 * inner + 2 * n) * size + heads * 4)
    t_bytes = 5 * b * a_pass / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")

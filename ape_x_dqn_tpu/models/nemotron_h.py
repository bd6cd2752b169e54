"""NVIDIA-Nemotron-3-Super-120B-A12B's layers (nvidia, ``config.json``,
``model_type`` ``nemotron_h``) as a Q-network's torso over a history of
frames: the spec made from the published keys.  The model's layers are one
sublayer each, ``x <- x + Mixer(RMSNorm(x))``, of the kind
``hybrid_override_pattern`` gives a layer: ``M`` Mamba-2, ``E`` experts, ``*``
attention.  A mixer followed by an ``E`` is the block ``models/expert_torso.py``
has (two pre-norms, two residual adds), so the held layers are paired into
blocks: ``M E`` is ``("mamba", "moe")``, ``* E`` ``("attention", "moe")``, and a
mixer that no ``E`` follows is a block with no FFN (``"none"``); an ``E`` that
follows no mixer cannot be paired and is refused.

``M``  Mamba-2 (Dao & Gu 2024, arXiv:2405.21060) in ``n_groups`` groups:
       ``granite_hybrid.Mamba2``, told its groups (``B`` and ``C`` a group of
       ``mamba_num_heads / n_groups`` consecutive heads, the gated RMSNorm over
       a group's channels), its chunk and the heads a chip holds, whole groups.
``*``  grouped-query causal softmax attention with no positional rule, scores
       over the square root of the head, no bias:
       ``solar_open2.GatedNopeAttention`` told that nothing is gated.
``E``  LatentMoE: ``n_routed_experts`` sigmoid scores on the layer's input, the
       ``num_experts_per_tok`` largest of ``score + bias`` chosen (``n_group``
       1: no groups; the bias chooses and does not weigh), gates normalised
       over the chosen ones (1e-20 on the sum) times ``routed_scaling_factor``;
       the routed experts are ``relu(v W_1)^2 W_2`` (``mlp_hidden_act``
       ``relu2``: two matrices, no gate) on ``v = u W_down`` in a latent of
       ``moe_latent_size`` and their gated sum goes back through ``W_up``; one
       shared expert of the same rule reads ``u`` itself.

The deployment this family's cell states: heads and the shared expert's
columns tensor-parallel (``heads_held`` of the query heads with the key-value
heads they read, ``mamba_heads_held``, ``shared_expert_held``), the routed
experts expert-parallel (``experts_held``); router, latent projections and
norms whole on every chip.  The partial sums of ``W_out``, ``W_o`` and the
shared expert's ``W_2`` go on as they are.

Left out: multi-token prediction (``num_nextn_predict_layers``: a Q-network
has no next-token head).  ``rope_theta`` and ``partial_rotary_factor`` are not
read: this family's attention applies no positional rule.
"""

from __future__ import annotations

from typing import Mapping

from ape_x_dqn_tpu.models.expert_torso import TorsoQ, TorsoSpec
from ape_x_dqn_tpu.models.granite_hybrid import Mamba2, MambaSizes
from ape_x_dqn_tpu.models.solar_open2 import GatedNopeAttention

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
MIXERS = {"mamba": Mamba2, "attention": GatedNopeAttention}
# What the published config may say and this family builds: anything else is refused.
BUILT = {"n_group": 1, "topk_group": 1, "moe_shared_expert_overlap": False, "mlp_bias": False,
         "attention_bias": False, "mamba_proj_bias": False, "use_bias": False,
         "use_conv_bias": True, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
         "sliding_window": None, "norm_topk_prob": True}


def layer_types(cfg: Mapping) -> list:
    """The published one-sublayer layers' kinds, from ``hybrid_override_pattern``."""
    pattern = cfg["hybrid_override_pattern"]
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown:
        raise ValueError(f"hybrid_override_pattern holds {unknown}; {sorted(KINDS)}")
    return [KINDS[c] for c in pattern]


def paired(types: list, held: list) -> tuple:
    """The held layers as blocks ``(op, ffn)``: a mixer with the expert layer
    that follows it, or alone."""
    blocks, i = [], 0
    while i < len(held):
        op = types[held[i]]
        if op == "moe":
            raise ValueError(f"layer {held[i]} is an expert layer that follows no held mixer: the "
                             f"held layers {held} cannot be paired into blocks")
        follows = (i + 1 < len(held) and held[i + 1] == held[i] + 1
                   and types[held[i + 1]] == "moe")
        blocks.append((op, "moe" if follows else "none"))
        i += 2 if follows else 1
    return tuple(blocks)


def spec_from_config(cfg: Mapping) -> TorsoSpec:
    """A ``TorsoSpec`` from the published ``config.json``'s keys, plus what a
    cut states: ``layers_held`` (indices into ``hybrid_override_pattern``,
    default the first ``num_hidden_layers``), ``router_outputs`` and
    ``experts_held`` (default every one of ``n_routed_experts``), ``heads_held``
    of the query heads, ``mamba_heads_held`` of the Mamba-2 heads and
    ``shared_expert_held`` of the shared expert's columns (default all), and the
    published counts under ``published`` where a key holds the cut's
    (``num_attention_heads``, ``num_key_value_heads`` and ``mamba_num_heads``
    then count the heads held, ``n_routed_experts`` the experts).  A
    ``layer_types`` key, if the file carries one, must be the pattern's.
    Refused, because not built (``BUILT``): router groups, the shared expert
    overlapped, any bias but the convolution's, another rule than ``relu2``
    or ``silu``, a window."""
    published = cfg.get("published", {})
    for key, built in BUILT.items():
        if cfg.get(key, built) != built:
            raise ValueError(f"this family's spec: {key} {built!r}, not {cfg[key]!r}")
    types = layer_types(cfg)
    if list(cfg.get("layer_types", types)) != types:
        raise ValueError("layer_types disagrees with hybrid_override_pattern")
    held = list(cfg.get("layers_held", range(int(cfg["num_hidden_layers"]))))
    if not held or not all(0 <= i < len(types) for i in held):
        raise ValueError(f"layers_held {held} are no layers of the {len(types)} published")
    blocks = paired(types, held)
    whole = lambda key: int(published.get(key, cfg[key]))  # noqa: E731
    d, heads, kv, mamba_heads = (int(cfg["hidden_size"]), whole("num_attention_heads"),
                                 whole("num_key_value_heads"), whole("mamba_num_heads"))
    outputs = int(cfg.get("router_outputs", whole("n_routed_experts")))
    share = tuple(cfg["heads_held"]) if cfg.get("heads_held") else None
    mamba_share = tuple(cfg["mamba_heads_held"]) if cfg.get("mamba_heads_held") else None
    if (share is None) != (mamba_share is None):
        raise ValueError("heads_held and mamba_heads_held state one share: both or neither")
    for name, stated, count in (("heads_held", share, "num_attention_heads"),
                                ("mamba_heads_held", mamba_share, "mamba_num_heads")):
        if stated and count in published and stated[1] - stated[0] != int(cfg[count]):
            raise ValueError(f"{name} {stated} is not the {cfg[count]} heads {count} counts")
    # raises where the held heads cut a group
    mamba = MambaSizes(heads=mamba_heads, head_dim=int(cfg["mamba_head_dim"]),
                       state=int(cfg["ssm_state_size"]), conv=int(cfg["conv_kernel"]),
                       chunk=int(cfg["chunk_size"]), groups=int(cfg["n_groups"]),
                       held=mamba_share)
    if mamba.inner != int(cfg["expand"]) * d or heads % kv:
        raise ValueError(f"mamba_num_heads x mamba_head_dim is not expand x {d}, or {heads} "
                         f"heads do not divide by {kv} key-value heads")
    shared = int(cfg.get("n_shared_experts", 0)) * int(cfg["moe_shared_expert_intermediate_size"])
    ops = sorted({op for op, _ in blocks})
    return TorsoSpec(
        hidden_size=d,
        intermediate_size=int(cfg["intermediate_size"]),     # no dense layer reads it
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        norm_eps=float(cfg["layer_norm_epsilon"]),
        router_outputs=outputs,
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        experts_held=tuple(cfg.get("experts_held", (0, outputs))),
        layers=blocks,
        mixers=tuple((op, MIXERS[op]) for op in ops),
        mixer_args=(("mamba", mamba), ("num_attention_heads", heads),
                    ("num_key_value_heads", kv), ("head_dim", int(cfg["head_dim"])),
                    ("use_gqa_gate", False)),
        norm_topk_prob=True,
        gate_norm_eps=1e-20,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        use_expert_bias=True,
        score_function="sigmoid",
        shared_expert_intermediate_size=shared,
        frame_history=True,
        float32_leaves=("A_log", "dt_bias", "['D']"),
        heads_held=share,
        expert_rule="relu2",
        moe_latent_size=int(cfg.get("moe_latent_size") or 0),
        shared_expert_held=(tuple(cfg["shared_expert_held"])
                            if cfg.get("shared_expert_held") else None),
    )


class NemotronHQ(TorsoQ):
    """Stem, a frame at a time -> a history's tokens -> Nemotron 3 layers, in
    blocks -> norm, mean over tokens -> dueling head."""

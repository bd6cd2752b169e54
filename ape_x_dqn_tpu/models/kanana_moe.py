"""kanana-2-30b-a3b-instruct-2601's block (kakaocorp, ``config.json``,
``model_type`` ``deepseek_v3``) as a Q-network's torso over a history of
frames: the spec made from the published keys.  Every layer's mixer is
multi-head latent attention, ``ling_hybrid.LatentAttention`` told that no head
is gated (``LatentSizes.gated`` false): a query of ``qk_nope_head_dim +
qk_rope_head_dim`` a head from one product (``q_lora_rank`` null: no query
latent, no query norm), keys and values expanded from one ``kv_lora_rank``-wide
normed latent a token, a rotary key that is one for every head, RoPE in the
pairs ``(2j, 2j + 1)`` (``rope_interleave``) with no factor on the scale
(``rope_scaling`` null).  The first ``first_k_dense_replace`` layers carry the
dense SwiGLU; every other layer (``moe_layer_freq`` 1) routes over
``n_routed_experts`` sigmoid scores (``scoring_func``), the ``num_experts_per_tok``
largest of ``score + bias`` chosen (``topk_method`` ``noaux_tc``: the bias
chooses and does not weigh), gates normalised over the chosen ones
(``norm_topk_prob``, 1e-20 on the sum) times ``routed_scaling_factor``, and
adds the ``n_shared_experts`` shared experts as one ungated SwiGLU of their
summed width.  The expert layer, the block and the Q-network around them are
``models/expert_torso.py``'s; the mixer, ``rope_pairs`` and the kernels' shared
key operand are ``models/ling_hybrid.py``'s and ``ops/pallas/blocked_attention.py``'s.

The deployment this family is trained in: attention data-parallel with every
head held (``heads_held`` None), the experts expert-parallel
(``experts_held``); a run of like expert layers is one scanned body with the
attention kernels inside it.
"""

from __future__ import annotations

from typing import Mapping

from ape_x_dqn_tpu.models.expert_torso import TorsoQ, TorsoSpec
from ape_x_dqn_tpu.models.ling_hybrid import LatentAttention, LatentSizes

LAYER_TYPE = "latent_attention"
# What the published config may say and this family builds: anything else is refused.
BUILT = {"q_lora_rank": None, "rope_scaling": None, "moe_layer_freq": 1,
         "topk_method": "noaux_tc", "scoring_func": "sigmoid", "attention_bias": False,
         "rope_interleave": True}


def layer_types(cfg: Mapping) -> list:
    """The published pattern over the published depth: latent attention in
    every layer."""
    depth = int(cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"]))
    return [LAYER_TYPE] * depth


def spec_from_config(cfg: Mapping) -> TorsoSpec:
    """A ``TorsoSpec`` from the published ``config.json``'s keys, plus what a
    cut states: ``layers_held`` (indices into the published depth, default the
    first ``num_hidden_layers``), ``router_outputs`` and ``experts_held``
    (default every one of ``n_routed_experts``), and the published counts
    under ``published`` where a key holds the cut's.  A layer before
    ``first_k_dense_replace`` is dense.  A ``layer_types`` key, if the file
    carries one, must be latent attention throughout.  Refused, because not
    built (``BUILT``): a query latent, a scaled RoPE, expert layers at another
    frequency than every layer, another choice than ``noaux_tc`` over sigmoid
    scores, an attention bias, RoPE in halves."""
    published = cfg.get("published", {})
    for key, built in BUILT.items():
        if cfg.get(key, built) != built:
            raise ValueError(f"this family's spec: {key} {built!r}, not {cfg[key]!r}")
    types = layer_types(cfg)
    if list(cfg.get("layer_types", types)) != types:
        raise ValueError("layer_types is not latent attention in every layer")
    held = list(cfg.get("layers_held", range(int(cfg["num_hidden_layers"]))))
    if not held or not all(0 <= i < len(types) for i in held):
        raise ValueError(f"layers_held {held} are no layers of the {len(types)} published")
    outputs = int(cfg.get("router_outputs",
                          published.get("n_routed_experts", cfg["n_routed_experts"])))
    dense = int(cfg.get("first_k_dense_replace", 0))
    latent = LatentSizes(heads=int(cfg["num_attention_heads"]), kv_rank=int(cfg["kv_lora_rank"]),
                         nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
                         v=int(cfg["v_head_dim"]), theta=float(cfg["rope_theta"]), gated=False)
    return TorsoSpec(
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        router_outputs=outputs,
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        experts_held=tuple(cfg.get("experts_held", (0, outputs))),
        layers=tuple((LAYER_TYPE, "dense" if i < dense else "moe") for i in held),
        mixers=((LAYER_TYPE, LatentAttention),),
        mixer_args=(("latent", latent),),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
        gate_norm_eps=1e-20,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        use_expert_bias=True,
        score_function="sigmoid",
        shared_expert_intermediate_size=(int(cfg.get("n_shared_experts", 0))
                                         * int(cfg["moe_intermediate_size"])),
        frame_history=True,
        router_groups=int(cfg.get("n_group", 1)),
        router_groups_kept=int(cfg.get("topk_group", 1)),
    )


class KananaMoeQ(TorsoQ):
    """Stem, a frame at a time -> a history's tokens -> Kanana-2 layers ->
    norm, mean over tokens -> dueling head."""

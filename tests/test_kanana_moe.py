"""The network kind ``kanana_moe`` in the program: the latent mixer without its
head gate against the reference's latent layer, the same module with the gate
on against ``ling_hybrid``'s bit for bit, the spec's refusals, the expert
layer's four shares against the uncut reference layer, the one scanned body of
like expert layers, and ``chip_smoke.py``'s leg ``kanana_kernels`` at a small
size with each mechanism lost in turn, at small widths on the CPU (the
attention kernels in Pallas' interpreter); what every torso is held to
(structure, ``benchmark/reference/kanana2_q.py`` on seeded weights, the float32
leaves, scopes, counters, the configuration path, the trainer's loop) is the
contract's, ``tests/torso_contract.py``, on this torso's row."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_torso, kanana_moe, ling_hybrid
from ape_x_dqn_tpu.models.ling_hybrid import LatentAttention, LatentSizes
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from tests import torso_contract as contract
from tests.torso_contract import built, init_of  # noqa: F401 - built: the module's fixture

TORSO = contract.KANANA
OP = kanana_moe.LAYER_TYPE


class TestContract(contract.of("kanana_moe")):
    """The contract's cases on this torso (``tests/torso_contract.py``)."""


@pytest.fixture(scope="module")
def ref():
    return importlib.import_module("reference.kanana2_q")


def _u(seed=0, rows=2, tokens=40, width=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, tokens, width))


# --------------------------------------------------- the mixer, told its gate

def test_the_ungated_mixer_is_the_references_latent_layer(ref):
    """``LatentAttention`` under this family's spec, float32, against
    ``kanana2_q.latent_attention`` on the module's own parameters: the two
    were written apart (a 0/+-1 partner matrix and the blocked kernels there,
    a 2 x 2 rotation a pair and whole masked score rows here)."""
    spec = kanana_moe.spec_from_config(TORSO)
    u = _u()
    mixer = LatentAttention(spec, OP, jnp.float32, jnp.float32)
    params = init_of(mixer, jax.random.PRNGKey(1), u)["params"]
    assert set(params) == {"w_q", "w_dkv", "kv_norm", "w_ukv", "w_o"}      # no w_g
    params = dict(params, kv_norm=1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (24,)))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: mixer.apply({"params": p}, u))(params)
        want = jax.jit(lambda p: ref.latent_attention(u, p, TORSO, jnp.float32, lambda x: x))(params)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * scale      # sums in another order
    for flag in ("reference_drops_shared_key", "reference_skips_latent_norm"):
        with jax.default_matmul_precision("highest"):
            other = ref.latent_attention(u, params, dict(TORSO, **{flag: True}), jnp.float32, lambda x: x)
        assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * scale, flag


def test_with_the_gate_on_the_module_is_ling_hybrids_bit_for_bit():
    """One module, told whether it gates: under ``ling_hybrid``'s spec
    (``LatentSizes.gated`` its default) the parameters are the six Ling has
    always had, drawn as before, and the output is the gated one; the same
    sizes told ``gated=False`` lose ``w_g`` and nothing else, and differ from
    the gated output by exactly the gate."""
    ling = ling_hybrid.spec_from_config(contract.LING)
    sizes = ling.arg("latent")
    assert sizes.gated and LatentSizes(4, 24, 16, 8, 16, 6e6) == sizes      # gated by default
    bare = dataclasses.replace(ling, mixer_args=(("linear", ling.arg("linear")), (
        "latent", dataclasses.replace(sizes, gated=False))))
    u, key = _u(3), jax.random.PRNGKey(4)
    gated, ungated = (LatentAttention(s, "latent_attention", jnp.float32, jnp.float32)
                      for s in (ling, bare))
    p_gated, p_bare = (init_of(m, key, u)["params"] for m in (gated, ungated))
    assert set(p_gated) == {"w_q", "w_dkv", "kv_norm", "w_ukv", "w_g", "w_o"}
    assert set(p_gated) - set(p_bare) == {"w_g"}
    for name in ("w_q", "w_dkv", "kv_norm", "w_ukv"):       # drawn before the gate: the same bits
        assert np.array_equal(np.asarray(p_gated[name]), np.asarray(p_bare[name])), name
    # a draw hangs on its place: the gate keeps its place before W_o, so Ling's six are drawn as ever
    assert not np.array_equal(np.asarray(p_gated["w_o"]), np.asarray(p_bare["w_o"]))
    net = contract.network("ling_hybrid")         # the network's own path builds this module
    assert net.spec.arg("latent") == sizes and dict(net.spec.mixers)["latent_attention"] is LatentAttention
    # W_o an identity (4 heads of 16 are the toy's width): the output is the heads' own
    p_gated = dict(p_gated, w_o=jnp.eye(64))
    out = gated.apply({"params": p_gated}, u).reshape(2, 40, 4, 16)
    bare_out = ungated.apply({"params": {k: v for k, v in p_gated.items() if k != "w_g"}}, u)
    gate = jax.nn.sigmoid(jnp.einsum("btd,dn->btn", u, p_gated["w_g"]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(bare_out.reshape(2, 40, 4, 16) * gate[..., None]),
                               atol=2e-6)
    assert float(jnp.max(jnp.abs(out.reshape(2, 40, 64) - bare_out))) > 1e-2


# ------------------------------------------------------- the spec's refusals

@pytest.mark.parametrize("bad", [
    dict(q_lora_rank=1536), dict(rope_scaling={"type": "yarn", "factor": 40}), dict(moe_layer_freq=2),
    dict(topk_method="greedy"), dict(scoring_func="softmax"), dict(attention_bias=True),
    dict(rope_interleave=False), dict(layer_types=["full_attention"] * 12),
    dict(layer_types=["latent_attention"] * 11), dict(layers_held=[0, 12]), dict(layers_held=[]),
    dict(experts_held=[6, 10]),
], ids=lambda bad: next(iter(bad)) + "_" + str(len(str(next(iter(bad.values()))))))
def test_the_spec_refuses_what_is_not_built(bad):
    """A query latent, a scaled RoPE, expert layers every second layer,
    another choice than ``noaux_tc``, softmax scores, an attention bias, RoPE
    in halves; a pattern that is not latent attention over the published
    depth; layers or experts that do not exist."""
    with pytest.raises(ValueError):
        kanana_moe.spec_from_config(dict(TORSO, **bad))


@pytest.mark.parametrize("key", sorted(kanana_moe.BUILT))
def test_the_spec_takes_the_published_value_and_its_absence(key):
    """What the published config says of each refused key is what is built, and
    a file that leaves the key out gets the same spec."""
    assert TORSO[key] == kanana_moe.BUILT[key]
    without = {k: v for k, v in TORSO.items() if k != key}
    assert kanana_moe.spec_from_config(without) == kanana_moe.spec_from_config(TORSO)


def test_the_spec_reads_the_deepseek_v3_keys():
    spec = kanana_moe.spec_from_config(TORSO)
    assert spec.layers == ((OP, "dense"),) + ((OP, "moe"),) * 3
    assert (spec.router_outputs, spec.experts_held, spec.num_experts_per_tok, spec.score_function,
            spec.use_expert_bias, spec.shared_expert_intermediate_size, spec.routed_scaling_factor,
            spec.router_groups, spec.router_groups_kept, spec.gate_norm_eps, spec.heads_held,
            spec.norm_topk_prob, spec.float32_leaves) == (
                8, (2, 4), 3, "sigmoid", True, 2 * 32, 2.448, 1, 1, 1e-20, None, True, ())
    assert spec.arg("latent") == LatentSizes(4, 24, 16, 8, 16, 1e6, gated=False)
    # the published counts beside a cut's: all eight outputs held where the file states no share
    whole = kanana_moe.spec_from_config({k: v for k, v in TORSO.items()
                                         if k not in ("experts_held", "router_outputs")})
    assert (whole.router_outputs, whole.experts_held) == (8, (0, 8))
    assert kanana_moe.layer_types(TORSO) == [OP] * 12
    moved = kanana_moe.spec_from_config(dict(TORSO, first_k_dense_replace=2, layers_held=[1, 2]))
    assert moved.layers == ((OP, "dense"), (OP, "moe"))


# ------------------------------------------------- the share ties to the model

def test_the_four_expert_shares_add_up_to_the_uncut_reference_layer(ref):
    """Eight router outputs on four expert-parallel shares of two: the
    program's expert layers (``ExpertShare`` told its range), with the shared
    expert counted once, add up to the reference's uncut layer (all eight
    held), and each share's part is the reference's for that range."""
    cfg = dict(TORSO, n_routed_experts=8, router_outputs=8, experts_held=[0, 8])
    base = kanana_moe.spec_from_config(cfg)
    u = _u(3)
    whole = expert_torso.ExpertShare(base, jnp.float32, jnp.float32)
    params = init_of(whole, jax.random.PRNGKey(5), u)["params"]
    shared = expert_torso.SwiGLU(base.shared_expert_intermediate_size, jnp.float32, jnp.float32)
    sp = init_of(shared, jax.random.PRNGKey(4), u)["params"]
    assert sp["w1"].shape == (64, 64)                  # two shared experts of 32 as one of 64
    weights = dict(router=params["router"], expert_bias=params["expert_bias"],
                   w1=params["w13"][..., :32], w3=params["w13"][..., 32:], w2=params["w2"],
                   shared_w1=sp["w1"], shared_w3=sp["w3"], shared_w2=sp["w2"])
    with jax.default_matmul_precision("highest"):
        want, load = ref.moe(u, weights, cfg, jnp.float32, lambda x: x)       # uncut
        total = shared.apply({"params": sp}, u)                               # counted once
        for lo in range(0, 8, 2):
            share = dataclasses.replace(base, experts_held=(lo, lo + 2))
            part = dict(params, w13=params["w13"][lo:lo + 2], w2=params["w2"][lo:lo + 2])
            y, sown = expert_torso.ExpertShare(share, jnp.float32, jnp.float32).apply(
                {"params": part}, u, mutable=["routing"])
            mine, _ = ref.routed(u, dict(weights, w1=weights["w1"][lo:lo + 2], w3=weights["w3"][lo:lo + 2],
                                         w2=weights["w2"][lo:lo + 2]), cfg, jnp.float32, lambda x: x,
                                 held=(lo, lo + 2))
            np.testing.assert_allclose(np.asarray(y), np.asarray(mine), atol=3e-5)
            np.testing.assert_array_equal(np.asarray(sown["routing"]["load"][0]), np.asarray(load))
            total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    assert float(jnp.sum(load)) == 2 * 40 * 3                 # every token's three pairs, on some output


def test_the_bias_chooses_and_does_not_weigh(ref):
    """``noaux_tc``: a large bias on one output makes every token take it, and
    its gate is still its score over the chosen scores' sum, times 2.448; the
    reference's rounds of "the largest not yet taken" and the program's
    selection agree on experts and gates."""
    spec = kanana_moe.spec_from_config(TORSO)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(7), (50, 8)))
    bias = jnp.zeros((8,)).at[5].set(10.0)
    chosen, gates = expert_torso.route(scores, bias, spec)
    want_chosen, want_gates = ref.route(scores, bias, TORSO)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want_chosen))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates), rtol=1e-6)
    assert bool(jnp.all(chosen[:, 0] == 5))
    np.testing.assert_allclose(np.asarray(jnp.sum(gates, -1)), 2.448, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates[:, 0]),
                               np.asarray(scores[:, 5] / jnp.sum(jnp.take_along_axis(scores, chosen, -1), -1) * 2.448),
                               rtol=1e-6)
    unscaled = ref.route(scores, bias, dict(TORSO, reference_unscaled_gates=True))[1]
    np.testing.assert_allclose(np.asarray(jnp.sum(unscaled, -1)), 1.0, rtol=1e-6)


# -------------------------------------- like expert layers, one scanned body

def test_the_like_expert_layers_are_one_scanned_body_with_the_kernels_inside(built):
    """Layers 1-3 of the toy (1-5 of the cell) are ``("latent_attention",
    "moe")`` one after the other: one stacked run, ``layers_1_3``, whose body
    holds the attention kernels once; the leading dense layer stands alone."""
    net, params = built.net(), built.params["params"]
    assert expert_torso.layer_runs(net.spec.layers) == [(0, 1, (OP, "dense")), (1, 3, (OP, "moe"))]
    assert {"layer_0", "layers_1_3"} <= set(params) and not any(
        k.startswith("layer") and k not in ("layer_0", "layers_1_3") for k in params)
    assert params["layers_1_3"][OP]["w_q"].shape == (3, 64, 4 * 24)
    assert "w_g" not in params["layers_1_3"][OP] and "w_g" not in params["layer_0"][OP]
    lowered = jax.jit(net.apply).lower(built.params, built.x)
    assert lowered.as_text().count("stablehlo.while") >= 1
    # the kernels (interpreted here) sit under the mixer's part in both the lone layer and the run
    debug = lowered.as_text(debug_info=True)
    assert "layer_0/torso:mixer/latent_attention/torso:attn_latent" in debug
    assert "layers_1_3/torso:mixer/latent_attention/torso:attn_latent" in debug
    counted = net.attention_metrics(built.x.shape)
    visited, total = blocked.blocks_visited(40, None, 1)
    assert counted["blocks_visited_latent"] == 2 * 4 * 4 * visited       # rows x layers x heads
    assert counted["blocks_total_latent"] == 2 * 4 * 4 * total
    assert counted["pairs_in_mask_latent"] == 2 * 4 * blocked.pairs_in_mask(40, None)


# ------------------- the chip's own numeric check (chip_smoke.py --kanana-kernels)

SMALL = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16)


@pytest.mark.parametrize("lost", [None, "latent_norm", "shared_key", "gate_factor"])
def test_the_chips_numeric_check_passes_here_and_fails_on_a_lost_mechanism(monkeypatch, lost):
    """``chip_smoke.py``'s leg ``kanana_kernels`` at a small size: it passes on
    the program as it is; a mixer that lost the latent's norm, a kernel call
    that lost ``q_shared k_shared^T`` and gates that lost their factor each
    fail it."""
    import chip_smoke

    if lost == "latent_norm":
        monkeypatch.setattr(jax.lax, "rsqrt", lambda x: jnp.ones_like(x))
        with pytest.raises(AssertionError, match="from the mixer written out"):
            chip_smoke.latent_mixer_against_plain(rows=1, tokens=200, **SMALL)
        return
    if lost == "shared_key":
        whole = blocked.blocked_attention
        monkeypatch.setattr(blocked, "blocked_attention",
                            lambda q, k, v, window=None, q_shared=None, k_shared=None: whole(q, k, v, window))
        with pytest.raises(AssertionError, match="from the mixer written out"):
            chip_smoke.latent_mixer_against_plain(rows=1, tokens=200, **SMALL)
        return
    if lost == "gate_factor":
        whole = expert_torso.route
        monkeypatch.setattr(expert_torso, "route", lambda s, b, spec, kept=None: whole(
            s, b, dataclasses.replace(spec, routed_scaling_factor=1.0)))
        with pytest.raises(AssertionError, match="from the host's"):
            chip_smoke.gates_against_sorting(tokens=512)
        return
    near, far = chip_smoke.latent_mixer_against_plain(rows=1, tokens=200, **SMALL)["mixer"]
    assert near <= chip_smoke.MIXER_REL < chip_smoke.MIXER_REL_WITHOUT_LATENT_NORM <= far
    routed = chip_smoke.gates_against_sorting(tokens=512)
    assert routed["differing"] == 0 and routed["gates_max_abs"] <= chip_smoke.GATE_ABS
    readings = chip_smoke.latent_kernels_against_plain(rows=1, heads=4, tokens=200)
    assert all(n <= chip_smoke.KERNEL_REL for n, _ in readings.values())

"""The program's LFM2-MoE Q-network against ``reference/lfm2_moe_q.py`` on
seeded weights at small widths (hidden 64, 4 experts of which 2 held, 2 a
token, 2+1 layers, batch 8), and the published configuration built
abstractly."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest as mf
import ops_count_lfm2_moe_q as ops
from reference import lfm2_moe_q as ref

CFG = dict(
    mf.load_json(os.path.join(mf.HERE, "configs", "lfm2moe_q_ep8.json")),
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, num_experts=2, router_outputs=4, experts_held=[0, 2],
    num_experts_per_tok=2, num_hidden_layers=3, layers_held=[0, 2, 3], num_dense_layers=1,
    obs_shape=[52, 52, 4], hidden=32, channels=[8, 8, 8], batch_size=8,
)


def program_net(cfg, compute):
    from ape_x_dqn_tpu.models.dueling import build_network

    return build_network("lfm2_moe", cfg["num_actions"], torso=cfg,
                         channels=tuple(cfg["channels"]), hidden=cfg["hidden"],
                         compute_dtype=compute, param_dtype=jnp.float32)


def observations(key, cfg, rows=8):
    return jax.random.randint(key, (rows, *cfg["obs_shape"]), 0, 256).astype(jnp.uint8)


# a run of three like layers, which the program holds stacked and scans
LONG = dict(CFG, num_hidden_layers=5, layers_held=[0, 2, 3, 4, 5])


@pytest.fixture(scope="module", params=["three_layers", "a_scanned_run"])
def cfg(request):
    return CFG if request.param == "three_layers" else LONG


@pytest.fixture(scope="module")
def weights(cfg):
    return ref.make_weights(jax.random.PRNGKey(11), cfg)


# float32: both sides compute the same sums in another order; 1e-4 of |Q| is
# float32 rounding through 3 layers at default CPU precision.  Stated
# precision: bfloat16 activations against float32 carry about 2^-8 a product
# through 3 layers and two streams, and a token whose router scores tie to
# within that flips an expert: the worst of 48 Q values read 0.08 of the
# spread of Q here, so 0.15 (with the run of three expert layers, two more
# places to flip, 0.18 read: 0.3).
@pytest.mark.parametrize("compute,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 0.15)])
def test_forward_q_matches_the_reference(cfg, weights, compute, tol):
    obs = observations(jax.random.PRNGKey(5), cfg)
    with jax.default_matmul_precision("highest"):
        want, loads = ref.forward(weights, obs, cfg)
        got, sown = program_net(cfg, compute).apply(
            ref.to_program_params(weights, cfg), obs, mutable=["routing"])
    scale = float(jnp.std(want)) + float(jnp.mean(jnp.abs(want)))
    tol = 2 * tol if compute == jnp.bfloat16 and cfg is LONG else tol
    assert float(jnp.max(jnp.abs(got[2] - want))) <= tol * scale
    if compute == jnp.float32:  # the same pairs on every one of the router's outputs
        counted = np.concatenate([np.asarray(v).reshape(-1, 4)
                                  for v in jax.tree_util.tree_leaves(sown["routing"])])
        np.testing.assert_array_equal(counted, np.asarray(loads))


def test_loss_gradients_match_the_reference(cfg, weights):
    """Gradients of sum(Q^2) in float32, leaf by leaf in the reference's
    names: 1e-3 relative to each leaf's norm (float32 sums in another
    order, through the backward pass of three layers); the expert bias gets
    none on either side."""
    obs = observations(jax.random.PRNGKey(6), cfg)
    net = program_net(cfg, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda w: jnp.sum(ref.forward(w, obs, cfg)[0] ** 2))(weights)
        got = jax.grad(lambda p: jnp.sum(net.apply(p, obs)[2] ** 2))(
            ref.to_program_params(weights, cfg))
    got = ref.from_program_params(got, cfg)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            assert float(jnp.max(jnp.abs(a))) == 0.0
            continue
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * float(jnp.linalg.norm(b)) + 1e-7, name


def test_parameter_maps_are_inverse(cfg, weights):
    back = ref.from_program_params(ref.to_program_params(weights, cfg), cfg)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(weights)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_shares_add_up_to_the_uncut_layer():
    """The expert layer's outputs from every share (held 0-1, 2-3), added,
    equal the uncut reference's whole layer: same router, gates normalised
    over all the chosen experts, each share its own experts' part."""
    from ape_x_dqn_tpu.models.lfm2_moe import ExpertShare, spec_from_config

    k = jax.random.PRNGKey(3)
    whole = dict(CFG, num_experts=4, experts_held=[0, 4])
    p = ref.make_weights(k, whole)["layer_1"]
    u = jax.random.normal(jax.random.fold_in(k, 9), (8, 16, CFG["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, load = ref.moe(u, p, whole, jnp.float32, lambda x: x)
        total = jnp.zeros_like(want)
        for lo, hi in ((0, 2), (2, 4)):
            share = dict(CFG, experts_held=[lo, hi])
            part = {n: (v[lo:hi] if n in ("w1", "w2", "w3") else v) for n, v in p.items()}
            ref_part, share_load = ref.moe(u, part, share, jnp.float32, lambda x: x)
            np.testing.assert_array_equal(np.asarray(share_load), np.asarray(load))
            layer = ExpertShare(spec_from_config(share), jnp.float32, jnp.float32)
            params = {"params": ref.to_program_params(
                {**{"layer_1": part}, **{k_: v for k_, v in ref.make_weights(k, share).items()
                                         if k_ != "layer_1"}}, share)["params"]["layer_1"]["moe"]}
            got = layer.apply(params, u)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref_part), atol=2e-5)
            total = total + got
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=4e-5)
    assert float(jnp.max(jnp.abs(want))) > 1e-2 and float(jnp.sum(load)) == 8 * 16 * 2


def test_published_configuration_builds_abstractly():
    """At the published widths: the program's parameter tree, mapped to the
    reference's names, has ``weight_shapes``' shapes; 455 M parameters +-1%;
    the operation count's parameter count is the same number."""
    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "lfm2moe_q_ep8.json"))
    net = program_net(cfg, jnp.bfloat16)
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    assert n == pytest.approx(455e6, rel=0.01)
    assert n == ref.param_count(cfg) == ops.param_count(cfg)
    mapped = jax.eval_shape(lambda p: ref.from_program_params(p, cfg), params)
    assert jax.tree_util.tree_map(lambda x: tuple(x.shape), mapped) == ref.weight_shapes(cfg)
    # every published number of the catalog's row is in the file under its key
    published = cfg["published"]
    assert published == {"num_hidden_layers": 40, "num_experts": 64}
    assert len(cfg["layer_types"]) == 40 and cfg["router_outputs"] == 64
    # the reference reads the balancing rule's rate from the file: the program's constant
    from ape_x_dqn_tpu.models.lfm2_moe import BIAS_UPDATE_RATE
    assert cfg["expert_bias_update_rate"] == BIAS_UPDATE_RATE


def test_operation_count_matches_xla_on_the_dense_parts():
    """``ops_count_lfm2_moe_q`` against XLA's count of the reference's forward
    at the small size: the reference computes every held expert on every
    token, so its experts are taken at that load.  Within 8% at hidden 64
    and 9 tokens (XLA adds the elementwise work, a larger share the smaller
    the widths, and the attention's masked half), and ours is never the
    larger."""
    obs = jax.ShapeDtypeStruct((8, *CFG["obs_shape"]), jnp.uint8)
    w = jax.eval_shape(lambda k: ref.make_weights(k, CFG), jax.random.PRNGKey(0))
    xla = jax.jit(lambda w, o: ref.forward_rows(w, o, CFG)[0]).lower(w, obs).cost_analysis()["flops"] / 8
    tokens = ops.tokens_per_sample(CFG)
    n_moe = sum(1 for _, f in ops.layer_kinds(CFG) if f == "moe")
    dense_experts = 2 * tokens * n_moe * 2 * ops.expert_macs_per_pair(CFG)  # 2 held, all tokens
    ours = ops.dense_flops_per_sample(CFG)[0] + dense_experts
    assert ours <= xla
    assert ours == pytest.approx(xla, rel=0.08)


def test_published_step_is_42_tflop():
    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "lfm2moe_q_ep8.json"))
    pairs = ops.expected_pairs_per_step(cfg)
    assert pairs == 3 * 512 * 49 * 4 * 8 / 64 * 4
    assert ops.step_flops(cfg, pairs) == pytest.approx(42.6e12, rel=0.01)
    peaks = json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"]
    t, bound = ops.step_floor_s(cfg, peaks, pairs)
    assert bound == "compute" and 0.21 < t < 0.22

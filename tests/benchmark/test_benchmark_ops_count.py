"""ops_count against XLA's own count for one forward at each configuration's
shapes, and the table of peaks."""
import json
import os

import numpy as np
import pytest

import manifest as mf
import ops_count
import peaks

M = mf.load_manifest()
CONFIGS = [c["name"] for c in M["configs"]]


def cfg_of(name):
    entry = [c for c in M["configs"] if c["name"] == name][0]
    return mf.load_json(os.path.join(mf.ROOT, entry["file"]))


@pytest.mark.parametrize("name", CONFIGS)
def test_forward_flops_match_cost_analysis(name):
    """XLA's count adds the elementwise work (bias, relu, /255, the dueling
    mean); convolutions and matmuls are over 99% of it at these shapes, so
    the two agree within 1%, and ours is never the larger."""
    import jax
    import jax.numpy as jnp

    from reference import dueling_dqn as ref

    cfg = cfg_of(name)
    weights = jax.eval_shape(lambda k: ref.make_weights(k, cfg), jax.random.PRNGKey(0))
    obs = jax.ShapeDtypeStruct((8, *cfg["obs_shape"]), jnp.uint8)
    cost = jax.jit(ref.forward).lower(weights, obs).cost_analysis()
    xla = cost["flops"] / 8
    ours = ops_count.forward_flops(cfg)
    assert ours <= xla
    assert ours == pytest.approx(xla, rel=0.01)


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_count_matches_the_reference_weights(name):
    from reference import dueling_dqn as ref

    cfg = cfg_of(name)
    n = sum(int(np.prod(s)) + s[-1] for s in ref.weight_shapes(cfg).values())
    assert ops_count.param_count(cfg) == n


def test_step_counts_and_floor():
    cfg = cfg_of("ref_b32")
    f = ops_count.forward_flops(cfg)
    assert f == 23_933_952
    assert ops_count.flops_per_sample(cfg) == 3 * f + ops_count.backward_flops(cfg)
    assert ops_count.backward_flops(cfg) == 2 * f - 2 * 20 * 20 * 64 * 8 * 8 * 1
    assert ops_count.step_flops(cfg) == 32 * ops_count.flops_per_sample(cfg)
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = ops_count.step_floor_s(cfg, pk)
    assert bound == "bandwidth" and 50e-6 < t < 65e-6
    t2, bound2 = ops_count.step_floor_s(cfg_of("apex_b512"), pk)
    assert bound2 == "compute" and 250e-6 < t2 < 290e-6
    # four chips: a quarter of the FLOPs each, the whole parameter traffic each
    dp = cfg_of("apex_b512_dp4")
    assert ops_count.step_floor_s(dp, pk)[0] == pytest.approx(t2 / 4)
    assert ops_count.step_bytes(dp) < ops_count.step_bytes(cfg_of("apex_b512"))


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
    table = json.load(open(os.path.join(mf.HERE, "peaks.json")))
    assert all("source" in v for v in table.values())

"""The comparison that decides ``correct``, at toy widths on the CPU: the
program's fused calls agree with the plain references for each layout and
under a four-way mesh, the controls do not, and faults put into the ring, the
sampler or the mathematics are seen."""
import os

import jax
import numpy as np
import pytest

import correctness
import manifest as mf
import program
from reference import prioritized_ring as ring_ref

TRAFFIC = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed.json"))
TRAFFIC["check"].update(ring_rows_per_chip=512, ingest_rows_per_chip=32)
BETA = TRAFFIC["beta"]
DRIVER = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed.py"), "bench_driver_t")
CONFIGS = [("ref_b32", 32, 1), ("apex_b512", 64, 1), ("apex_b512_dp4", 64, 4)]


def toy_cfg(name, batch, n=1):
    cfg = mf.load_json(os.path.join(mf.HERE, "configs", name + ".json"))
    cfg.update(obs_shape=[36, 36, cfg["obs_shape"][2]], hidden=32, channels=[8, 8, 8],
               batch_size=batch, data_parallel=n)
    return cfg


@pytest.fixture(scope="module", params=CONFIGS, ids=[c[0] for c in CONFIGS])
def shot(request):
    cfg = toy_cfg(*request.param)
    inputs, shots = DRIVER.check_shots(cfg, TRAFFIC, 2**31 + 7)
    counts, numbers, reference = correctness.program_numbers(cfg, BETA, inputs, shots)
    return cfg, inputs, shots, counts, numbers, reference


def test_program_agrees_with_the_references(shot):
    _cfg, _inputs, _shots, counts, numbers, _ref = shot
    assert counts == dict.fromkeys(correctness.EXACT, 0)
    assert numbers["fused_priority_rel"] < 0.02, numbers
    assert numbers["fused_priority_median_rel"] < 0.002, numbers
    assert numbers["fused_update_rel"] < 0.3, numbers


@pytest.mark.parametrize("control,number,at_least", [
    (("bf16_held", 0), "fused_update_rel", 3.0),
    (("fp8_activations", 0), "fused_priority_median_rel", 0.006),
    (("stated", 1), "fused_priority_rel", 0.3),
])
def test_controls_fail(shot, control, number, at_least):
    cfg, inputs, shots, _counts, numbers, reference = shot
    got = correctness.control_numbers(cfg, BETA, inputs, shots, reference, *control)
    assert got[number] > at_least and got[number] > 3 * numbers[number], (got, numbers)


def _again(shot, **changed):
    cfg, inputs, shots, *_ = shot
    return correctness.program_numbers(cfg, BETA, inputs, dict(shots, **changed))


def _edit(shots, key, call, shard, field, fn):
    """``shots[key]`` with one array of one call and shard replaced."""
    calls = [[dict(s) for s in c] for c in shots[key]]
    calls[call][shard][field] = fn(calls[call][shard][field].copy())
    return calls


def test_a_ring_row_written_wrongly_is_seen(shot):
    cfg, _inputs, shots, *_ = shot
    field = ring_ref.DATA_FIELDS[cfg["replay_layout"]][0]

    def flip(a):
        a[7] ^= 1
        return a

    counts, _n, _r = _again(shot, rings=_edit(shots, "rings", 1, 0, field, flip))
    assert counts["ring_rows_differing"] > 0


def test_a_mass_that_moved_without_a_sample_is_seen(shot):
    _cfg, _inputs, shots, *_ = shot
    quiet = int(np.flatnonzero(
        shots["rings"][1][0]["mass"] == shots["rings"][0][0]["mass"])[-1])

    def bump(a):
        a[quiet] *= 0.5
        return a

    counts, _n, _r = _again(shot, rings=_edit(shots, "rings", 1, 0, "mass", bump))
    assert counts["masses_unexplained"] > 0


def test_a_sampler_off_its_strata_is_seen(shot):
    """The same draws read as a batch in another order: rows leave their strata."""
    _cfg, _inputs, shots, *_ = shot
    counts, _n, _r = _again(shot, priorities=[p[::-1].copy() for p in shots["priorities"]])
    assert counts["rows_outside_stratum"] + counts["masses_unexplained"] > 0


def test_wrong_mathematics_is_seen(shot):
    """The reference with the importance weights left out of the loss (beta 0)
    in the program's place reads far from the reference."""
    cfg, inputs, shots, _counts, _numbers, reference = shot
    flat = correctness.reference_run(cfg, 0.0, inputs, shots)
    got = correctness.compare(inputs["weights"], flat["weights"], flat["priorities"], reference)
    assert got["fused_update_rel"] > 0.25, got


def test_reference_ring_semantics():
    ring = dict(frames=np.zeros((10, 2), np.uint8), obs_ref=np.arange(8, dtype=np.int32),
                next_ref=np.arange(8, dtype=np.int32) + 1, action=np.zeros(8, np.int32),
                reward=np.zeros(8, np.float32), discount=np.ones(8, np.float32),
                mass=np.ones(8, np.float32), cursor=np.int32(6), count=np.int32(8),
                fcount=np.int32(10))
    chunk = dict(frames=np.full((3, 2), 9, np.uint8), obs_ref=np.array([10, 11], np.int32),
                 next_ref=np.array([11, 12], np.int32), action=np.array([1, 2], np.int32),
                 reward=np.array([.5, .25], np.float32), discount=np.ones(2, np.float32),
                 priority=np.array([4., 9.], np.float32))
    took = ring_ref.ingest(ring, chunk, "dedup", 0.5)
    assert list(took) == [6, 7] and ring["cursor"] == 0 and ring["fcount"] == 13
    assert (ring["frames"][:3] == 9).all() and (ring["frames"][3:] == 0).all()
    # rows 0..2 named frames 0..2, now overwritten: swept; 6 and 7 are the new rows
    assert list(ring["mass"]) == [0, 0, 0, 1, 1, 1, 2, 3]
    assert (ring_ref.gather(ring, np.array([6]), "dedup")["obs"] == 9).all()
    first, last = ring_ref.strata(ring["mass"], 4, 0.0)
    assert list(first) == [3, 5, 6, 7] and list(last) == [4, 6, 7, 7]
    w = ring_ref.importance_weights([ring["mass"]], [np.array([3, 7])], [8], 1.0)[0]
    assert np.allclose(w, [1.0, 1 / 3])


def test_seed_key_takes_large_seeds():
    a, b = program.seed_key(5), program.seed_key(2**31 + 5)
    assert not bool((jax.random.key_data(a) == jax.random.key_data(b)).all())
    program.seed_key(2**32 + 11)

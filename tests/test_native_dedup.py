"""Native (C++) dedup replay core vs the numpy oracle (verdict item 1b).

n_stripes=1 must be BIT-exact with replay.dedup.DedupReplay — same slots,
same samples, IS weights to 1-ulp (libm vs numpy pow), same frame bytes — through FIFO wrap,
frame-death sweeps, restamps, and snapshot roundtrips (snapshots are
interchangeable between the two implementations).  Striped mode checks
the per-stripe sampling law and lock discipline under threads.
"""

import numpy as np
import pytest

from ape_x_dqn_tpu.replay.dedup import DedupReplay
from ape_x_dqn_tpu.replay.native_dedup import (
    NativeDedupReplay,
    native_dedup_available,
    native_dedup_error,
)
from ape_x_dqn_tpu.replay.sum_tree import SumTree
from ape_x_dqn_tpu.types import DedupChunk

pytestmark = pytest.mark.skipif(
    not native_dedup_available(),
    reason=f"native replay core unavailable: {native_dedup_error()}",
)

OBS = (5, 5, 1)


def frame(seq: int) -> np.ndarray:
    return np.full(OBS, seq % 251, np.uint8)


def make_chunk(source, chunk_seq, fbase, n_tx=6, carry=0, prev_frames=0):
    U = n_tx + 1
    frames = np.stack([frame(fbase + i) for i in range(U)])
    rng = np.random.default_rng(chunk_seq * 131 + source)
    m = n_tx + carry
    return DedupChunk(
        frames=frames,
        obs_ref=np.concatenate([
            -np.arange(carry, 0, -1, dtype=np.int32),
            np.arange(n_tx, dtype=np.int32)]),
        next_ref=np.concatenate([
            np.zeros(carry, np.int32),
            np.arange(1, n_tx + 1, dtype=np.int32)]),
        action=rng.integers(0, 4, m).astype(np.int32),
        reward=rng.normal(size=m).astype(np.float32),
        discount=np.full(m, 0.97, np.float32),
        source=source, chunk_seq=chunk_seq, prev_frames=prev_frames,
    )


def stream(n_chunks, n_tx=6, source=9):
    out, fbase, prev_U = [], 0, 0
    for i in range(n_chunks):
        c = make_chunk(source, i, fbase, n_tx=n_tx,
                       carry=2 if i else 0, prev_frames=prev_U)
        out.append(c)
        fbase += c.frames.shape[0]
        prev_U = c.frames.shape[0]
    return out


def pair(capacity=64, frame_ratio=2.0, **kw):
    nat = NativeDedupReplay(capacity, OBS, frame_ratio=frame_ratio, **kw)
    ref = DedupReplay(capacity, OBS, sum_tree_cls=SumTree,
                      frame_ratio=frame_ratio)
    return nat, ref


class TestNativeParity:
    def test_bit_exact_through_wrap(self):
        nat, ref = pair()
        prng = np.random.default_rng(0)
        for c in stream(40):
            p = (np.abs(prng.normal(size=c.action.shape[0])) + 0.1)
            i1 = nat.add(p, c)
            i2 = ref.add(p, c)
            np.testing.assert_array_equal(i1, i2)
        assert nat.size() == ref.size() == 64
        assert nat.stats == ref.stats
        assert nat.max_priority() == pytest.approx(ref.max_priority())
        for t in range(6):
            b1 = nat.sample(16, beta=0.5, rng=np.random.default_rng(t))
            b2 = ref.sample(16, beta=0.5, rng=np.random.default_rng(t))
            np.testing.assert_array_equal(b1.indices, b2.indices)
            np.testing.assert_allclose(b1.is_weights, b2.is_weights, rtol=2e-7)
            for f in ("obs", "action", "reward", "discount", "next_obs"):
                np.testing.assert_array_equal(
                    getattr(b1.transition, f), getattr(b2.transition, f), f
                )
            upd = np.abs(np.random.default_rng(50 + t).normal(size=16)) + 0.1
            nat.update_priorities(b1.indices, upd)
            ref.update_priorities(b2.indices, upd)

    def test_frame_death_and_restamp_guard_parity(self):
        nat, ref = pair(frame_ratio=0.5)
        for c in stream(30, n_tx=4):
            p = np.ones(c.action.shape[0])
            nat.add(p, c)
            ref.add(p, c)
        assert nat.stats["frame_dead"] == ref.stats["frame_dead"] > 0
        dead = np.nonzero(~ref._alive[: ref.size()])[0]
        assert dead.size
        nat.update_priorities(dead[:4], np.full(4, 7.7))
        ref.update_priorities(dead[:4], np.full(4, 7.7))
        for s in dead[:4]:
            assert float(nat._lib.rc_get_mass(nat._handle, int(s))) == 0.0
        b1 = nat.sample(16, rng=np.random.default_rng(1))
        b2 = ref.sample(16, rng=np.random.default_rng(1))
        np.testing.assert_array_equal(b1.indices, b2.indices)
        np.testing.assert_array_equal(
            b1.transition.obs, b2.transition.obs
        )

    def test_carry_gap_parity(self):
        nat, ref = pair()
        c0 = make_chunk(3, 0, 0)
        gap = make_chunk(3, 4, 7, carry=2, prev_frames=7)
        for r in (nat, ref):
            r.add(np.ones(6), c0)
            r.add(np.ones(8), gap)
        assert nat.stats["dropped_carry"] == ref.stats["dropped_carry"] == 2
        assert nat.size() == ref.size()

    def test_snapshots_interchange(self):
        """A native snapshot restores into the numpy replay and vice versa
        — one checkpoint format for the host dedup path."""
        nat, ref = pair(capacity=32, frame_ratio=1.5)
        prng = np.random.default_rng(2)
        for c in stream(20, n_tx=4):
            p = np.abs(prng.normal(size=c.action.shape[0])) + 0.1
            nat.add(p, c)
            ref.add(p, c)
        # native -> numpy
        ref2 = DedupReplay(32, OBS, sum_tree_cls=SumTree, frame_ratio=1.5)
        ref2.load_state_dict(nat.state_dict())
        b1 = ref2.sample(8, rng=np.random.default_rng(5))
        b2 = ref.sample(8, rng=np.random.default_rng(5))
        np.testing.assert_array_equal(b1.indices, b2.indices)
        np.testing.assert_array_equal(b1.transition.obs, b2.transition.obs)
        # numpy -> native
        nat2 = NativeDedupReplay(32, OBS, frame_ratio=1.5)
        nat2.load_state_dict(ref.state_dict())
        b3 = nat2.sample(8, rng=np.random.default_rng(5))
        np.testing.assert_array_equal(b3.indices, b2.indices)
        np.testing.assert_array_equal(b3.transition.obs, b2.transition.obs)
        np.testing.assert_allclose(b3.is_weights, b2.is_weights, rtol=2e-7)
        # carry continues across the restore
        nxt = stream(21, n_tx=4)[-1]
        idx = nat2.add(np.ones(6), nxt)
        assert len(idx) == 6 and nat2.stats["dropped_carry"] == 0


class TestStripedLaw:
    def test_stripes_cover_all_slots_and_weights_bounded(self):
        nat = NativeDedupReplay(64, OBS, frame_ratio=2.0, n_stripes=4)
        prng = np.random.default_rng(0)
        for c in stream(40):
            nat.add(np.abs(prng.normal(size=c.action.shape[0])) + 0.1, c)
        seen = set()
        for t in range(200):
            b = nat.sample(16, rng=np.random.default_rng(t))
            seen.update(int(i) for i in b.indices)
            assert np.all(b.is_weights > 0) and np.all(b.is_weights <= 1.0)
            # stripe quota: 4 rows per stripe per sample
            stripes = np.asarray(b.indices) % 4
            assert all((stripes == s).sum() == 4 for s in range(4))
        assert len(seen) > 55  # proportional sampling reaches ~every slot

    def test_striped_frequency_matches_realized_law(self):
        """Empirical sampling frequency ∝ (mass / stripe_total) / K — the
        documented law the IS weights correct for."""
        C, K = 16, 4
        nat = NativeDedupReplay(C, OBS, frame_ratio=4.0, n_stripes=K)
        # One chunk with known priorities: slot i gets priority i+1.
        c = make_chunk(1, 0, 0, n_tx=C)
        nat.add(np.arange(1, C + 1, dtype=np.float64), c)
        mass = np.array([
            float(nat._lib.rc_get_mass(nat._handle, s)) for s in range(C)
        ])
        stripe_tot = np.array([mass[s::K].sum() for s in range(K)])
        expect = np.array([
            mass[s] / stripe_tot[s % K] / K for s in range(C)
        ])
        counts = np.zeros(C)
        trials = 3000
        for t in range(trials):
            b = nat.sample(8, rng=np.random.default_rng(t))
            for i in b.indices:
                counts[int(i)] += 1
        freq = counts / (trials * 8)
        np.testing.assert_allclose(freq, expect, atol=0.01)

    def test_batch_not_divisible_rejected(self):
        nat = NativeDedupReplay(64, OBS, n_stripes=4)
        nat.add(np.ones(6), make_chunk(1, 0, 0))
        with pytest.raises(ValueError, match="n_stripes"):
            nat.sample(10)

    def test_threaded_adds_and_samples(self):
        import threading

        nat = NativeDedupReplay(256, OBS, frame_ratio=2.0, n_stripes=4)
        for c in stream(10):
            nat.add(np.ones(c.action.shape[0]), c)
        errs = []

        def sampler():
            try:
                for t in range(50):
                    b = nat.sample(16, rng=np.random.default_rng(t))
                    assert np.isfinite(b.is_weights).all()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def adder(src):
            try:
                fbase, prev = 0, 0
                for i in range(30):
                    c = make_chunk(src, i, fbase, carry=2 if i else 0,
                                   prev_frames=prev)
                    nat.add(np.ones(c.action.shape[0]), c)
                    fbase += c.frames.shape[0]
                    prev = c.frames.shape[0]
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=sampler)] + [
            threading.Thread(target=adder, args=(100 + s,)) for s in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs


class TestStripedFanOut:
    """The parallel per-stripe fan-out (rc_sample_stripe / rc_update_stripe
    through the wrapper's persistent thread pool): striped calls must
    overlap, not serialize behind the wrapper."""

    def _filled(self, n_stripes=2, capacity=256):
        nat = NativeDedupReplay(capacity, OBS, frame_ratio=2.0,
                                n_stripes=n_stripes)
        prng = np.random.default_rng(0)
        for c in stream(40):
            nat.add(np.abs(prng.normal(size=c.action.shape[0])) + 0.1, c)
        return nat

    def test_fanout_bit_parity_with_serial_rc_sample(self):
        """Same uniforms through the parallel fan-out and the serial C
        rc_sample: identical slots, bit-identical weights, same rows."""
        from ape_x_dqn_tpu.replay.native_dedup import (
            _f32p, _f64p, _i32p, _i64p, _p, _u8p,
        )

        nat = self._filled(n_stripes=4)
        B = 32
        for trial in range(5):
            u = np.ascontiguousarray(
                np.random.default_rng(trial).random(B)
            )
            got = nat._sample_with_uniforms(u.copy(), beta=0.5)
            idx = np.empty(B, np.int64)
            w = np.empty(B, np.float64)
            obs = np.empty((B, *OBS), np.uint8)
            nxt = np.empty((B, *OBS), np.uint8)
            act = np.empty(B, np.int32)
            rew = np.empty(B, np.float32)
            dis = np.empty(B, np.float32)
            rc = nat._lib.rc_sample(
                nat._handle, B, 0.5, _p(u, _f64p), _p(idx, _i64p),
                _p(w, _f64p), _p(obs, _u8p), _p(nxt, _u8p),
                _p(act, _i32p), _p(rew, _f32p), _p(dis, _f32p),
            )
            assert rc == 0
            np.testing.assert_array_equal(got.indices, idx.astype(np.int32))
            np.testing.assert_array_equal(
                got.is_weights, w.astype(np.float32)
            )
            np.testing.assert_array_equal(got.transition.obs, obs)
            np.testing.assert_array_equal(got.transition.next_obs, nxt)
            np.testing.assert_array_equal(got.transition.action, act)

    def test_update_fanout_parity_and_duplicate_last_wins(self):
        a, b = self._filled(n_stripes=4), self._filled(n_stripes=4)
        C = a.capacity
        rng = np.random.default_rng(3)
        # Duplicates across and within stripes; later entries must win.
        idx = rng.integers(0, min(C, 200), size=64).astype(np.int64)
        idx[10] = idx[40]  # forced duplicate
        prio = (np.abs(rng.normal(size=64)) + 0.05).astype(np.float32)
        a.update_priorities(idx, prio)          # parallel fan-out
        b._lib.rc_update(                        # serial C spelling
            b._handle, 64,
            idx.ctypes.data_as(
                __import__("ctypes").POINTER(__import__("ctypes").c_int64)
            ),
            prio.ctypes.data_as(
                __import__("ctypes").POINTER(__import__("ctypes").c_float)
            ),
        )
        for s in range(C):
            assert a._lib.rc_get_mass(a._handle, s) == \
                b._lib.rc_get_mass(b._handle, s)

    def test_stripe_calls_overlap_in_wall_clock(self):
        """The satellite's pin: per-stripe sample calls genuinely overlap
        — the span intervals of one fan-out intersect.  Sized so each
        stripe call does several ms of GIL-released gather work; retried
        because a 1-core host's scheduler may run short calls back-to-back
        on any single try."""
        big_obs = (48, 48, 1)
        M = 256
        nat = NativeDedupReplay(2048, big_obs, frame_ratio=2.0,
                                n_stripes=2)
        rng = np.random.default_rng(0)
        for i in range(8):
            frames = rng.integers(
                0, 255, (M + 1, *big_obs), dtype=np.uint8
            )
            nat.add(
                (np.abs(rng.normal(size=M)) + 0.1).astype(np.float32),
                DedupChunk(
                    frames=frames, source=1, chunk_seq=i,
                    obs_ref=np.arange(M, dtype=np.int32),
                    next_ref=np.arange(1, M + 1, dtype=np.int32),
                    action=rng.integers(0, 4, M).astype(np.int32),
                    reward=rng.normal(size=M).astype(np.float32),
                    discount=np.full(M, 0.97, np.float32),
                    prev_frames=M + 1,
                ),
            )
        overlapped = False
        for trial in range(15):
            nat.sample(8192, rng=np.random.default_rng(trial))
            spans = nat.last_stripe_spans
            assert len(spans) == 2
            if max(s[0] for s in spans) < min(s[1] for s in spans):
                overlapped = True
                break
        assert overlapped, (
            f"stripe calls never overlapped in 15 tries: {spans}"
        )

"""Central inference (SEED-style paramless actors) — ISSUE 12.

Covers: the batched F_IREQ/F_IREP codec, the v2 serve hello's run-token
discipline, the live server's adversarial decode matrix on the
obs→inference path (torn/bitflipped/oversize request AND reply frames
counted, never decoded), whole-request retry applied exactly once per
lost reply, the ε-ladder slice identity pin (worker-side ε on the
returned argmax, same global partition as local mode), the typed
serving-outage degradation path (block-with-stall vs local fallback),
the fleet's selector seam, the obs `inference` schema contract, the
replay-service `service_codec=auto` reply gate, and seeded
central-vs-local convergence parity on fake-atari."""

import io
import os
import socket
import struct
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from ape_x_dqn_tpu.runtime.net import (
    E_BAD_REQUEST,
    E_OVERLOADED,
    F_IREP,
    F_IREQ,
    F_SERR,
    FRAME,
    CODEC_OFF,
    CODEC_ZLIB,
    FrameParser,
    decode_error,
    decode_inference_reply,
    decode_inference_request,
    encode_inference_reply,
    encode_inference_request,
    frame_bytes,
    parse_serve_hello_ext,
    serve_hello_bytes,
    serve_hello_ext_bytes,
)
from ape_x_dqn_tpu.serving.batcher import ServedAction, ServerOverloaded
from ape_x_dqn_tpu.serving.central import (
    CentralInferenceClient,
    CentralSelector,
    InferenceUnavailable,
    aggregate_inference_stats,
    split_groups,
)
from ape_x_dqn_tpu.serving.net_server import ServingNetServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StubPolicy:
    """PolicyServer stand-in: greedy action = obs row sum mod A."""

    def __init__(self, num_actions: int = 4, version: int = 7):
        self.num_actions = num_actions
        self.param_version = version
        self.served = 0
        self.fail_with = None

    def q_row(self, obs) -> np.ndarray:
        a = int(np.asarray(obs, np.uint64).sum()) % self.num_actions
        q = np.zeros(self.num_actions, np.float32)
        q[a] = 1.0
        return q

    def submit(self, obs) -> Future:
        if self.fail_with is not None:
            raise self.fail_with
        f = Future()
        self.served += 1
        q = self.q_row(obs)
        f.set_result(ServedAction(
            int(q.argmax()), q, self.param_version, 0.0,
        ))
        return f


@pytest.fixture
def net_server():
    srv = ServingNetServer(StubPolicy(), run_token=4242).start()
    yield srv
    srv.close()


def _client(srv, **kw):
    kw.setdefault("token", 4242)
    kw.setdefault("seed", 1)
    return CentralInferenceClient("127.0.0.1", srv.port, **kw)


def _obs(n=6, shape=(8, 8, 1), seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (n, *shape), dtype=np.uint8
    )


def _wait(cond, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timeout waiting for {msg}")


class TestInferenceCodec:
    def test_request_roundtrip_bit_exact(self):
        obs = _obs(5, (4, 12, 12))
        obs[3] = obs[1]          # identical rows: the dedup window's prey
        for codec in (CODEC_OFF, CODEC_ZLIB):
            payload, st = encode_inference_request(9, obs, codec=codec)
            rid, rows = decode_inference_request(payload)
            assert rid == 9 and len(rows) == 5
            for i in range(5):
                np.testing.assert_array_equal(rows[i], obs[i])
        # The duplicate row deduped: 4 plane refs, full row's bytes saved.
        assert st["dedup_hits"] == 4
        assert st["dedup_bytes"] == obs[1].nbytes

    def test_reply_roundtrip(self):
        acts = np.array([2, 0, 1], np.int32)
        q = np.arange(9, dtype=np.float32).reshape(3, 3)
        rid, back_a, ver, back_q = decode_inference_reply(
            encode_inference_reply(5, acts, 33, q)
        )
        assert (rid, ver) == (5, 33)
        np.testing.assert_array_equal(back_a, acts)
        np.testing.assert_array_equal(back_q, q)

    def test_reply_geometry_mismatch_raises(self):
        body = bytearray(encode_inference_reply(
            1, np.zeros(2, np.int32), 0, np.zeros((2, 3), np.float32)
        ))
        with pytest.raises(ValueError):
            decode_inference_reply(bytes(body[:-1]))

    def test_row_count_head_mismatch_raises(self):
        payload = bytearray(encode_inference_request(1, _obs(3))[0])
        # Head says 4 rows, body carries 3.
        struct.pack_into("<I", payload, 8, 4)
        with pytest.raises(ValueError, match="rows"):
            decode_inference_request(bytes(payload))

    def test_compressed_on_off_negotiation_raises(self):
        payload, st = encode_inference_request(
            1, np.zeros((4, 64, 64, 1), np.uint8), codec=CODEC_ZLIB
        )
        assert st["compressed"]
        with pytest.raises(ValueError, match="codec"):
            decode_inference_request(payload, allow_zlib=False)


class TestHelloToken:
    def test_ext_hello_roundtrip(self):
        h = serve_hello_ext_bytes(3, 2, 99, CODEC_ZLIB)
        ext = parse_serve_hello_ext(h[8:])
        assert ext == {"wid": 3, "attempt": 2, "token": 99,
                       "codec": CODEC_ZLIB, "flags": 0}
        # The flags byte lives in what was pad: a flags-0 hello is
        # byte-identical to the pre-flags wire, and a trace-flagged one
        # round-trips the bit.
        from ape_x_dqn_tpu.runtime.net import HELLO_FLAG_TRACE

        traced = serve_hello_ext_bytes(3, 2, 99, CODEC_ZLIB,
                                       flags=HELLO_FLAG_TRACE)
        assert parse_serve_hello_ext(traced[8:])["flags"] == HELLO_FLAG_TRACE
        assert traced != h and len(traced) == len(h)

    def test_wrong_token_rejected_before_framing(self, net_server):
        s = socket.create_connection(("127.0.0.1", net_server.port), 5.0)
        s.sendall(serve_hello_ext_bytes(0, 0, 1, CODEC_OFF))
        _wait(lambda: net_server.token_rejects == 1, msg="token reject")
        assert net_server.stats()["requests"] == 0
        s.close()

    def test_anonymous_v1_hello_still_accepted(self, net_server):
        # The single-request front door stays public even with a token
        # set: v1 hellos carry no token and are admitted.
        from ape_x_dqn_tpu.runtime.net import F_SREQ, encode_request

        s = socket.create_connection(("127.0.0.1", net_server.port), 5.0)
        s.sendall(serve_hello_bytes())
        s.sendall(frame_bytes(
            F_SREQ, 1, [encode_request(1, np.zeros(8, np.uint8))]
        ))
        _wait(lambda: net_server.replies == 1, msg="v1 reply")
        s.close()

    def test_good_token_lands_per_source_stats(self, net_server):
        cl = _client(net_server, wid=11)
        try:
            cl.select(_obs(4), timeout_s=10)
        finally:
            cl.close()
        src = net_server.stats()["sources"]
        assert src["11"]["rows"] == 4
        assert src["11"]["replies"] >= 1


class TestServerInference:
    def test_batched_select_matches_stub(self, net_server):
        obs = _obs(7)
        cl = _client(net_server, inflight=3)
        try:
            actions, q, version = cl.select(obs, timeout_s=10)
        finally:
            cl.close()
        stub = StubPolicy()
        want = np.array([stub.q_row(o).argmax() for o in obs], np.int32)
        np.testing.assert_array_equal(actions, want)
        assert version == 7
        assert q.shape == (7, 4)
        st = net_server.stats()
        assert st["inference_requests"] == 3       # inflight groups
        assert st["inference_rows"] == 7
        assert st["torn_frames"] == 0

    def test_zlib_negotiated_end_to_end(self, net_server):
        cl = _client(net_server, codec="zlib",
                     inflight=1)
        try:
            obs = np.zeros((6, 32, 32, 1), np.uint8)   # compresses well
            actions, _q, _v = cl.select(obs, timeout_s=10)
        finally:
            cl.close()
        assert cl.compressed_frames >= 1
        assert cl.wire_bytes_out < obs.nbytes      # the codec won
        assert net_server.stats()["torn_frames"] == 0

    def test_shed_is_typed_and_retried(self, net_server):
        stub = net_server._server
        stub.fail_with = ServerOverloaded("full")
        cl = _client(net_server)

        def lift():
            time.sleep(0.3)
            stub.fail_with = None

        t = threading.Thread(target=lift)
        t.start()
        try:
            actions, _q, _v = cl.select(_obs(4), timeout_s=15)
            assert actions.shape == (4,)
            assert cl.shed_seen >= 1       # refusals were typed, counted
            assert cl.torn_replies == 0    # ...and never torn
        finally:
            t.join()
            cl.close()

    def test_bad_body_typed_not_torn(self, net_server):
        s = socket.create_connection(("127.0.0.1", net_server.port), 5.0)
        s.sendall(serve_hello_ext_bytes(0, 0, 4242, CODEC_OFF))
        # Well-framed F_IREQ whose body is garbage: crc passes, decode
        # must reply typed E_BAD_REQUEST — not count torn.
        s.sendall(frame_bytes(F_IREQ, 1, [b"\x99" * 64]))
        parser = FrameParser()
        deadline = time.monotonic() + 5.0
        got = None
        while got is None and time.monotonic() < deadline:
            parser.feed(s.recv(4096))
            got = parser.next()
        kind, payload = got
        assert kind == F_SERR
        assert decode_error(payload)[1] == E_BAD_REQUEST
        assert net_server.torn_frames == 0
        s.close()

    def test_torn_request_frames_never_decoded(self, net_server):
        """Truncation / crc bitflip / oversize prefix on the F_IREQ
        plane: counted torn, nothing reaches the batcher."""
        stub = net_server._server
        good = frame_bytes(
            F_IREQ, 1, [encode_inference_request(1, _obs(4))[0]]
        )
        cases = []
        cases.append(good[: FRAME.size + 10])             # truncated body
        flipped = bytearray(good)
        flipped[FRAME.size + 4] ^= 0x40                   # payload bitflip
        cases.append(bytes(flipped))
        huge = bytearray(good)
        struct.pack_into("<I", huge, 0, 1 << 29)          # absurd length
        cases.append(bytes(huge))
        before = stub.served
        for i, wire in enumerate(cases):
            torn0 = net_server.torn_frames
            s = socket.create_connection(
                ("127.0.0.1", net_server.port), 5.0
            )
            s.sendall(serve_hello_ext_bytes(0, 0, 4242, CODEC_OFF))
            s.sendall(wire)
            s.shutdown(socket.SHUT_WR)
            _wait(lambda: net_server.torn_frames > torn0,
                  msg=f"torn case {i}")
            s.close()
        assert stub.served == before        # nothing decoded, ever


class _FlippingProxy:
    """TCP proxy that XORs one byte of the Nth server→client payload
    byte window — the bitflipped-REPLY-stream shape."""

    def __init__(self, dst_port: int, flip_at: int = 60):
        self._dst = dst_port
        self._flip_at = flip_at
        self._flipped = False
        self._lsock = socket.socket()
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(8)
        self.port = self._lsock.getsockname()[1]
        self._stop = False
        self._threads = []
        t = threading.Thread(target=self._accept, daemon=True)
        t.start()
        self._threads.append(t)

    def _accept(self):
        while not self._stop:
            try:
                c, _ = self._lsock.accept()
            except OSError:
                return
            u = socket.create_connection(("127.0.0.1", self._dst), 5.0)
            for src, dst, flip in ((c, u, False), (u, c, True)):
                t = threading.Thread(
                    target=self._pump, args=(src, dst, flip), daemon=True
                )
                t.start()
                self._threads.append(t)

    def _pump(self, src, dst, flip):
        seen = 0
        while not self._stop:
            try:
                data = src.recv(4096)
            except OSError:
                break
            if not data:
                break
            if flip and not self._flipped and seen + len(data) > \
                    self._flip_at:
                b = bytearray(data)
                b[self._flip_at - seen] ^= 0x10
                data = bytes(b)
                self._flipped = True
            seen += len(data)
            try:
                dst.sendall(data)
            except OSError:
                break
        for s in (src, dst):
            try:
                s.close()
            except OSError:
                pass

    def close(self):
        self._stop = True
        try:
            self._lsock.close()
        except OSError:
            pass


class TestClientAdversarial:
    def test_bitflipped_reply_dropped_and_retried(self, net_server):
        proxy = _FlippingProxy(net_server.port, flip_at=40)
        cl = CentralInferenceClient(
            "127.0.0.1", proxy.port, token=4242, seed=2, inflight=1,
        )
        try:
            obs = _obs(4)
            actions, _q, _v = cl.select(obs, timeout_s=20)
            stub = StubPolicy()
            want = np.array(
                [stub.q_row(o).argmax() for o in obs], np.int32
            )
            np.testing.assert_array_equal(actions, want)
            # The flipped stream was detected torn client-side, never
            # decoded, and the request retried whole.
            assert cl.torn_replies >= 1
            assert cl.retries >= 1
        finally:
            cl.close()
            proxy.close()

    def test_lost_reply_retried_exactly_once(self):
        """A server that swallows the FIRST request: the client's io
        deadline expires, it reconnects and resends the request WHOLE —
        exactly one retry round for one lost reply."""
        stub = StubPolicy()
        srv = ServingNetServer(stub, run_token=4242).start()
        orig = srv._handle_inference
        dropped = {"n": 0}

        def dropping(conn, payload):
            if dropped["n"] == 0:
                dropped["n"] += 1
                return            # swallow: no reply, no error
            orig(conn, payload)

        srv._handle_inference = dropping
        cl = CentralInferenceClient(
            "127.0.0.1", srv.port, token=4242, seed=3, inflight=1,
            io_timeout_s=0.5,
        )
        try:
            cl.select(_obs(3), timeout_s=20)
            assert dropped["n"] == 1
            assert cl.retries == 1
        finally:
            cl.close()
            srv.close()

    def test_outage_is_typed(self):
        # Nothing listening: the deadline expires into the TYPED signal.
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        cl = CentralInferenceClient("127.0.0.1", port, seed=4)
        try:
            with pytest.raises(InferenceUnavailable):
                cl.select(_obs(2), timeout_s=1.0)
            assert cl.stall_s > 0
        finally:
            cl.close()


class TestSelector:
    def test_epsilon_ladder_slice_identity(self):
        """The partition pin: worker wid's central-mode ε slice IS the
        global ladder slice local mode would use — actor identity is
        placement- and inference-mode-independent."""
        from ape_x_dqn_tpu.ops.exploration import epsilon_ladder
        from ape_x_dqn_tpu.runtime.process_actors import worker_slice

        N, W = 16, 4
        ladder = np.asarray(epsilon_ladder(0.4, 7.0, N))
        for wid in range(W):
            lo, hi = worker_slice(wid, N, W)
            sel = CentralSelector(
                CentralInferenceClient("127.0.0.1", 1, seed=0),
                ladder[lo:hi], 4,
            )
            np.testing.assert_allclose(sel.epsilons, ladder[lo:hi])
            sel.close()

    def test_epsilon_zero_is_server_greedy(self, net_server):
        obs = _obs(5)
        cl = _client(net_server)
        sel = CentralSelector(cl, np.zeros(5), 4, seed=9)
        try:
            actions, q, _v = sel.select(obs, 0)
        finally:
            sel.close()
        stub = StubPolicy()
        want = np.array([stub.q_row(o).argmax() for o in obs], np.int32)
        np.testing.assert_array_equal(actions, want)
        np.testing.assert_array_equal(
            actions, np.asarray(q).argmax(axis=1)
        )

    def test_epsilon_one_is_seeded_uniform(self, net_server):
        obs = _obs(64)
        cl = _client(net_server)
        sel = CentralSelector(cl, np.ones(64), 4, seed=9)
        cl2 = _client(net_server)
        sel2 = CentralSelector(cl2, np.ones(64), 4, seed=9)
        try:
            a1, _, _ = sel.select(obs, 0)
            a2, _, _ = sel2.select(obs, 0)
        finally:
            sel.close()
            sel2.close()
        np.testing.assert_array_equal(a1, a2)   # seeded: reproducible
        assert len(np.unique(a1)) == 4          # ...and actually random

    def test_outage_uses_local_fallback(self):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        calls = []

        def fallback(obs, step):
            calls.append(step)
            return (np.zeros(obs.shape[0], np.int32),
                    np.zeros((obs.shape[0], 4), np.float32), 3)

        cl = CentralInferenceClient("127.0.0.1", port, seed=5)
        sel = CentralSelector(cl, np.zeros(2), 4, timeout_s=0.5,
                              fallback=fallback)
        try:
            actions, _q, version = sel.select(_obs(2), 17)
        finally:
            sel.close()
        assert calls == [17]
        assert version == 3
        assert sel.outages == 1
        assert cl.fallback_steps == 1

    def test_outage_without_fallback_blocks_until_stop(self):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        stop = threading.Event()
        cl = CentralInferenceClient("127.0.0.1", port, seed=6)
        sel = CentralSelector(cl, np.zeros(2), 4, timeout_s=0.3,
                              should_stop=stop.is_set)
        threading.Timer(1.0, stop.set).start()
        t0 = time.monotonic()
        try:
            with pytest.raises(InferenceUnavailable):
                sel.select(_obs(2), 0)
        finally:
            sel.close()
        # It blocked past the per-attempt deadline (outages counted) and
        # only gave up when stopped.
        assert time.monotonic() - t0 >= 0.9
        assert sel.outages >= 1
        assert cl.stall_s > 0

    def test_split_groups_balanced(self):
        assert split_groups(7, 3) == [(0, 2), (2, 4), (4, 7)]
        assert split_groups(2, 8) == [(0, 1), (1, 2)]


class TestFleetSeam:
    def test_collect_with_selector_is_paramless(self, net_server):
        """ActorFleet.collect(selector=...) never touches params and
        adopts the reply version; chunks/priorities flow as local."""
        from ape_x_dqn_tpu.actors import ActorFleet
        from ape_x_dqn_tpu.models.dueling import build_network

        net = build_network("mlp", 2)
        env_fns = [
            (lambda i=i: __import__(
                "ape_x_dqn_tpu.envs", fromlist=["make_env"]
            ).make_env("chain:6", seed=100 + i))
            for i in range(4)
        ]
        fleet = ActorFleet(env_fns, net, n_step=3, flush_every=8, seed=0)
        cl = _client(net_server)
        sel = CentralSelector(cl, np.asarray(fleet._epsilons), 2, seed=1)
        try:
            chunks, _stats = fleet.collect(24, selector=sel)
        finally:
            sel.close()
        assert fleet.params is None            # truly paramless
        assert fleet.param_version == 7        # adopted from replies
        assert chunks and all(
            np.isfinite(c.priorities).all() for c in chunks
        )

    def test_collect_without_selector_still_requires_params(self):
        from ape_x_dqn_tpu.actors import ActorFleet
        from ape_x_dqn_tpu.envs import make_env
        from ape_x_dqn_tpu.models.dueling import build_network

        fleet = ActorFleet(
            [lambda: make_env("chain:6", seed=0)],
            build_network("mlp", 2), seed=0,
        )
        with pytest.raises(RuntimeError, match="no params"):
            fleet.collect(4)


def _doc_keys(section_header):
    # Shared parser (apexlint satellite): one implementation in
    # ape_x_dqn_tpu/analysis/metrics_doc.py serves every schema pin.
    from ape_x_dqn_tpu.analysis.metrics_doc import doc_section_keys

    return doc_section_keys(
        section_header, os.path.join(REPO, "docs", "METRICS.md"))


@pytest.fixture(scope="module")
def central_thread_run():
    """One small central-mode thread run (chain MDP, auto in-process
    serving tier) shared by the schema + freshness tests."""
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.num_actors = 4
    cfg.actor.T = 100_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 16
    cfg.actor.inference = "central"
    cfg.actor.inference_inflight = 2
    cfg.actor.inference_codec = "zlib"
    cfg.serving.max_batch = 8
    cfg.serving.max_wait_ms = 2.0
    # The 80 learner steps last ~0.2 s: at the default 0.25 s poll, whether
    # one hot reload lands inside the run is a matter of thread phase.
    cfg.serving.reload_poll_s = 0.02
    cfg.learner.min_replay_mem_size = 256
    cfg.learner.publish_every = 5
    cfg.learner.total_steps = 80
    cfg.learner.optimizer = "adam"
    cfg.replay.capacity = 4096
    cfg.validate()
    buf = io.StringIO()
    pipe = AsyncPipeline(cfg, logger=MetricLogger(stream=buf), log_every=40)
    final = pipe.run(learner_steps=80, warmup_timeout=180.0)
    return {"final_record": final, "pipe": pipe}


def test_in_process_server_params_do_not_alias_train_state():
    """The learner's jitted step donates the train state, and on a TPU a
    donated buffer is deleted: a server whose first params ARE the train
    state's buffers (device_put of an on-device array is no copy) answers
    with errors from the first learner call until its first hot reload —
    found by chip_smoke.py's serving leg, invisible on the CPU backend."""
    import jax

    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.num_actors = 2
    cfg.actor.inference = "central"
    cfg.serving.max_batch = 2
    cfg.learner.min_replay_mem_size = 64
    cfg.replay.capacity = 256
    cfg.validate()
    pipe = AsyncPipeline(cfg, logger=MetricLogger(stream=io.StringIO()))
    try:
        state = {
            id(x) for x in jax.tree_util.tree_leaves(pipe.comps.state.params)
        }
        served = jax.tree_util.tree_leaves(pipe._central_server._live[0])
        assert served and not any(id(x) in state for x in served)
    finally:
        pipe._close_obs()
        pipe._publisher.close()


class TestObsSchema:
    def test_inference_section_matches_doc(self, central_thread_run):
        doc = _doc_keys("## Inference schema")
        assert doc, "Inference schema doc section missing"
        rec = central_thread_run["final_record"]
        assert "inference" in rec, "inference section absent from emit"
        assert set(doc) == set(rec["inference"]), (
            set(doc) ^ set(rec["inference"])
        )

    def test_serving_net_doc_covers_new_keys(self):
        doc = _doc_keys("## Serving net schema")
        for k in ("token_rejects", "inference_requests",
                  "inference_rows", "inference_replies", "sources"):
            assert k in doc, k

    def test_central_run_is_fresh_and_clean(self, central_thread_run):
        inf = central_thread_run["final_record"]["inference"]
        assert inf["mode"] == "central"
        assert inf["replies"] > 0
        assert inf["torn_replies"] == 0
        assert inf["param_version"] >= 1
        # Freshness: replies track the store within a couple publishes
        # (the reload poll cadence bounds the lag).
        assert inf["version_lag"] is not None and inf["version_lag"] <= 5
        assert inf["rtt"]["count"] > 0
        # And the in-process batcher really batched across the fleet.
        assert inf["batch_occupancy_mean"] is not None

    def test_varz_provider_registered(self, central_thread_run):
        snap = central_thread_run["pipe"].obs_registry.snapshot()
        assert "inference" in snap
        assert snap["inference"]["mode"] == "central"


class TestAggregation:
    def test_aggregate_merges_counters_and_rtt(self):
        from ape_x_dqn_tpu.utils.metrics import LatencyHistogram

        h1, h2 = LatencyHistogram(), LatencyHistogram()
        h1.record(0.01)
        h2.record(0.1)
        dicts = []
        for h, reqs, v in ((h1, 3, 5), (h2, 4, 9)):
            with h._lock:
                state = {"counts": list(h._counts), "count": h._count,
                         "sum": h._sum, "max": h._max}
            dicts.append({
                "requests": reqs, "rows": reqs, "replies": reqs,
                "retries": 0, "reconnects": 0, "shed_seen": 0,
                "torn_replies": 0, "errors": 0, "fallback_steps": 0,
                "selects": reqs, "outages": 0, "stall_ms": 1.5,
                "param_version": v, "wire_bytes_out": 10,
                "logical_bytes_out": 20, "rtt_state": state,
            })
        out = aggregate_inference_stats(dicts)
        assert out["requests"] == 7
        assert out["param_version"] == 5      # freshness floor
        assert out["stall_ms"] == 3.0
        assert out["rtt"]["count"] == 2
        assert out["wire_over_logical"] == 0.5


class TestReplaySvcAutoCodec:
    def test_auto_gates_on_backpressure(self):
        """service_codec=auto: raw replies while the reply path is
        unblocked; zlib after observed backpressure; raw again after the
        idle decay."""
        from ape_x_dqn_tpu.replay.buffer import PrioritizedReplay
        from ape_x_dqn_tpu.replay.service import ReplayShardServer

        rep = PrioritizedReplay(64, (4, 4, 1))
        srv = ReplayShardServer(rep, 0, codec="auto")
        try:
            assert srv._reply_codec() == CODEC_OFF        # unloaded: raw
            srv.reply_full_waits += 1                     # blocked send
            assert srv._reply_codec() == CODEC_ZLIB       # wire-bound
            for _ in range(400):                          # idle decay
                srv._reply_codec()
            assert srv._reply_codec() == CODEC_OFF
        finally:
            srv.close()

    def test_auto_end_to_end_unloaded_ships_raw(self):
        from ape_x_dqn_tpu.replay.buffer import PrioritizedReplay
        from ape_x_dqn_tpu.replay.service import (
            ReplayShardServer,
            ShardClient,
            ShardedReplayClient,
        )

        rep = PrioritizedReplay(128, (8, 8, 1))
        srv = ReplayShardServer(rep, 0, token=7, codec="auto").start()
        cl = ShardedReplayClient(
            [{"id": 0, "host": "127.0.0.1", "port": srv.port, "base": 0,
              "capacity": 128, "incarnation": srv.incarnation}],
            token=7, codec="auto", request_timeout_s=5.0,
        )
        try:
            rng = np.random.default_rng(0)

            class B:
                pass

            b = B()
            b.obs = rng.integers(0, 255, (32, 8, 8, 1), dtype=np.uint8)
            b.next_obs = np.roll(b.obs, -1, axis=0)
            b.action = np.zeros(32, np.int32)
            b.reward = np.zeros(32, np.float32)
            b.discount = np.ones(32, np.float32)
            cl.add(np.ones(32), b)
            for _ in range(4):
                cl.sample(8, rng=rng)
            sc = ShardClient(0, "127.0.0.1", srv.port, token=7,
                             client_id=99, incarnation=srv.incarnation,
                             codec="auto")
            st = sc.shard_stats(timeout=5.0)
            sc.close()
            assert st["codec_policy"] == "auto"
            assert st["reply_raw"] >= 4       # unloaded loopback: raw
            assert st["reply_zlib"] == 0
        finally:
            cl.close()
            srv.close()


class TestConvergenceParity:
    """Seeded central-vs-local parity on fake-atari: same config, same
    seed, the two inference modes must track the same learning curve
    within tolerance (the rewards are policy-independent by design, so
    the value estimates — mean_q — and the greedy eval score are the
    curve; the structural claims — replies flowed, zero torn, fresh
    versions — make the run central in fact, not just in name)."""

    def _run(self, inference: str):
        from ape_x_dqn_tpu.config import ApexConfig
        from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
        from ape_x_dqn_tpu.utils.metrics import MetricLogger

        cfg = ApexConfig()
        cfg.network = "mlp"
        cfg.env.name = "fake-atari"
        cfg.actor.num_actors = 2
        cfg.actor.T = 100_000
        cfg.actor.flush_every = 8
        cfg.actor.sync_every = 16
        cfg.actor.inference = inference
        cfg.actor.inference_inflight = 2
        cfg.serving.max_batch = 8
        cfg.serving.max_wait_ms = 2.0
        cfg.learner.min_replay_mem_size = 300
        cfg.learner.publish_every = 10
        cfg.learner.total_steps = 150
        cfg.learner.optimizer = "adam"
        cfg.learner.learning_rate = 1e-3
        cfg.replay.capacity = 4096
        cfg.seed = 11
        cfg.validate()
        buf = io.StringIO()
        pipe = AsyncPipeline(
            cfg, logger=MetricLogger(stream=buf), log_every=75,
            eval_every=150, eval_episodes=2,
        )
        final = pipe.run(learner_steps=150, warmup_timeout=300.0)
        return final, pipe

    def test_central_matches_local_curve(self):
        final_l, pipe_l = self._run("local")
        final_c, pipe_c = self._run("central")
        # Central was really central: selection flowed through the tier.
        inf = final_c["inference"]
        assert inf["replies"] > 0 and inf["torn_replies"] == 0
        assert inf["version_lag"] is not None and inf["version_lag"] <= 5
        # Curve parity: the value estimate both runs converge toward.
        q_l = final_l["learner/mean_q"]
        q_c = final_c["learner/mean_q"]
        assert np.isfinite(q_l) and np.isfinite(q_c)
        assert abs(q_c - q_l) <= 0.5 * max(1.0, abs(q_l)), (q_l, q_c)
        # Eval parity (greedy rollouts on the learned nets).
        s_l = pipe_l.eval_scores[-1]
        s_c = pipe_c.eval_scores[-1]
        assert abs(s_c - s_l) <= 0.25 * max(1.0, abs(s_l)), (s_l, s_c)

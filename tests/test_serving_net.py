"""Network serving tier tests: the request/reply codec + the socket
server's adversarial decode matrix (the serving mirror of
tests/test_net_transport.py — torn frames typed, never decoded,
connection retired), health-aware routing (503 drain / recovery
re-entry / dead-replica failover with client retry), the socket param
source against a real hub, and the APXC param-tail fallback chain."""

import json
import socket
import threading
import time
from concurrent.futures import Future
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from ape_x_dqn_tpu.runtime.net import (
    E_BAD_REQUEST,
    E_OVERLOADED,
    F_SERR,
    F_SREP,
    F_SREQ,
    FRAME,
    FrameParser,
    decode_error,
    decode_reply,
    decode_request,
    encode_error,
    encode_reply,
    encode_request,
    frame_bytes,
    serve_hello_bytes,
)
from ape_x_dqn_tpu.serving.batcher import ServedAction, ServerOverloaded
from ape_x_dqn_tpu.serving.net_server import ServingClient, ServingNetServer
from ape_x_dqn_tpu.serving.router import ServingRouter
from ape_x_dqn_tpu.serving.sources import (
    ParamTailSource,
    ParamTailWriter,
    parse_hub_spec,
)


class StubPolicy:
    """PolicyServer stand-in: instant completed futures, no jax."""

    def __init__(self, num_actions: int = 4, version: int = 7):
        self.param_version = version
        self.served = 0
        self.fail_with = None        # exception to raise from submit

    def submit(self, obs) -> Future:
        if self.fail_with is not None:
            raise self.fail_with
        f = Future()
        self.served += 1
        f.set_result(ServedAction(
            int(np.asarray(obs).sum()) % 4,
            np.arange(4, dtype=np.float32),
            self.param_version, 0.0,
        ))
        return f


@pytest.fixture
def net_server():
    srv = ServingNetServer(StubPolicy()).start()
    yield srv
    srv.close()


def _raw_conn(port: int, hello: bytes = None) -> socket.socket:
    s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
    s.sendall(serve_hello_bytes() if hello is None else hello)
    return s


def _wait(cond, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timeout waiting for {msg}")


class TestCodec:
    def test_request_roundtrip(self):
        obs = np.random.default_rng(0).integers(
            0, 255, (84, 84, 1), dtype=np.uint8
        )
        rid, back = decode_request(encode_request(123, obs))
        assert rid == 123
        np.testing.assert_array_equal(back, obs)

    def test_reply_roundtrip(self):
        q = np.arange(6, dtype=np.float32) * 0.5
        rid, action, version, back = decode_reply(
            encode_reply(9, 3, 42, q)
        )
        assert (rid, action, version) == (9, 3, 42)
        np.testing.assert_array_equal(back, q)

    def test_error_roundtrip(self):
        rid, code, msg = decode_error(
            encode_error(5, E_OVERLOADED, "queue full")
        )
        assert (rid, code, msg) == (5, E_OVERLOADED, "queue full")

    def test_shape_mismatch_typed(self):
        payload = bytearray(encode_request(1, np.zeros((4, 4), np.uint8)))
        with pytest.raises(ValueError, match="shape"):
            decode_request(bytes(payload[:-1]))   # one body byte short

    def test_bad_dtype_code_typed(self):
        payload = bytearray(encode_request(1, np.zeros(4, np.uint8)))
        payload[9] = 99                           # dtype code field
        with pytest.raises(ValueError, match="dtype"):
            decode_request(bytes(payload))


class TestServerAdversarial:
    """The decode matrix against a LIVE socket server: every framing
    fault is counted torn, nothing reaches the batcher, and the
    connection is retired."""

    def _req_frame(self, seq=1, rid=1):
        return frame_bytes(F_SREQ, seq,
                           [encode_request(rid, np.zeros(8, np.uint8))])

    def test_truncation_mid_prefix(self, net_server):
        s = _raw_conn(net_server.port)
        s.sendall(self._req_frame()[:FRAME.size - 3])
        s.close()
        _wait(lambda: net_server.torn_frames == 1, msg="torn count")
        assert net_server.requests == 0

    def test_truncation_mid_payload(self, net_server):
        s = _raw_conn(net_server.port)
        s.sendall(self._req_frame()[:FRAME.size + 5])
        s.close()
        _wait(lambda: net_server.torn_frames == 1, msg="torn count")
        assert net_server.requests == 0

    def test_crc_bitflip_retires_connection(self, net_server):
        buf = bytearray(self._req_frame())
        buf[FRAME.size + 4] ^= 0x10
        s = _raw_conn(net_server.port)
        s.sendall(bytes(buf))
        _wait(lambda: net_server.torn_frames == 1, msg="torn count")
        assert net_server.requests == 0
        # Connection retired: the peer observes EOF.
        s.settimeout(5.0)
        assert s.recv(64) == b""
        s.close()

    def test_oversize_length_prefix_rejected(self, net_server):
        s = _raw_conn(net_server.port)
        # Within the transport's GiB sanity cap but over the serving
        # plane's max_request_bytes — rejected BEFORE buffering it.
        s.sendall(FRAME.pack(64 << 20, 0, 1, F_SREQ))
        _wait(lambda: net_server.torn_frames == 1, msg="torn count")
        assert net_server.requests == 0
        s.settimeout(5.0)
        assert s.recv(64) == b""
        s.close()

    def test_wrong_kind_is_protocol_violation(self, net_server):
        s = _raw_conn(net_server.port)
        s.sendall(frame_bytes(F_SREP, 1, [b"client-sent-a-reply"]))
        _wait(lambda: net_server.torn_frames == 1, msg="torn count")
        assert net_server.requests == 0
        s.close()

    def test_bad_hello_rejected_before_framing(self, net_server):
        s = _raw_conn(net_server.port, hello=b"GET / HT")
        s.settimeout(5.0)
        assert s.recv(64) == b""
        _wait(lambda: net_server.bad_hellos == 1, msg="bad hello")
        assert net_server.torn_frames == 0
        s.close()

    def test_seq_skip_detected(self, net_server):
        s = _raw_conn(net_server.port)
        s.sendall(self._req_frame(seq=1, rid=1))
        s.sendall(self._req_frame(seq=3, rid=2))
        _wait(lambda: net_server.torn_frames == 1, msg="torn count")
        # The first (verified) request WAS served; the skip retired the
        # stream before the second could be decoded.
        assert net_server.requests == 1
        s.close()

    def test_well_framed_bad_request_is_typed_not_torn(self, net_server):
        bad = bytearray(encode_request(7, np.zeros(8, np.uint8)))
        bad[9] = 99                                # dtype code
        s = _raw_conn(net_server.port)
        s.sendall(frame_bytes(F_SREQ, 1, [bytes(bad)]))
        _wait(lambda: net_server.errors == 1, msg="typed error")
        assert net_server.torn_frames == 0
        # The connection SURVIVES (it framed correctly): an error reply
        # comes back and a follow-up request still works.
        p = FrameParser()
        s.settimeout(5.0)
        while True:
            got = p.next()
            if got is not None:
                break
            p.feed(s.recv(4096))
        kind, payload = got
        assert kind == F_SERR
        assert decode_error(payload)[1] == E_BAD_REQUEST
        s.sendall(self._req_frame(seq=2, rid=8))
        _wait(lambda: net_server.requests == 1, msg="follow-up served")
        s.close()

    def test_shed_is_typed_reply(self, net_server):
        net_server._server.fail_with = ServerOverloaded("full")
        c = ServingClient("127.0.0.1", net_server.port)
        with pytest.raises(ServerOverloaded):
            c.act(np.zeros(8, np.uint8), timeout=5.0)
        assert net_server.shed == 1
        c.close()

    def test_stats_schema_stable(self, net_server):
        keys = set(net_server.stats())
        assert {"port", "connections", "requests", "replies", "shed",
                "torn_frames", "bytes_in", "bytes_out", "param_version",
                "latency"} <= keys


class TestClientRetry:
    def test_roundtrip_and_latency(self, net_server):
        c = ServingClient("127.0.0.1", net_server.port)
        r = c.act(np.ones((4, 4), np.uint8), timeout=5.0)
        assert r.param_version == 7
        assert r.latency_s < 5.0
        assert c.retries == 0
        c.close()

    def test_client_survives_server_restart(self):
        policy = StubPolicy()
        srv = ServingNetServer(policy).start()
        c = ServingClient("127.0.0.1", srv.port)
        assert c.act(np.zeros(4, np.uint8), timeout=5.0).action >= 0
        srv.close()                      # connection dies under the client
        srv2 = ServingNetServer(policy).start()
        c.port = srv2.port               # "router" moved the backend
        r = c.act(np.zeros(4, np.uint8), timeout=30.0)
        assert r.param_version == 7
        assert c.reconnects >= 1
        c.close()
        srv2.close()


class _HealthStub:
    """Toggleable /healthz endpoint (the obs exporter stand-in)."""

    def __init__(self):
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: N802
                pass

            def do_GET(self):  # noqa: N802
                body = json.dumps({"status": "ok" if stub.ok else "bad"})
                code = 200 if stub.ok else 503
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body.encode())

        self.ok = True
        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self._httpd.daemon_threads = True
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self._httpd.server_address[1]}/healthz"

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()


class TestRouter:
    """Health-aware routing over in-process stub replicas: real sockets,
    real /healthz probes, no subprocesses (the subprocess e2e lives in
    tools/serving_net_smoke.py, verify gate 8)."""

    def _fleet(self, n=2):
        replicas = []
        for i in range(n):
            policy = StubPolicy(version=i + 1)
            srv = ServingNetServer(policy).start()
            health = _HealthStub()
            replicas.append((policy, srv, health))
        router = ServingRouter(port=0, probe_interval_s=30.0)  # manual probes
        for rid, (_, srv, health) in enumerate(replicas):
            router.set_endpoint(rid, "127.0.0.1", srv.port,
                                health_url=health.url)
        router.start()
        return router, replicas

    def _teardown(self, router, replicas):
        router.close()
        for _, srv, health in replicas:
            srv.close()
            health.close()

    def test_round_robin_spreads_connections(self):
        router, replicas = self._fleet(2)
        try:
            clients = [ServingClient("127.0.0.1", router.port, seed=i)
                       for i in range(4)]
            for c in clients:
                c.act(np.zeros(8, np.uint8), timeout=10.0)
            served = [srv.accepted for _, srv, _ in replicas]
            assert sum(served) == 4
            assert all(s > 0 for s in served), served
            for c in clients:
                c.close()
        finally:
            self._teardown(router, replicas)

    def test_unhealthy_replica_drains_and_reenters(self):
        router, replicas = self._fleet(2)
        try:
            # Replica 0 goes 503: the probe drains it from rotation.
            replicas[0][2].ok = False
            router.probe_once()
            assert router.stats()["healthy"] == 1
            before = replicas[0][1].accepted
            clients = [ServingClient("127.0.0.1", router.port, seed=i)
                       for i in range(4)]
            for c in clients:
                c.act(np.zeros(8, np.uint8), timeout=10.0)
            # ZERO new connections routed to the drained replica; every
            # request answered by the healthy one (its version on replies).
            assert replicas[0][1].accepted == before
            assert replicas[1][1].stats()["requests"] >= 4
            for c in clients:
                c.close()
            # Recovery: healthz 200 again -> back in rotation.
            replicas[0][2].ok = True
            router.probe_once()
            assert router.stats()["healthy"] == 2
            after = [ServingClient("127.0.0.1", router.port, seed=10 + i)
                     for i in range(4)]
            for c in after:
                c.act(np.zeros(8, np.uint8), timeout=10.0)
            assert replicas[0][1].accepted > before
            for c in after:
                c.close()
        finally:
            self._teardown(router, replicas)

    def test_dead_replica_failover_client_retries(self):
        """SIGKILL-shaped death mid-stream (the in-process twin: close
        the replica's listener and sockets): the client's next request
        rides a reconnect to the LIVE replica — zero drops."""
        router, replicas = self._fleet(2)
        try:
            c = ServingClient("127.0.0.1", router.port, seed=0)
            first = c.act(np.zeros(8, np.uint8), timeout=10.0)
            victim = first.param_version - 1      # rid == version - 1
            live = 1 - victim
            replicas[victim][1].close()           # dies mid-stream
            replicas[victim][2].ok = False
            router.probe_once()
            r = c.act(np.zeros(8, np.uint8), timeout=30.0)
            assert r.param_version == live + 1    # served by the live one
            assert c.reconnects >= 1
            c.close()
        finally:
            self._teardown(router, replicas)

    def test_no_healthy_replicas_fails_fast_then_recovers(self):
        router, replicas = self._fleet(1)
        try:
            replicas[0][2].ok = False
            router.probe_once()
            c = ServingClient("127.0.0.1", router.port, seed=0)
            with pytest.raises(TimeoutError):
                c.act(np.zeros(8, np.uint8), timeout=1.5)
            assert router.stats()["route_fails"] >= 1
            replicas[0][2].ok = True
            router.probe_once()
            assert c.act(np.zeros(8, np.uint8), timeout=10.0) is not None
            c.close()
        finally:
            self._teardown(router, replicas)

    def test_stats_schema_stable(self):
        router = ServingRouter(port=0)
        try:
            keys = set(router.stats())
            assert {"port", "replicas", "healthy", "active",
                    "routed_total", "route_fails", "splices_broken",
                    "probe_failures", "endpoints"} == keys
        finally:
            router.close()


class TestHubSpec:
    def test_parse_roundtrip(self):
        spec = parse_hub_spec("10.0.0.5:9100:12345:3:2")
        assert spec == {"host": "10.0.0.5", "port": 9100, "token": 12345,
                        "wid": 3, "attempt": 2}

    def test_parse_rejects_malformed(self):
        with pytest.raises(ValueError):
            parse_hub_spec("localhost:9100")


class TestSocketParamSource:
    def test_full_then_delta_over_hub(self):
        from ape_x_dqn_tpu.runtime.net import NetTransport
        from ape_x_dqn_tpu.serving.sources import SocketParamSource
        from ape_x_dqn_tpu.utils.serialization import tree_to_bytes

        template = {"w": np.zeros((64, 64), np.float32),
                    "b": np.zeros(64, np.float32)}
        hub = NetTransport(port=0)
        hub.make_channel(0, 0)
        try:
            params1 = {"w": np.ones((64, 64), np.float32),
                       "b": np.zeros(64, np.float32)}
            hub.set_params(tree_to_bytes(params1), 1)
            src = SocketParamSource(
                f"127.0.0.1:{hub.port}:{hub.token}:0:0", template
            )
            got = None
            deadline = time.monotonic() + 10.0
            while got is None and time.monotonic() < deadline:
                hub.pump()
                got = src.get(-1)
                time.sleep(0.01)
            assert got is not None, "no full sync over the hub"
            params, version = got
            assert version == 1
            np.testing.assert_array_equal(params["w"], params1["w"])
            # Delta publish: one small region dirty.
            params2 = {"w": params1["w"].copy(), "b": params1["b"].copy()}
            params2["b"][:] = 3.0
            push = hub.set_params(tree_to_bytes(params2), 2)
            assert push["delta"] == 1
            assert push["bytes"] < len(tree_to_bytes(params2)) / 4
            got = None
            deadline = time.monotonic() + 10.0
            while got is None and time.monotonic() < deadline:
                hub.pump()
                got = src.get(1)
                time.sleep(0.01)
            assert got is not None, "no delta update over the hub"
            params, version = got
            assert version == 2
            np.testing.assert_array_equal(params["b"], params2["b"])
            assert src.version == 2
            src.close()
        finally:
            hub.close()


class TestParamTail:
    def _tree(self, fill):
        return {"w": np.full((128, 32), fill, np.float32),
                "b": np.zeros(32, np.float32)}

    def test_full_then_delta_chain(self, tmp_path):
        w = ParamTailWriter(str(tmp_path), base_every=8)
        src = ParamTailSource(str(tmp_path), self._tree(0.0))
        w.publish(self._tree(1.0))
        params, v = src.get(-1)
        assert v == 1
        np.testing.assert_array_equal(params["w"],
                                      self._tree(1.0)["w"])
        # Small perturbations -> delta files.
        t = self._tree(1.0)
        for i in range(3):
            t["b"][:] = float(i + 1)
            w.publish(t)
        assert w.delta_writes == 3 and w.full_writes == 1
        params, v = src.get(1)
        assert v == 4
        np.testing.assert_array_equal(params["b"], t["b"])
        # Nothing new -> None.
        assert src.get(4) is None

    def test_base_every_forces_full(self, tmp_path):
        w = ParamTailWriter(str(tmp_path), base_every=2)
        t = self._tree(1.0)
        for i in range(4):
            t["b"][:] = float(i)
            w.publish(t)
        assert w.full_writes >= 2

    def test_corrupt_delta_walks_back(self, tmp_path):
        w = ParamTailWriter(str(tmp_path), base_every=16)
        t = self._tree(1.0)
        w.publish(t)
        t["b"][:] = 2.0
        w.publish(t)
        t["b"][:] = 3.0
        path3 = w.publish(t)
        # Bit-flip the newest delta: a FRESH reader must stop the chain
        # at the last good rung (version 2), never decode the bad one.
        with open(path3, "r+b") as f:
            f.seek(40)
            b = f.read(1)
            f.seek(40)
            f.write(bytes([b[0] ^ 0xFF]))
        src = ParamTailSource(str(tmp_path), self._tree(0.0))
        params, v = src.get(-1)
        assert v == 2
        assert src.corrupt_skips >= 1
        np.testing.assert_array_equal(
            params["b"], np.full(32, 2.0, np.float32)
        )

    def test_corrupt_full_uses_previous_generation(self, tmp_path):
        w = ParamTailWriter(str(tmp_path), base_every=2)
        t = self._tree(1.0)
        for i in range(4):          # fulls at v1, v3 (base_every=2)
            t["b"][:] = float(i + 1)
            w.publish(t)
        import os as _os

        newest_full = sorted(
            n for n in _os.listdir(tmp_path) if n.endswith("_full.apxc")
        )[-1]
        with open(tmp_path / newest_full, "r+b") as f:
            f.seek(30)
            f.write(b"\xde\xad")
        src = ParamTailSource(str(tmp_path), self._tree(0.0))
        got = src.get(-1)
        assert got is not None
        _, v = got
        assert v < 4 and src.corrupt_skips >= 1

    def test_pruning_bounds_directory(self, tmp_path):
        w = ParamTailWriter(str(tmp_path), base_every=4)
        t = self._tree(1.0)
        for i in range(20):
            t["b"][:] = float(i)
            w.publish(t)
        names = list(tmp_path.iterdir())
        # Current chain + previous full's chain at most: 2 * base_every.
        assert len(names) <= 2 * 4 + 1

"""Fleet discovery plane: the membership registry's adversarial wire
matrix + the elastic-replay routing contracts (fleet/registry.py,
replay/service.py adopt_membership, obs/fleet.py membership adoption,
autopilot's replay fleet).

The announce channel inherits the repo's decode discipline — a torn,
bitflipped, wrong-token, or stale-incarnation frame is COUNTED and never
mutates membership — and adds the lease semantics on top: joins are
versioned, leaves are immediate, silence past ``fleet.ttl_s`` is swept
with a typed ``member_lost``.  The digest-gated endpoints-file re-read
(the mtime-granularity regression) is pinned here for BOTH readers.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from ape_x_dqn_tpu.fleet.registry import (
    FleetAnnouncer,
    FleetClient,
    FleetRegistry,
    member_doc,
    member_id_for,
)
from ape_x_dqn_tpu.runtime.net import (
    F_FANN,
    FLEET_ACK,
    FLEET_ACK_MAGIC,
    FLEET_HELLO,
    FLEET_HELLO_VERSION,
    FLEET_MAGIC,
    frame_bytes,
)

TOKEN = 4242


def _wait(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture
def registry():
    events = []
    lock = threading.Lock()

    def on_event(name, **fields):
        with lock:
            events.append((name, fields))

    reg = FleetRegistry(token=TOKEN, ttl_s=0.5,
                        on_event=on_event).serve()
    reg.test_events = events
    yield reg
    reg.close()


def _hello_bytes(token=TOKEN, version=FLEET_HELLO_VERSION,
                 magic=FLEET_MAGIC, member_id=7, incarnation=1):
    return FLEET_HELLO.pack(magic, version, member_id, incarnation, token)


def _raw_conn(reg, **hello_kw):
    """Dial + hello; returns the socket past the ack, or None when the
    registry rejected by close."""
    s = socket.create_connection(("127.0.0.1", reg.port), timeout=5.0)
    s.settimeout(5.0)
    s.sendall(_hello_bytes(**hello_kw))
    ack = b""
    while len(ack) < FLEET_ACK.size:
        try:
            got = s.recv(FLEET_ACK.size - len(ack))
        except (ConnectionError, socket.timeout):
            got = b""
        if not got:
            s.close()
            return None
        ack += got
    assert FLEET_ACK.unpack(ack)[0] == FLEET_ACK_MAGIC
    return s


def _announce_bytes(op="join", member=None, seq=1):
    body = json.dumps({"op": op, "member": member}).encode()
    return frame_bytes(F_FANN, seq, (body,))


class TestAnnounceWireAdversarial:
    """Garbage on the announce plane is counted and NEVER a membership
    mutation — the torn-ring contract, on the fourth protocol."""

    def test_wrong_token_hello_rejected_by_close(self, registry):
        assert _raw_conn(registry, token=TOKEN + 1) is None
        _wait(lambda: registry.stats()["bad_hellos"] >= 1,
              msg="bad_hellos")
        assert registry.stats()["members"] == 0

    def test_wrong_magic_and_version_rejected(self, registry):
        assert _raw_conn(registry, magic=b"NOPE") is None
        assert _raw_conn(registry, version=FLEET_HELLO_VERSION + 9) is None
        _wait(lambda: registry.stats()["bad_hellos"] >= 2,
              msg="bad_hellos")
        assert registry.stats()["accepted"] == 0

    def test_torn_frame_counted_never_applied(self, registry):
        s = _raw_conn(registry)
        doc = member_doc("replay/shard9", "replay_shard", port=1, capacity=4)
        frame = _announce_bytes(member=doc)
        s.sendall(frame[: len(frame) - 3])   # truncated mid-frame
        s.close()
        _wait(lambda: registry.stats()["torn_frames"] >= 1,
              msg="torn_frames")
        assert registry.stats()["members"] == 0
        assert registry.stats()["joins"] == 0

    def test_bitflipped_frame_torn(self, registry):
        s = _raw_conn(registry)
        frame = bytearray(_announce_bytes(
            member=member_doc("x", "observer")))
        frame[-1] ^= 0x40                    # payload bit under the crc
        s.sendall(bytes(frame))
        _wait(lambda: registry.stats()["torn_frames"] >= 1,
              msg="torn_frames")
        assert registry.stats()["members"] == 0
        s.close()

    def test_unknown_kind_counted_and_retired(self, registry):
        s = _raw_conn(registry)
        s.sendall(frame_bytes(F_FANN + 1, 1, (b"{}",)))
        _wait(lambda: registry.stats()["unexpected_kinds"] >= 1,
              msg="unexpected_kinds")
        assert registry.stats()["members"] == 0
        s.close()

    def test_well_framed_garbage_announce_counted(self, registry):
        for body in (b"not json", b'{"op": "invade"}',
                     b'{"op": "join"}'):        # join without a member
            s = _raw_conn(registry)
            s.sendall(frame_bytes(F_FANN, 1, (body,)))
            s.close()
        _wait(lambda: registry.stats()["bad_announces"] >= 3,
              msg="bad_announces")
        assert registry.stats()["members"] == 0

    def test_stale_incarnation_announce_refused(self, registry):
        cli = FleetClient("127.0.0.1", registry.port, token=TOKEN)
        fresh = member_doc("replay/shard0", "replay_shard",
                           port=9001, incarnation=3)
        cli.announce("join", fresh)
        stale = member_doc("replay/shard0", "replay_shard",
                           port=6666, incarnation=2)
        snap = cli.announce("heartbeat", stale)
        cli.close()
        assert registry.stats()["stale_rejects"] == 1
        member = snap["members"]["replay/shard0"]
        assert member["incarnation"] == 3
        assert member["port"] == 9001       # the stale doc never landed


class TestMembershipLifecycle:
    def test_join_heartbeat_leave_versions(self, registry):
        cli = FleetClient("127.0.0.1", registry.port, token=TOKEN,
                          member_id=member_id_for("w"))
        doc = member_doc("worker/host0", "worker_host",
                         varz_url="http://x/varz")
        snap = cli.announce("join", doc)
        v_join = snap["version"]
        assert snap["members"]["worker/host0"]["kind"] == "worker_host"
        # An unchanged heartbeat refreshes the lease without a version
        # bump; watchers stay cheap.
        snap = cli.announce("heartbeat", doc)
        assert snap["version"] == v_join
        snap = cli.announce("leave", doc)
        assert "worker/host0" not in snap["members"]
        assert snap["version"] > v_join
        cli.close()
        names = [n for n, _f in registry.test_events]
        assert "member_join" in names and "member_lost" in names
        lost = [f for n, f in registry.test_events if n == "member_lost"]
        assert lost[0]["reason"] == "leave"

    def test_ttl_sweep_expires_silent_member(self, registry):
        cli = FleetClient("127.0.0.1", registry.port, token=TOKEN)
        cli.announce("join", member_doc("serving/replica0",
                                        "serving_replica", port=8080))
        cli.close()
        _wait(lambda: registry.stats()["members"] == 0, timeout=5.0,
              msg="ttl expiry")
        assert registry.stats()["expired"] == 1
        lost = [f for n, f in registry.test_events if n == "member_lost"]
        assert lost and lost[-1]["reason"] == "ttl"

    def test_sweep_is_deterministic_under_explicit_now(self):
        reg = FleetRegistry(token=1, ttl_s=5.0)     # never served: no clock
        reg._apply("join", member_doc("a", "observer"))
        assert reg.sweep(time.monotonic() + 4.0) == []
        assert reg.sweep(time.monotonic() + 6.0) == ["a"]
        assert reg.stats()["members"] == 0

    def test_sync_is_a_pure_read(self, registry):
        cli = FleetClient("127.0.0.1", registry.port, token=TOKEN)
        snap = cli.sync()
        assert snap["token"] == TOKEN and snap["members"] == {}
        assert registry.stats()["joins"] == 0
        cli.close()

    def test_announcer_lifecycle_and_watch(self, registry):
        seen = []
        ann = FleetAnnouncer("127.0.0.1", registry.port, token=TOKEN,
                             member_id=member_id_for("fleet"),
                             heartbeat_s=0.05,
                             on_membership=seen.append).start()
        ann.set_member(member_doc("replay/shard0", "replay_shard",
                                  port=7001, capacity=64, incarnation=1))
        ann.poke()
        _wait(lambda: registry.members("replay_shard"), msg="join")
        ann.remove_member("replay/shard0")
        ann.poke()
        _wait(lambda: not registry.members("replay_shard"), msg="leave")
        ann.close(leave=True)
        assert seen and any("replay/shard0" in s.get("members", {})
                            for s in seen)


class TestEndpointsDigestRegression:
    """Two atomic rewrites inside one mtime granule must BOTH land: the
    re-read gates on content digest, never mtime equality.  Pinned for
    both readers (the replay client's probe refresh and the aggregator's
    endpoints-file watch)."""

    def _write(self, path, port, mtime=None):
        doc = {"token": 5, "codec": "off", "total_capacity": 64,
               "shards": [{"id": 0, "host": "127.0.0.1", "port": port,
                           "base": 0, "capacity": 64, "incarnation": 2}]}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        if mtime is not None:
            os.utime(path, (mtime, mtime))

    def test_client_refresh_survives_same_mtime_rewrite(self, tmp_path):
        from ape_x_dqn_tpu.replay.service import ShardedReplayClient

        path = str(tmp_path / "endpoints.json")
        self._write(path, port=1111, mtime=1000.0)
        client = ShardedReplayClient(
            [{"id": 0, "host": "127.0.0.1", "port": 1111, "base": 0,
              "capacity": 64, "incarnation": 2}],
            token=5, endpoints_path=path, probe_interval_s=60.0,
        )
        try:
            client._refresh_endpoints()
            assert client._clients[0].port == 1111
            # The respawn-storm rewrite: new port, SAME mtime.
            self._write(path, port=2222, mtime=1000.0)
            client._refresh_endpoints()
            assert client._clients[0].port == 2222
        finally:
            client.close()

    def test_aggregator_refresh_survives_same_mtime_rewrite(self, tmp_path):
        from ape_x_dqn_tpu.obs.fleet import FleetAggregator

        path = str(tmp_path / "endpoints.json")
        self._write(path, port=1111, mtime=1000.0)
        agg = FleetAggregator(scrape_interval_s=60.0)
        agg.watch_replay_endpoints(path)
        assert agg._eps["replay_shard0"].shard_spec["port"] == 1111
        self._write(path, port=2222, mtime=1000.0)
        agg._refresh_replay_files()
        assert agg._eps["replay_shard0"].shard_spec["port"] == 2222


class TestClientMembershipAdoption:
    """adopt_membership drives the ELASTIC routing set: admit grown
    shards, stop routing adds at draining ones, retire removed ones
    (parked write-backs dropped and counted, never raised)."""

    def _spec(self, sid, port, draining=False, incarnation=1):
        return member_doc(f"replay/shard{sid}", "replay_shard",
                          host="127.0.0.1", port=port,
                          incarnation=incarnation, base=sid * 64,
                          capacity=64, draining=draining)

    def _snapshot(self, *docs, version=1):
        return {"token": 5, "version": version, "incarnation": 1,
                "members": {d["name"]: d for d in docs}}

    def _client(self):
        from ape_x_dqn_tpu.replay.service import ShardedReplayClient

        return ShardedReplayClient(
            [{"id": 0, "host": "127.0.0.1", "port": 1111, "base": 0,
              "capacity": 64, "incarnation": 1},
             {"id": 1, "host": "127.0.0.1", "port": 1112, "base": 64,
              "capacity": 64, "incarnation": 1}],
            token=5, probe_interval_s=60.0,
        )

    def test_grow_admits_new_shard(self):
        client = self._client()
        try:
            client.adopt_membership(self._snapshot(
                self._spec(0, 1111), self._spec(1, 1112),
                self._spec(2, 1113), version=3))
            assert client.num_shards == 3
            assert client.capacity == 3 * 64
            assert sorted(client._clients) == [0, 1, 2]
            assert client.membership_version == 3
            assert client._addable() == [0, 1, 2]
        finally:
            client.close()

    def test_draining_shard_leaves_the_add_path(self):
        client = self._client()
        try:
            client.adopt_membership(self._snapshot(
                self._spec(0, 1111), self._spec(1, 1112, draining=True)))
            assert client.num_shards == 2       # still sampled/updated
            assert client._addable() == [0]
            assert client.stats()["shards_draining"] == [1]
        finally:
            client.close()

    def test_retired_shard_drops_parked_writebacks_counted(self):
        client = self._client()
        try:
            with client._state:
                client._pending[1] = {70: 0.5, 71: 0.25}
            client.adopt_membership(self._snapshot(self._spec(0, 1111)))
            assert client.num_shards == 1
            assert 1 not in client._clients
            assert client.updates_dropped == 2
            # The vacated slot range's write-backs never raise.
            client.update_priorities(np.array([70], np.int64),
                                     np.array([0.9], np.float64))
            assert client.updates_dropped == 3
        finally:
            client.close()

    def test_empty_snapshot_never_strands_the_client(self):
        client = self._client()
        try:
            client.adopt_membership({"version": 9, "members": {}})
            assert client.num_shards == 2       # routing set intact
        finally:
            client.close()


class TestAggregatorMembershipAdoption:
    def _snapshot(self, members, version=1):
        return {"token": 5, "version": version, "incarnation": 1,
                "members": {d["name"]: d for d in members}}

    def test_members_become_endpoints_and_departures_drop(self):
        from ape_x_dqn_tpu.obs.fleet import FleetAggregator

        agg = FleetAggregator(scrape_interval_s=60.0)
        shard = member_doc("replay/shard0", "replay_shard",
                           host="127.0.0.1", port=7001, base=0,
                           capacity=64, incarnation=1)
        replica = member_doc("serving/replica0", "serving_replica",
                             port=8001, varz_url="http://127.0.0.1:1/varz")
        agg.adopt_membership(self._snapshot([shard, replica], version=2))
        assert agg._eps["replay_shard0"].shard_spec["port"] == 7001
        assert agg._eps["serving/replica0"].kind == "replica"
        mem = agg._membership
        assert mem["version"] == 2 and mem["members"] == 2
        assert mem["by_kind"] == {"replay_shard": 1, "serving_replica": 1}
        # The replica leaves (retired / TTL): its endpoint must drop so
        # a departed member never reads as a liveness breach.
        agg.adopt_membership(self._snapshot([shard], version=3))
        assert "serving/replica0" not in agg._eps
        assert "replay_shard0" in agg._eps

    def test_draining_surfaced_in_membership_rollup(self):
        from ape_x_dqn_tpu.obs.fleet import FleetAggregator

        agg = FleetAggregator(scrape_interval_s=60.0)
        shard = member_doc("replay/shard1", "replay_shard",
                           host="127.0.0.1", port=7002, base=64,
                           capacity=64, draining=True)
        agg.adopt_membership(self._snapshot([shard]))
        assert agg._membership["draining"] == ["replay/shard1"]

    def test_bind_registry_adopts_in_process(self):
        from ape_x_dqn_tpu.obs.fleet import FleetAggregator

        reg = FleetRegistry(token=11, ttl_s=60.0)
        reg._apply("join", member_doc("replay/shard0", "replay_shard",
                                      host="127.0.0.1", port=7003,
                                      capacity=64))
        agg = FleetAggregator(scrape_interval_s=60.0)
        agg.bind_registry(reg)
        assert agg._eps["replay_shard0"].shard_spec["token"] == 11
        rollup = agg.scrape_once(now=time.monotonic())
        assert rollup["membership"]["members"] == 1


class _FakeReplayFleet:
    """ReplayServiceFleet's actuator surface, decoupled from processes."""

    def __init__(self, shards=2):
        self.num_shards = shards
        self.grown = 0
        self.retired = 0
        self._resharding = False

    def resharding(self):
        return self._resharding

    def grow(self, timeout=60.0):
        sid = self.num_shards
        self.num_shards += 1
        self.grown += 1
        return sid

    def retire(self, drain_grace_s=0.5, timeout=60.0):
        if self.num_shards <= 1:
            return None
        self.num_shards -= 1
        self.retired += 1
        return self.num_shards


class TestReplayFleetControl:
    def _cfg(self, **kw):
        from ape_x_dqn_tpu.config import AutopilotConfig

        kw.setdefault("enabled", True)
        kw.setdefault("cooldown_up_s", 0.0)
        kw.setdefault("cooldown_down_s", 0.0)
        kw.setdefault("hold_opposite_s", 0.0)
        kw.setdefault("replay_min_shards", 1)
        kw.setdefault("replay_max_shards", 3)
        return AutopilotConfig(**kw)

    def _controller(self, cfg, rollup=None):
        from ape_x_dqn_tpu.autopilot import (
            AutopilotController,
            ReplayFleetActuator,
        )

        fleet = _FakeReplayFleet()
        ctl = AutopilotController(cfg, rollup_fn=lambda: rollup or {})
        ctl.attach_replay(ReplayFleetActuator(fleet))
        return ctl, fleet

    def test_add_qps_breach_grows_the_fleet(self):
        ctl, fleet = self._controller(self._cfg())
        ctl.on_slo_event("slo_breach", rule="replay_add_qps", value=900.0)
        acted = ctl.step(now=100.0)
        assert [a["action"] for a in acted] == ["scale_up"]
        assert acted[0]["fleet"] == "replay"
        assert fleet.num_shards == 3

    def test_grow_respects_max_and_busy(self):
        ctl, fleet = self._controller(self._cfg(replay_max_shards=2))
        ctl.on_slo_event("slo_breach", rule="replay_add_qps", value=900.0)
        assert ctl.step(now=100.0) == []
        assert ctl.suppressed.get("replay:up:at_max") == 1
        fleet.num_shards = 1
        fleet._resharding = True            # mid-handoff: hands off
        assert ctl.step(now=101.0) == []
        assert ctl.suppressed.get("replay:up:busy") == 1
        assert fleet.grown == 0

    def test_idle_rule_retires_through_own_burn_window(self):
        cfg = self._cfg(replay_idle_add_qps_per_shard=5.0,
                        idle_window_s=10.0)
        rollup = {"replay": {"shards_alive": 2, "add_qps": 0.5}}
        ctl, fleet = self._controller(cfg, rollup=rollup)
        acted = []
        for k in range(8):                  # burn window must fill first
            acted += ctl.step(now=100.0 + k)
        assert [a["action"] for a in acted] == ["scale_down"]
        assert acted[0]["rule"] == "replay_idle"
        assert fleet.retired == 1 and fleet.num_shards == 1
        # At the floor the idle rule is suppressed, not actuated.
        for k in range(4):
            acted2 = ctl.step(now=110.0 + k)
            assert acted2 == []
        assert ctl.suppressed.get("replay:down:at_min", 0) >= 1

    def test_breach_vetoes_idle_scale_down(self):
        cfg = self._cfg(replay_idle_add_qps_per_shard=5.0,
                        idle_window_s=10.0)
        rollup = {"replay": {"shards_alive": 2, "add_qps": 0.5}}
        ctl, fleet = self._controller(cfg, rollup=rollup)
        ctl.on_slo_event("slo_breach", rule="replay_add_qps", value=900.0)
        for k in range(8):
            for a in ctl.step(now=100.0 + k):
                assert a["action"] != "scale_down"
        assert fleet.retired == 0


class TestSpillBackedShardBitExact:
    """replay.service_hot_frame_budget_bytes: a shard hosting its replay
    on the tiered (spill-backed) store answers sample/digest bit-exactly
    against an untiered twin fed the identical stream."""

    def test_tiered_shard_digest_matches_dense_twin(self, tmp_path):
        from ape_x_dqn_tpu.replay.buffer import PrioritizedReplay
        from ape_x_dqn_tpu.replay.service import (
            ReplayShardServer,
            ShardClient,
            encode_body,
        )
        from ape_x_dqn_tpu.runtime.net import CODEC_ZLIB

        obs = (6,)
        dense = PrioritizedReplay(64, obs, priority_exponent=0.6)
        tiered = PrioritizedReplay(
            64, obs, priority_exponent=0.6,
            hot_frame_budget_bytes=8 * int(np.prod(obs)),   # forces spill
            spill_dir=str(tmp_path / "spill"),
        )
        servers = [ReplayShardServer(rep, 0, incarnation=1, token=9,
                                     codec="zlib").start()
                   for rep in (dense, tiered)]
        try:
            r = np.random.default_rng(3)
            for chunk in range(6):
                n = 16
                o = r.integers(0, 255, (n, *obs), dtype=np.uint8)
                body = encode_body({
                    "prio": (np.abs(r.normal(size=n)) + 0.1)
                    .astype(np.float64),
                    "obs": o,
                    "action": r.integers(0, 2, n).astype(np.int32),
                    "reward": r.normal(size=n).astype(np.float32),
                    "discount": np.full(n, 0.99, np.float32),
                    "next_obs": np.roll(o, -1, axis=0),
                }, codec=CODEC_ZLIB)
                for srv in servers:
                    cli = ShardClient(0, "127.0.0.1", srv.port,
                                      token=9, client_id=100 + chunk,
                                      incarnation=1)
                    from ape_x_dqn_tpu.replay.service import OP_ADD
                    cli.request(OP_ADD, body, timeout=10.0)
                    cli.close()
            # The shard's pump thread must actually spill (the budget is
            # a fraction of the stored frames) before the proof runs, so
            # the crc scan REALLY faults spans back from the cold file.
            # (Waited for, not asserted once: an early sweep satisfies
            # spill_spans while the last adds have re-heated the ring and
            # their own sweep is still a pump iteration away.)
            _wait(lambda: servers[1].spill_spans > 0
                  and tiered.frames_nbytes() < dense.frames_nbytes(),
                  msg="spill sweep")
            digests = []
            for srv in servers:
                cli = ShardClient(0, "127.0.0.1", srv.port, token=9,
                                  client_id=55, incarnation=1)
                digests.append(cli.digest(with_crc=True, timeout=10.0))
                cli.close()
            dense_d, tiered_d = digests
            for key in ("count", "cursor", "size", "crc"):
                assert int(dense_d[key]) == int(tiered_d[key]), key
            assert abs(dense_d["total_mass"]
                       - tiered_d["total_mass"]) <= 1e-9
            assert servers[1].stats()["spill_bytes"] > 0
        finally:
            for srv in servers:
                srv.close()

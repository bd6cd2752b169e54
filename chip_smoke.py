"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

Drives the trainer's main path once through ``ape_x_dqn_tpu.train.main``, the
entry point a user calls, at the full width of the one model the repo
supports — dueling conv 64/64/64 with 512-unit streams, 84x84x1 uint8 frames
from ``fake-atari`` (the full wrapper stack), batch 32, a 100k-slot HBM ring,
K=2048 fused steps per dispatch, sample-ahead, rmsprop with bf16 second
moment and bf16 target.  Depth is cut (a few fused calls per leg) and the
weights are random, made from the config's seed.  Legs, all in THIS process,
which owns the chip:

  thread    64 thread actors sharing the learner's device
  process   2 worker processes x 8 actors; the children must stay on the CPU
  serving   actors select actions through the in-process PolicyServer
  dp4       learner.data_parallel=4 + replay.dedup (only with >= 4 devices)
  lfm2moe   configs/config6_lfm2moe_q_ep8.json (the 455 M parameter expert
            torso at its published widths, batch 512, K=2) with 16 thread
            actors on the learner's chip, 24 learner steps: action
            selection serves the network too.  Only with --lfm2moe: the leg
            compiles for minutes and is the on-chip run ISSUE 28 asks of
            its builder, not part of the quick proof
  laguna    configs/config7_laguna_q_ep32.json (the 737 M parameter torso of
            window and full attention layers over a 32-frame history, 1,568
            tokens) with 4 thread actors on the learner's chip, 12 learner
            steps at batch 2 on a 1,024-slot ring: beside the learner's 5.9
            GB of state the actors' copy of the parameters (2.95 GB) leaves
            the cell's batch of 8 no room.  Only with --laguna
  granite   configs/config8_granite4h_q_l10.json (the 749 M parameter torso of
            nine Mamba-2 layers to one attention layer over the same 32-frame
            history: the chunked scan and its backward pass under the
            trainer's loop) with 4 thread actors on the learner's chip, 12
            learner steps at batch 2 on a 1,024-slot ring, as the laguna
            leg and for its reason.  Only with --granite
  solar     configs/config9_solar2_q_ep40.json (the 709 M parameter torso of
            three gated delta-rule layers to one gated softmax layer, 8 of
            320 experts and 16 of 64 heads held, over the same history: the
            chunked delta-rule scan and its backward pass under the
            trainer's loop) with 4 thread actors, 12 learner steps at batch 2
            on a 1,024-slot ring, as the laguna leg.  Only with --solar
  ling      configs/config10_ling3_q_l7.json (the torso of five bounded-gate
            delta-rule layers to one latent-attention layer under a router
            that keeps groups, a leading dense layer, 8 of 32 heads held,
            over the same history) with 4 thread actors, 12 learner steps at
            batch 2 on a 1,024-slot ring, as the laguna leg.  Only with --ling,
            and after ling_kernels
  ling_kernels  what that cell's comparison does not see on the chip (PERF.md,
            section 6): the blocked attention kernels with their shared key
            operand, forward and all five gradients, against plain attention
            in float32 at the cell's shapes ([8, 8, 1568, 128 + 64], bfloat16
            in), and the router's choice by groups at 12,544 x 512 against a
            router by sorting on the host, with the tolerances proven on the
            spot: plain attention without the shared key's scores, and the
            top 8 of all 512, must fail them; the router's choice by
            selection against the sort, and the walk's combine by column
            blocks against one scatter-add of whole rows at a tile of each of
            the four expert cells, with the microseconds of each.  With
            --ling, or alone with --ling-kernels (no network is built: about
            two minutes)
  kanana    configs/config12_kanana2_q_ep8.json (the 624 M parameter torso of
            latent attention in every layer, all 32 heads held, a leading
            dense layer and five expert layers in one scanned body, 16 of 128
            experts held, over the same history) with 4 thread actors, 12
            learner steps at batch 2 on a 1,024-slot ring, as the laguna leg.
            Only with --kanana, and after kanana_kernels
  kanana_kernels  what that cell's comparison leaves to the chip (PERF.md,
            section 6, PR 56): the blocked attention kernels with their
            shared key operand at all 32 heads ([2, 32, 1568, 128 + 64],
            bfloat16 in: the shared key's gradient summed over 32 heads),
            forward and all five gradients against plain attention in
            float32; the ungated latent mixer whole at the published widths
            (``ling_hybrid.LatentAttention`` under this family's spec, two
            rows) against the mixer written out in float32, where one that
            lost the latent's norm must fail; and the router's 6 of 128 with
            its gates (normalised, times 2.448) against a sort on the host,
            where gates without the factor must fail.  With --kanana, or
            alone with --kanana-kernels (no network is built: about two
            minutes)
  nemotron  configs/config13_nemotron3s_q_ep32.json (the 699 M parameter torso
            of one-sublayer layers: Mamba-2 in groups, LatentMoE of relu2
            experts in a latent of 1024 at 22 of 512, one NoPE GQA layer; a
            TP4 x EP32 chip's heads, columns and 16 experts, over the same
            history) with 4 thread actors, 12 learner steps at batch 2 on a
            1,024-slot ring, as the laguna leg.  Only with --nemotron, and
            after nemotron_kernels
  nemotron_kernels  what that cell's comparison leaves to the chip (PERF.md,
            section 6, PR 59): the Mamba-2 mixer whole at the held widths
            (``granite_hybrid.Mamba2`` told 2 groups of 16 heads of 64, chunk
            128, two rows of 1,568 tokens, bfloat16) against the mixer written
            out in float32 with the recurrence a token at a time, where every
            head on group 0's B and C, and the norm over all 2,048 channels,
            must each fail; the LatentMoE layer whole (``ExpertShare`` under
            this family's spec: 22 of 512, 16 held, the latent of 1024)
            against the layer written out in float32 over masks, where
            ``silu`` experts, and a router that reads the latent, must each
            fail; and the router's 22 of 512 with its gates (normalised, times
            5) against a sort on the host, where gates without the factor must
            fail.  With --nemotron, or alone with --nemotron-kernels (no
            network is built: about three minutes)
  olmo_kernels  the delta walk (``ops/chunked_delta.py``) against the
            recurrence stepped a token at a time in float32, in both forms:
            a decay a head and token at ``olmoh_q_l4``'s head sizes (30
            heads, keys of 96, values of 192) and a decay a key channel at
            ``solar2_q_ep40``'s (16 heads of 128); 1,568 tokens, chunk 64,
            bfloat16 in, forward and the five gradients, with the
            microseconds of a forward and of a forward and backward, and the
            ``scan_path`` spans; the same walk with ``beta`` held to 1 must
            fail the limit.  Only with --olmo-kernels (no network is built:
            about three minutes)
  first_conv  the bootstrap's first convolution apart, at the three conv
            cells' shapes: the online and the target net's convolutions of N
            outputs on the same bytes against one of 2N with the two filter
            banks side by side (``dueling.first_conv_of_two``), equal bits,
            and the microseconds of both, alone and with each net's second
            convolution behind its half (PERF.md section 6, PR 49: the cell
            gains more than the part).  Only with --first-conv (no network
            is trained: a minute); it runs in no cell
  fetch     a side of the dedup ring's gather stage apart, at 512 and 128
            rows of 84x84x4: ``dedup_fetch`` on a ring whose rows are whole
            tiles (one kernel a side, ``ops/pallas/row_fetch.py``) beside the
            compiler's gather, copy and unpack on the same words, each with
            a first convolution behind it, equal bits, the microseconds of
            both and the ``gather_path`` spans of the launch log (PERF.md
            section 6, PR 50).  Only with --fetch (a minute); it runs in no
            cell

Sets no platform itself.  Exits non-zero, with one line saying why and no
result, before compiling anything if jax's default backend is not a TPU, and
in a directory that holds nothing else of the repo.  Fails if any leg fails
or either native replay core does not build.  On success the last line of
stdout is ``{"ok": true, "device": {...}}`` with the device as jax reports it.

Module scope stays import-light: the process leg's spawned workers re-import
this file as ``__mp_main__`` and must not meet jax here.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time
import traceback

CAPACITY = 100_000
OBS_SHAPE = (84, 84, 1)
K = 2048
MAIN_PATH = [
    "--set", "network=conv",
    "--set", "env.name=fake-atari",
    "--set", "learner.device_replay=true",
    "--set", "learner.sample_ahead=true",
    "--set", "learner.replay_sample_size=32",
    "--set", f"replay.capacity={CAPACITY}",
    "--set", f"learner.steps_per_call={K}",
    "--set", "learner.optimizer=rmsprop",
    "--set", "learner.second_moment_dtype=bfloat16",
    "--set", "learner.target_dtype=bfloat16",
    # Poll for params every fleet step: the legs are seconds long.
    "--set", "actor.sync_every=1",
    "--log-every", str(K),
]


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def check_on_tpu(name: str, tree) -> int:
    """Every array leaf of ``tree`` lives on TPU devices only."""
    import jax

    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if hasattr(x, "devices")]
    assert leaves, f"{name}: no device arrays to check"
    for leaf in leaves:
        plats = {d.platform for d in leaf.devices()}
        assert plats == {"tpu"}, f"{name}: leaf {leaf.shape} on {plats}"
    return len(leaves)


def check_run(leg: str, pipe, final: dict, steps: int) -> None:
    """What every leg asserts: step target, finite loss, residency."""
    import math

    assert final["step"] >= steps, f"{leg}: step {final['step']} < {steps}"
    loss = final["learner/loss"]
    assert math.isfinite(loss), f"{leg}: loss {loss}"
    assert final["actor_steps"] > 0, f"{leg}: no actor steps"
    n_state = check_on_tpu(f"{leg} train state", pipe.fused.state)
    n_ring = check_on_tpu(f"{leg} ring", pipe.fused._replay)
    say(f"{leg}: step={final['step']} loss={loss:.5f} "
        f"actor_steps={final['actor_steps']} "
        f"param_version={final['param_version']} "
        f"state_leaves_on_tpu={n_state} ring_leaves_on_tpu={n_ring}")


def check_launch(leg: str, t0: float) -> None:
    """The leg's launch as the program accounts for it (profiling.launch):
    from ``t0``, the leg's start (what this script did before it, the native
    build and the ring's footprint, is not the trainer's), to the first fused
    call the loop waited for.  The nine parts add up to the interval, and
    what no span covers stays under a tenth."""
    from ape_x_dqn_tpu.utils import profiling

    s = profiling.launch.summary(t0=t0, top=5)
    assert s["done"], f"{leg}: the loop never ended its launch"
    parts = {p: s[p] for p in profiling.LAUNCH_PARTS}
    say(f"{leg}: launch {s['seconds']:.3f} s to step {s['step']}: "
        + " ".join(f"{p}={v:.3f}" for p, v in parts.items())
        + f"; cache hits {s['cache_hits']} misses {s['cache_misses']}; "
        f"slowest programs {json.dumps(s['programs'])}")
    for note in s["notes"]:
        say(f"{leg}: launch: {note}")
    assert abs(sum(parts.values()) - s["seconds"]) < 1e-6, (
        f"{leg}: the launch's parts add up to {sum(parts.values())}, "
        f"the interval is {s['seconds']}")
    assert parts["unattributed_s"] <= 0.1 * s["seconds"], (
        f"{leg}: {parts['unattributed_s']:.3f} s of a {s['seconds']:.3f} s "
        "launch lie under no span")


def run_leg(leg: str, argv: list, steps: int, inspect) -> None:
    from ape_x_dqn_tpu import train

    def _inspect(pipe, final):
        check_run(leg, pipe, final, steps)
        check_launch(leg, t0)
        inspect(pipe, final)

    t0 = time.perf_counter()
    rc = train.main(MAIN_PATH + argv + ["--steps", str(steps)],
                    inspect=_inspect)
    assert rc == 0, f"{leg}: train.main returned {rc}"


def ring_footprint() -> None:
    """bytes_in_use taken by the ring's own allocation beside its logical
    size: the ring is uint8[C,84,84,1] and a trailing dimension of 1 is
    where TPU tiling can pad."""
    import jax

    from ape_x_dqn_tpu.replay.device import init_device_replay

    dev = jax.devices()[0]
    before = dev.memory_stats()["bytes_in_use"]
    ring = jax.block_until_ready(init_device_replay(CAPACITY, OBS_SHAPE))
    used = dev.memory_stats()["bytes_in_use"] - before
    logical = sum(x.nbytes for x in jax.tree_util.tree_leaves(ring))
    frames = 2 * CAPACITY * 84 * 84
    say(f"ring: bytes_in_use={used} logical={logical} "
        f"(frames 2x{CAPACITY}x7056={frames}) ratio={used / logical:.3f}")
    del ring


def time_fused_forcing(pipe) -> None:
    """One fused call timed twice on the finished run's own learner: forced
    by jax.block_until_ready, and by a host read of the loss."""
    import jax
    import numpy as np

    fused = pipe.fused
    float(np.asarray(fused.train(0.4).loss)[-1])  # settle the queue
    t0 = time.perf_counter()
    m = fused.train(0.4)
    t_enqueue = time.perf_counter() - t0
    jax.block_until_ready(m.loss)
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    m = fused.train(0.4)
    float(np.asarray(m.loss)[-1])
    t_read = time.perf_counter() - t0
    say(f"one fused call (K={K}): enqueue returned after "
        f"{t_enqueue * 1e3:.1f} ms; forced by block_until_ready "
        f"{t_block * 1e3:.1f} ms; forced by host read of the loss "
        f"{t_read * 1e3:.1f} ms")


def leg_thread() -> None:
    steps = 8 * K  # >= 3 fused calls; long enough for actors to see a publish

    def inspect(pipe, final):
        import jax

        seen = pipe.worker.param_version
        assert final["param_version"] >= 1, "thread: nothing was published"
        assert seen >= 1, f"thread: actors never saw a publish (v{seen})"
        say(f"thread: actors adopted param_version {seen} of "
            f"{final['param_version']} published")
        time_fused_forcing(pipe)
        used = jax.devices()[0].memory_stats()
        say(f"thread: device bytes_in_use at end of leg "
            f"{used['bytes_in_use']} peak {used.get('peak_bytes_in_use')}")

    run_leg("thread", [
        "--set", "actor.num_actors=64",
        "--set", "learner.min_replay_mem_size=4096",
    ], steps, inspect)


def leg_process() -> None:
    def inspect(pipe, final):
        pool = pipe.worker.pool
        assert not pool.worker_errors, f"process: {pool.worker_errors}"
        assert final["actor_restarts"] == 0, final["actor_restarts"]
        assert final["supervisor"]["respawns"] == 0, final["supervisor"]
        workers = final["workers"]
        assert len(workers) == 2, workers
        for wid, w in workers.items():
            assert w["env_steps"] > 0, f"process: worker {wid} idle: {w}"
        say("process: 2 workers on the CPU (each checks its own backend), "
            "0 deaths, 0 respawns, env_steps="
            f"{[int(w['env_steps']) for w in workers.values()]}")

    run_leg("process", [
        "--set", "actor.mode=process",
        "--set", "actor.num_workers=2",
        "--set", "actor.num_actors=16",
        "--set", "learner.min_replay_mem_size=2048",
    ], 2 * K, inspect)


def leg_serving() -> None:
    def inspect(pipe, final):
        server = pipe._central_server
        assert server is not None, "serving: no in-process PolicyServer"
        stats = server.stats()
        check_on_tpu("serving params", server._live[0])
        buckets = server.batcher.buckets
        compiled = server._apply._cache_size()
        assert compiled == len(buckets), (
            f"serving: {compiled} compiled shapes for buckets {buckets} — "
            "a request shape was not warmed"
        )
        assert stats["served_total"] >= 300, stats
        assert stats["error_total"] == 0, stats
        inf = final["inference"]
        say(f"serving: served={stats['served_total']} errors=0 "
            f"buckets_warmed={buckets} batch_hist={stats['batch_hist']} "
            f"p50_ms={stats['latency'].get('p50_ms')} "
            f"p99_ms={stats['latency'].get('p99_ms')} "
            f"reply_param_version={inf.get('param_version')}")

    run_leg("serving", [
        "--set", "actor.num_actors=64",
        "--set", "actor.inference=central",
        "--set", "learner.min_replay_mem_size=4096",
    ], 2 * K, inspect)


def leg_dp4() -> None:
    import jax

    def inspect(pipe, final):
        fused = pipe.fused
        devs = jax.devices()[:4]
        frames = fused._replay.rows
        shard_devs = [s.device for s in frames.addressable_shards]
        assert len(shard_devs) == 4 and set(shard_devs) == set(devs), (
            f"dp4: frame ring shards on {shard_devs}"
        )
        rows = {s.data.shape[0] for s in frames.addressable_shards}
        assert len(rows) == 1, f"dp4: uneven ring shards {rows}"
        for leaf in jax.tree_util.tree_leaves(fused.state.params):
            assert leaf.sharding.is_fully_replicated, leaf.sharding
            assert {s.device for s in leaf.addressable_shards} == set(devs)
        used = [d.memory_stats()["bytes_in_use"] for d in devs]
        spread = (max(used) - min(used)) / max(used)
        assert spread <= 0.10, f"dp4: bytes_in_use per device {used}"
        say(f"dp4: frame ring rows {frames.shape} in 4 shards of "
            f"{rows.pop()} rows on {[d.id for d in shard_devs]}; params "
            f"replicated on all 4; bytes_in_use per device {used} "
            f"(spread {spread:.1%})")
        # The fused program's collectives, from its compiled text: the two
        # streams' gradients come from gathered rows, so no all-reduce carries
        # a 3136 x 512 kernel (6.4 MB of them in float32, 3.2 in bfloat16).
        from ape_x_dqn_tpu.utils import profiling

        name = "jit_" + fused._fused.__wrapped__.__name__
        collectives = profiling.hlo_collectives(profiling.fused_hlo_text(name))
        say(f"dp4: collectives of {name}: {json.dumps(collectives)}")
        total = lambda kind: sum(  # noqa: E731
            how["bytes"] for how in collectives.get(kind, {}).values())
        assert total("all-gather") > 0 and total("all-reduce") < 1 << 20, (
            f"dp4: the streams' kernels are still all-reduced: {collectives}")

    run_leg("dp4", [
        "--set", "actor.num_actors=64",
        "--set", "learner.data_parallel=4",
        "--set", "replay.dedup=true",
        "--set", "learner.min_replay_mem_size=4096",
    ], 2 * K, inspect)


def leg_lfm2moe() -> None:
    from ape_x_dqn_tpu import train

    steps = 24

    def inspect(pipe, final):
        check_run("lfm2moe", pipe, final, steps)
        check_launch("lfm2moe", t0)
        assert type(pipe.comps.network).__name__ == "Lfm2MoeQ"
        assert final["param_version"] >= 1, "lfm2moe: nothing was published"
        routing = final.get("routing") or {}
        assert routing.get("held_pairs", 0) > 0, f"lfm2moe: no routing counters: {final}"
        say(f"lfm2moe: routing a step {routing}; actors adopted param_version "
            f"{pipe.worker.param_version} of {final['param_version']}")

    t0 = time.perf_counter()
    rc = train.main([
        "--params-file", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                      "configs", "config6_lfm2moe_q_ep8.json"),
        "--set", "env.name=fake-atari",
        "--set", "actor.mode=thread", "--set", "actor.num_actors=16",
        "--set", "actor.sync_every=1",
        # the ring a quarter of the cell's: the actors hold a copy of the
        # parameters beside the learner's state and temporaries
        "--set", "replay.capacity=32768",
        "--set", "learner.min_replay_mem_size=2048",
        "--set", "learner.publish_every=4",
        "--log-every", "4", "--steps", str(steps),
    ], inspect=inspect)
    assert rc == 0, f"lfm2moe: train.main returned {rc}"


def _train_on_histories(leg: str, config: str, steps: int, inspect) -> None:
    """``train.main`` on one of the 32-frame-history torsos with 4 thread
    actors, batch and ring cut to what fits beside the actors' parameters."""
    from ape_x_dqn_tpu import train

    def _inspect(pipe, final):
        check_launch(leg, t0)
        inspect(pipe, final)

    t0 = time.perf_counter()
    rc = train.main([
        "--params-file", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                      "configs", config),
        "--set", "env.name=fake-atari",
        "--set", "actor.mode=thread", "--set", "actor.num_actors=4",
        "--set", "actor.sync_every=1",
        "--set", "learner.replay_sample_size=2",
        "--set", "replay.capacity=1024",
        "--set", "learner.min_replay_mem_size=64",
        "--set", "learner.publish_every=4",
        "--log-every", "4", "--steps", str(steps),
    ], inspect=_inspect)
    assert rc == 0, f"{leg}: train.main returned {rc}"


def leg_laguna() -> None:
    steps = 12

    def inspect(pipe, final):
        check_run("laguna", pipe, final, steps)
        assert type(pipe.comps.network).__name__ == "LagunaMoeQ"
        assert final["param_version"] >= 1, "laguna: nothing was published"
        routing, attention = final.get("routing") or {}, final.get("attention") or {}
        assert routing.get("held_pairs", 0) > 0, f"laguna: no routing counters: {final}"
        assert 0 < attention.get("blocks_visited_window", 0) < attention.get(
            "blocks_total_window", 0), f"laguna: no attention counters: {final}"
        say(f"laguna: routing a step {routing}; attention a step {attention}; actors "
            f"adopted param_version {pipe.worker.param_version} of {final['param_version']}")

    _train_on_histories("laguna", "config7_laguna_q_ep32.json", steps, inspect)


def leg_granite() -> None:
    steps = 12

    def inspect(pipe, final):
        check_run("granite", pipe, final, steps)
        assert type(pipe.comps.network).__name__ == "GraniteHybridQ"
        assert final["param_version"] >= 1, "granite: nothing was published"
        scan, attention = final.get("scan") or {}, final.get("attention") or {}
        assert "routing" not in final, f"granite: routing counters without experts: {final}"
        # batch 2, three forwards, nine state-space layers, 7 chunks of 256 over 1,568 tokens
        assert scan.get("chunks") == 2 * 3 * 9 * 7 and 0 < scan.get("tokens", 0) < scan.get(
            "tokens_padded", 0), f"granite: no scan counters: {final}"
        assert 0 < attention.get("blocks_visited_full", 0) < attention.get(
            "blocks_total_full", 0), f"granite: no attention counters: {final}"
        say(f"granite: scan a step {scan}; attention a step {attention}; actors "
            f"adopted param_version {pipe.worker.param_version} of {final['param_version']}")

    _train_on_histories("granite", "config8_granite4h_q_l10.json", steps, inspect)


def leg_solar() -> None:
    steps = 12

    def inspect(pipe, final):
        check_run("solar", pipe, final, steps)
        assert type(pipe.comps.network).__name__ == "SolarOpen2Q"
        assert final["param_version"] >= 1, "solar: nothing was published"
        delta, attention = final.get("delta") or {}, final.get("attention") or {}
        routing = final.get("routing") or {}
        assert "scan" not in final, f"solar: state-space counters without such layers: {final}"
        # batch 2, three forwards, three delta-rule layers, 25 chunks of 64 over 1,568 tokens
        assert delta.get("chunks") == 2 * 3 * 3 * 25 and 0 < delta.get("tokens", 0) < delta.get(
            "tokens_padded", 0), f"solar: no delta-rule counters: {final}"
        assert 0 < attention.get("blocks_visited_full", 0) < attention.get(
            "blocks_total_full", 0), f"solar: no attention counters: {final}"
        assert routing.get("held_pairs", 0) > 0, f"solar: no routing counters: {final}"
        say(f"solar: delta rule a step {delta}; attention a step {attention}; routing a step "
            f"{routing}; actors adopted param_version {pipe.worker.param_version} of "
            f"{final['param_version']}")

    _train_on_histories("solar", "config9_solar2_q_ep40.json", steps, inspect)


def leg_ling() -> None:
    steps = 12

    def inspect(pipe, final):
        check_run("ling", pipe, final, steps)
        assert type(pipe.comps.network).__name__ == "LingHybridQ"
        assert final["param_version"] >= 1, "ling: nothing was published"
        delta, attention = final.get("delta") or {}, final.get("attention") or {}
        routing = final.get("routing") or {}
        assert "scan" not in final, f"ling: state-space counters without such layers: {final}"
        # batch 2, three forwards, six delta-rule layers, 25 chunks of 64 over 1,568 tokens
        assert delta.get("chunks") == 2 * 3 * 6 * 25 and 0 < delta.get("tokens", 0) < delta.get(
            "tokens_padded", 0), f"ling: no delta-rule counters: {final}"
        assert 0 < attention.get("blocks_visited_latent", 0) < attention.get(
            "blocks_total_latent", 0), f"ling: no latent attention counters: {final}"
        assert routing.get("held_pairs", 0) > 0 and 0 < routing.get(
            "groups_kept_hold_share", 0) <= 1, f"ling: no routing counters: {final}"
        say(f"ling: delta rule a step {delta}; attention a step {attention}; routing a step "
            f"{routing}; actors adopted param_version {pipe.worker.param_version} of "
            f"{final['param_version']}")

    _train_on_histories("ling", "config10_ling3_q_l7.json", steps, inspect)


# What the blocked kernels may differ by from plain float32 attention on the
# same bfloat16 operands, as ||got - want|| / ||want|| over a whole tensor
# (and how far at least from plain attention that lost the shared key's
# scores, as ||got - wrong|| / ||got||, where the two gradients it lacks read 1):
# the kernels round the probabilities and the output to bfloat16 (2**-9 an
# element, which averages out over a tensor's norm).  Read on the chip at the
# cell's shapes (my chip run, PR 42): the output 0.00204, the five gradients
# 0.00284-0.00339 (in Pallas' interpreter on the CPU at 300 tokens 0.0019 and
# 0.0032-0.0038); without the shared key's scores 0.471-0.489.
KERNEL_REL = 0.01
KERNEL_REL_WITHOUT_SHARED_KEY = 0.2


def latent_kernels_against_plain(rows: int = 8, heads: int = 8, tokens: int = 1568,
                                 width: int = 128, shared: int = 64) -> dict:
    """``blocked_attention`` with its shared key operand against plain causal
    attention in float32 on the same bfloat16 operands: the output and the
    gradients of both query parts, the keys, the values and the shared key,
    each within ``KERNEL_REL`` of plain attention and further than
    ``KERNEL_REL_WITHOUT_SHARED_KEY`` from plain attention that dropped
    ``q_shared k_shared^T``.  -> the readings by name, (with, without)."""
    import math

    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked

    def plain(q, k, v, qs, ks, with_shared=True):
        f32 = [x.astype(jnp.float32) for x in (q, k, v, qs, ks)]
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bhtd,bhsd->bhts", f32[0], f32[1])
            if with_shared:
                s = s + jnp.einsum("bhtd,bosd->bhts", f32[3], f32[4])
            p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((tokens, tokens), bool)), s, -jnp.inf), -1)
            return jnp.einsum("bhts,bhsd->bhtd", p, f32[2])

    key = jax.random.split(jax.random.PRNGKey(42), 6)
    scale = 1.0 / math.sqrt(width + shared)
    bf = jnp.bfloat16
    args = ((jax.random.normal(key[0], (rows, heads, tokens, width)) * scale).astype(bf),
            jax.random.normal(key[1], (rows, heads, tokens, width)).astype(bf),
            jax.random.normal(key[2], (rows, heads, tokens, width)).astype(bf),
            (jax.random.normal(key[3], (rows, heads, tokens, shared)) * scale).astype(bf),
            jax.random.normal(key[4], (rows, 1, tokens, shared)).astype(bf))
    cot = jax.random.normal(key[5], (rows, heads, tokens, width))

    def both(fn):       # the output and the five gradients under one cotangent, in float32
        def run(cot, *args):
            out, pull = jax.vjp(fn, *args)
            return [x.astype(jnp.float32) for x in (out, *pull(cot.astype(out.dtype)))]
        return jax.jit(run)(cot, *args)

    got = both(lambda *a: blocked.blocked_attention(a[0], a[1], a[2], None, a[3], a[4]))
    want, wrong = both(plain), both(lambda *a: plain(*a, with_shared=False))
    readings = {}
    for name, a, b, c in zip(("out", "dq", "dk", "dv", "dq_shared", "dk_shared"), got, want, wrong):
        assert a.shape == b.shape, f"{name}: {a.shape} for {b.shape}"
        readings[name] = (float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
                          float(jnp.linalg.norm(a - c) / jnp.linalg.norm(a)))
        assert readings[name][0] <= KERNEL_REL, f"{name}: {readings[name]} from plain attention"
        assert readings[name][1] >= KERNEL_REL_WITHOUT_SHARED_KEY, (
            f"{name}: {readings[name]}: the check would not see a lost shared key")
    return readings


def route_against_sorting(config: str = "config10_ling3_q_l7.json", tokens: int = 12544) -> dict:
    """``expert_torso.route`` under the configuration's spec (512 outputs in 8
    groups, 4 kept, 8 a token) against a router by sorting on the host, in
    float32 as the program's: the same experts for every token, the gates
    within 1e-5, no expert outside a kept group; and the top 8 of all 512,
    what a router that forgot its groups gives, differs on most tokens."""
    import dataclasses

    import jax
    import numpy as np

    from ape_x_dqn_tpu.config import load_config
    from ape_x_dqn_tpu.models import expert_torso, ling_hybrid

    here = os.path.dirname(os.path.abspath(__file__))
    torso = load_config(os.path.join(here, "configs", config)).torso
    spec = ling_hybrid.spec_from_config({k: v for k, v in torso.items() if not k.startswith("_")})
    outputs, groups, kept, k = (spec.router_outputs, spec.router_groups, spec.router_groups_kept,
                                spec.num_experts_per_tok)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(7), (tokens, outputs)))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(8), (outputs,))
    chosen, gates = jax.jit(lambda s, b: expert_torso.route(s, b, spec))(scores, bias)
    plain = jax.jit(lambda s, b: expert_torso.route(s, b, dataclasses.replace(
        spec, router_groups=1, router_groups_kept=1))[0])(scores, bias)
    chosen, gates, plain = np.asarray(chosen), np.asarray(gates), np.asarray(plain)

    s, b = np.asarray(scores, np.float32), np.asarray(bias, np.float32)
    biased = s + b
    by_group = np.sort(biased.reshape(tokens, groups, -1), -1)
    group_score = by_group[..., -1] + by_group[..., -2]
    keep = np.argsort(-group_score, -1, kind="stable")[:, :kept]        # the earlier of two equal first
    allowed = (keep[:, :, None] == np.arange(groups)).any(1).repeat(outputs // groups, -1)
    want = np.argsort(-np.where(allowed, biased, -np.inf), -1, kind="stable")[:, :k]
    want_gates = np.take_along_axis(s, want, -1)
    want_gates = want_gates / (want_gates.sum(-1, keepdims=True) + spec.gate_norm_eps) * (
        spec.routed_scaling_factor)
    differing = int((chosen != want).any(-1).sum())
    assert differing == 0, f"route: {differing} of {tokens} tokens choose other experts than sorting"
    np.testing.assert_allclose(gates, want_gates, rtol=1e-5)
    assert np.take_along_axis(allowed, chosen, -1).all(), "route: an expert outside the kept groups"
    ungrouped = float((np.sort(plain, -1) != np.sort(chosen, -1)).any(-1).mean())
    assert ungrouped > 0.5, f"route: the top {k} of all {outputs} agree on {1 - ungrouped:.2f} of tokens"
    return {"tokens": tokens, "differing": differing, "ungrouped_differs_share": ungrouped,
            "kept_hold_group_0_share": float(allowed[:, 0].mean())}


# (tokens a step's forward, router outputs, a token's, groups, groups kept): ling3_q_l7,
# solar2_q_ep40, laguna_q_ep32, lfm2moe_q_ep8; then the rows of a tile of the walk and the width
ROUTER_SHAPES = ((12544, 512, 8, 8, 4), (12544, 320, 8, 1, 1), (12544, 256, 10, 1, 1),
                 (25088, 64, 4, 1, 1))
WALK_TILE = (4608, 2560)
GATE_NORM_EPS, GATE_SCALE = 1e-6, 2.5


def choice_by_sorting(scores, bias, k: int, groups: int, kept: int):
    """(chosen, gates, groups kept or None, raw gates) as the router chose
    until PR 43: three ``top_k`` and a ``take_along_axis``, the gates
    normalised over their ``jnp.sum``.  The oracle."""
    import jax
    import jax.numpy as jnp

    biased, held = scores + bias, None
    if groups > 1:
        by_group = biased.reshape(biased.shape[0], groups, -1)
        _, best = jax.lax.top_k(jnp.sum(jax.lax.top_k(by_group, 2)[0], -1), kept)
        held = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
        biased = jnp.where(jnp.repeat(held, biased.shape[1] // groups, axis=-1), biased, -jnp.inf)
    _, chosen = jax.lax.top_k(biased, k)
    raw = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = raw / (jnp.sum(raw, -1, keepdims=True) + GATE_NORM_EPS) * GATE_SCALE
    return chosen, gates, held, raw


def choice_by_selection(scores, bias, k: int, groups: int, kept: int):
    """The same four from ``expert_torso.choose`` under a spec of these
    shapes (the raw gates from ``router_choice`` itself)."""
    from ape_x_dqn_tpu.models import expert_torso
    from ape_x_dqn_tpu.ops.router_choice import router_choice

    spec = expert_torso.TorsoSpec(
        hidden_size=8, intermediate_size=8, moe_intermediate_size=8, norm_eps=1e-5,
        router_outputs=scores.shape[1], num_experts_per_tok=k, experts_held=(0, 1),
        layers=(("op", "moe"),), mixers=(("op", None),), gate_norm_eps=GATE_NORM_EPS,
        routed_scaling_factor=GATE_SCALE, router_groups=groups, router_groups_kept=kept)
    return (*expert_torso.choose(scores, bias, spec), router_choice(scores, bias, None, k, groups, kept)[1])


def _device_microseconds(step, carry, repeats: int, *held) -> float:
    """One execution of ``step`` (carry, *held -> carry) on the device, from
    ``repeats`` dependent ones inside one program: a dispatch from this host
    is 200 us, more than most of what is timed here.  ``held`` is read and
    not returned: an array too large to close over or to copy out."""
    import jax

    run = _repeated(step, repeats)
    jax.block_until_ready(run(carry, *held))
    t0 = time.perf_counter()
    jax.block_until_ready(run(carry, *held))
    return (time.perf_counter() - t0) / repeats * 1e6


def _repeated(step, repeats: int):
    import jax

    return jax.jit(lambda c, *held: jax.lax.fori_loop(0, repeats, lambda _, c: step(c, *held), c))


def _device_op_microseconds(step, carry, repeats: int, *held, top: int = 6) -> dict:
    """{instruction: us an execution} of the ``top`` longest device ops of
    ``step``, from a trace of ``repeats`` dependent executions in one
    program (``_device_microseconds``' program, run once more under the
    profiler)."""
    import collections
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    run = _repeated(step, repeats)
    jax.block_until_ready(run(carry, *held))
    seconds = collections.Counter()
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            jax.block_until_ready(run(carry, *held))
        (found,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
        for plane in ProfileData.from_file(found).planes:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        seconds[e.name.split(" = ", 1)[0].lstrip("%").strip()] += e.duration_ns * 1e-3
    return {name: round(us / repeats, 2) for name, us in seconds.most_common(top)}


def choice_against_sorting_on_the_chip(shapes=ROUTER_SHAPES, walk_tile=WALK_TILE,
                                       repeats: int = 20) -> list:
    """``expert_torso.choose`` against ``choice_by_sorting`` on this device, at
    the four expert cells' shapes, on random scores and on scores in 1/64ths
    with no bias (ties on most rows): chosen, kept, raw and normalised gates
    and the gradient of a weighted sum of the normalised gates equal bit for
    bit (on a TPU the oracle's ``jnp.sum`` over k runs along the lanes, the
    order ``expert_torso._lane_sum`` writes out); the microseconds of both
    (the scores' sigmoid and the bias's dependence on the last execution
    inside), of the pairs' ``argsort`` and of one tile's scatter-add (the
    walk's combine), which are not the choice's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def both(choice, k, groups, kept):
        def run(scores, bias, weight):
            out, pull = jax.vjp(lambda s: choice(s, bias, k, groups, kept)[1], scores)
            return choice(scores, bias, k, groups, kept), pull(weight)[0]
        return jax.jit(run)

    rows = []
    for tokens, outputs, k, groups, kept in shapes:
        key = jax.random.split(jax.random.PRNGKey(tokens + outputs), 3)
        logits = jax.random.normal(key[0], (tokens, outputs))
        scores = jax.nn.sigmoid(logits)
        bias = 0.05 * jax.random.normal(key[1], (outputs,))
        weight = jax.random.normal(key[2], (tokens, k))
        new, old = both(choice_by_selection, k, groups, kept), both(choice_by_sorting, k, groups, kept)
        names = ("chosen", "gates") + (("kept",) if groups > 1 else ()) + ("raw gates", "dscores")
        for name, (s, b) in (("random", (scores, bias)),
                             ("ties", (jnp.round(scores * 64) / 64, jnp.zeros_like(bias)))):
            got, want = jax.tree.leaves(new(s, b, weight)), jax.tree.leaves(old(s, b, weight))
            assert len(got) == len(want) == len(names)
            for what, a, c in zip(names, got, want):
                a, c = np.asarray(a), np.asarray(c)
                if what in ("gates", "dscores") and jax.default_backend() != "tpu":
                    # off the chip the oracle's sum over k adds one after another
                    np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-6 * np.abs(c).max())
                    continue
                if a.dtype == np.float32:
                    a, c = a.view(np.uint32), c.view(np.uint32)
                differing = int((a != c).reshape(tokens, -1).any(-1).sum())
                assert a.shape == c.shape and differing == 0, (
                    f"choice: {what} differs from the sort's on {differing} of {tokens} tokens "
                    f"({outputs} outputs, {name} scores)")

        def chained(choice, pulled: bool):
            def step(bias):     # the next execution's bias hangs on this one's gates
                s = jax.nn.sigmoid(logits + bias[0])
                if not pulled:
                    return bias + 0.0 * choice(s, bias, k, groups, kept)[1][0, 0]
                gates, pull = jax.vjp(lambda s: choice(s, bias, k, groups, kept)[1], s)
                return bias + 0.0 * (gates[0, 0] + pull(weight)[0][0, 0])
            return step

        pairs = jax.random.randint(key[2], (tokens * k,), 0, 17)
        timed = {f"{name}{suffix}_us": _device_microseconds(chained(choice, pulled), bias, repeats)
                 for name, choice in (("selection", choice_by_selection), ("sorting", choice_by_sorting))
                 for suffix, pulled in (("", False), ("_with_gradient", True))}
        rows.append({
            "tokens": tokens, "outputs": outputs, "k": k, "groups": groups, **timed,
            "sigmoid_alone_us": _device_microseconds(
                lambda b: b + 0.0 * jax.nn.sigmoid(logits + b[0])[0, 0], bias, repeats),
            "pairs_argsort_us": _device_microseconds(
                lambda p: p + jnp.minimum(jnp.argsort(p, stable=True)[0], 0), pairs, repeats)})
    tile, width = walk_tile
    tokens = shapes[0][0]
    token = jax.random.randint(jax.random.PRNGKey(3), (tile,), 0, tokens)
    ys = jax.random.normal(jax.random.PRNGKey(4), (tile, width))
    rows.append({"tile_rows": tile, "width": width, "scatter_add_us": _device_microseconds(
        lambda y: y.at[token].add(ys), jnp.zeros((tokens, width)), repeats)})
    return rows


# (tokens a step's forward, router outputs, a token's, experts held, width): the four expert
# cells in ``ROUTER_SHAPES``' order; a tile of the walk is ``tile_rows`` of these
WALK_SHAPES = ((12544, 512, 8, 16, 2560), (12544, 320, 8, 8, 4096), (12544, 256, 10, 8, 3072),
               (25088, 64, 4, 8, 2048))


def combine_against_whole_rows_on_the_chip(shapes=WALK_SHAPES, repeats: int = 20) -> list:
    """The walk's combine, ``expert_torso._combined`` (a token sum in column
    blocks, one scatter-add a block), against one scatter-add of whole rows
    on this device at a tile of each expert cell: the held pairs of a random
    choice of experts sorted by expert as the walk has them, float32 rows,
    zero past the last pair, onto sums that hold something.  Both within
    float32's rounding of a float64 sum by token (they add the same terms in
    the same order: ``equal_bits``), and the microseconds of both, dependent
    executions inside one program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ape_x_dqn_tpu.models import expert_torso

    def chained(add):       # the next execution's indices hang on this one's sums
        def step(carry):
            y, token, ys = carry
            moved = (jax.tree.leaves(y)[0][0, 0] < -1e30).astype(token.dtype)
            return add(y, token + moved, ys), token, ys
        return step

    whole_rows = lambda y, token, ys: y.at[token].add(ys)  # noqa: E731
    rows = []
    for tokens, outputs, k, held, width in shapes:
        rng = np.random.default_rng(tokens + outputs)
        chosen = np.argsort(rng.random((tokens, outputs)), -1)[:, :k]
        keys = np.where(chosen < held, chosen, held).reshape(-1)
        order = np.argsort(keys, kind="stable")
        tile = int(expert_torso.tile_rows(tokens * k, held, outputs))
        live = min(int((keys < held).sum()), tile)
        token = jnp.asarray(np.pad(order, (0, -order.size % tile))[:tile] // k, jnp.int32)
        key = jax.random.split(jax.random.PRNGKey(tokens + width), 2)
        ys = jnp.where((jnp.arange(tile) < live)[:, None], jax.random.normal(key[0], (tile, width)), 0.0)
        y0 = jax.random.normal(key[1], (tokens, width))
        widths = [b.shape[1] for b in jax.eval_shape(expert_torso._zero_blocks, y0)]
        blocks0 = tuple(jnp.split(y0, np.cumsum(widths)[:-1], axis=1))
        got = np.asarray(jnp.concatenate(jax.jit(expert_torso._combined)(blocks0, token, ys), axis=1))
        want = np.asarray(jax.jit(whole_rows)(y0, token, ys))
        exact = np.asarray(y0, np.float64)
        np.add.at(exact, np.asarray(token), np.asarray(ys, np.float64))
        for name, sums in (("column blocks", got), ("whole rows", want)):
            np.testing.assert_allclose(sums, exact, rtol=0, atol=1e-5, err_msg=(
                f"combine: {name} differ from the float64 sum by token ({tokens} x {width})"))
        rows.append({
            "tokens": tokens, "tile_rows": tile, "live_rows": live, "width": width, "blocks": len(widths),
            "equal_bits": bool(np.array_equal(got, want)),
            "column_blocks_us": _device_microseconds(
                chained(expert_torso._combined), (blocks0, token, ys), repeats),
            "whole_rows_us": _device_microseconds(chained(whole_rows), (y0, token, ys), repeats)})
    return rows


# (rows a chip, frames an observation, the first convolution's outputs): the three conv cells,
# ``apex_b512``, a chip of ``apex_b512_dp4`` and ``ref_b32``
FIRST_CONV_SHAPES = ((512, 4, 32), (128, 4, 32), (32, 1, 64))


def first_conv_of_two_on_the_chip(shapes=FIRST_CONV_SHAPES, repeats: int = 50) -> list:
    """The bootstrap's first convolution apart, on this device, at the conv
    cells' shapes: two convolutions of N outputs (an online bank in float32
    and a target bank in bfloat16 on the same bytes, each with the cast, the
    bias and the ReLU, as two ``nn.Conv`` make them) against
    ``dueling.first_conv_of_two`` (one of 2N), equal bits, and the
    microseconds of both: alone, the outputs written out, and with each
    net's second convolution behind its half, which is what reads them in
    the step.  The observations are the loop's to lay out, as the step's
    batch is the scan's."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.models import dueling

    def conv(x, leaves, window):     # nn.Conv's own call, operands cast as it casts them
        (k, s), cd = window, x.dtype
        y = jax.lax.conv_general_dilated(x, leaves["kernel"].astype(cd), (s, s), "VALID",
                                         dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + leaves["bias"].astype(cd))

    rows = []
    for batch, frames, outputs in shapes:
        channels, cd = (outputs, 64, 64), jnp.bfloat16
        net = dueling.DuelingDQN(num_actions=18, channels=channels, compute_dtype=cd)
        key = jax.random.split(jax.random.PRNGKey(batch + outputs), 3)
        obs = jax.random.randint(key[0], (batch, 84, 84, frames), 0, 256).astype(jnp.uint8)
        init = jax.jit(net.init)
        online = init(key[1], obs)
        target = jax.tree.map(lambda p: p.astype(cd), init(key[2], obs))
        nets = (online, target)

        def apart(obs):
            x = obs.astype(cd) / 255.0
            return tuple(conv(x, p["params"]["Conv_0"], dueling.STEM_WINDOWS[0]) for p in nets)

        def joined(obs):
            return dueling.first_conv_of_two(online, target, obs, cd)

        def then_second(firsts):
            def run(obs):
                return tuple(conv(y, p["params"]["Conv_1"], dueling.STEM_WINDOWS[1])
                             for y, p in zip(firsts(obs), nets))
            return run

        def chained(outputs_of):    # the next execution's bytes hang on this one's outputs
            def step(carry):
                obs, moved, _ = carry
                ys = outputs_of(obs ^ moved)    # fused into the cast: no pass of its own
                return obs, sum(jnp.isnan(y[0, 0, 0, 0]) for y in ys).astype(jnp.uint8), ys
            return step

        want, got = jax.jit(apart)(obs), jax.jit(joined)(obs)
        equal = all(bool(jnp.array_equal(w, g)) for w, g in zip(want, got))
        assert equal, f"first convolution: one of {2 * outputs} differs from two of {outputs} at {obs.shape}"
        timed = {}
        for name, fn in (("apart", apart), ("joined", joined),
                         ("apart_then_second", then_second(apart)),
                         ("joined_then_second", then_second(joined))):
            carry = (obs, jnp.uint8(0), jax.jit(fn)(obs))
            timed[name + "_us"] = round(_device_microseconds(chained(fn), carry, repeats), 2)
        flop = 2.0 * batch * 20 * 20 * 64 * frames * outputs     # a bank's
        rows.append({
            "obs": list(obs.shape), "outputs": outputs, "equal_bits": equal, **timed,
            "apart_tflops": round(2 * flop / timed["apart_us"] / 1e6, 2),
            "joined_tflops": round(2 * flop / timed["joined_us"] / 1e6, 2)})
    return rows


def leg_first_conv() -> None:
    for row in first_conv_of_two_on_the_chip():
        say(f"first_conv: two first convolutions of N against one of 2N: {row}")


# (rows a chip): ``apex_b512`` and ``lfm2moe_q_ep8``, and a chip of ``apex_b512_dp4``
FETCH_BATCHES = (512, 128)


def fetch_against_three_ops_on_the_chip(batches=FETCH_BATCHES, obs_shape=(84, 84, 4),
                                        frames: int = 65536, repeats: int = 50) -> list:
    """A side of the gather stage apart, on this device: ``dedup_fetch`` on a
    ring of ``frames`` rows stored as whole tiles (the kernel,
    ``ops/pallas/row_fetch.py``) beside the three ops it replaced on the same
    words stored a run a row (the compiler's row gather, the copy that turns
    the batch to the lanes, the unpack into bytes), each with the first
    convolution behind it, which is what decides the layout of both; the bytes
    of both sides must be the host's.  The microseconds of each and of the
    convolution alone on bytes the loop lays out, each program's longest
    device ops from a trace, and the kernel's own time and rate from there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ape_x_dqn_tpu.replay.device_dedup import DedupDeviceReplayState, RowFormat, dedup_fetch
    from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch

    fmt = RowFormat.of(obs_shape, np.uint8)
    words = fmt.row_elems // 4
    key = jax.random.split(jax.random.PRNGKey(50), 3)
    tiled = jax.jit(lambda k: jax.random.bits(k, (frames, *fmt.row_shape), jnp.uint32))(key[0])
    runs = jax.jit(lambda r: r.reshape(frames, fmt.row_stride))(tiled)
    kernel_w = (jax.random.normal(key[1], (8, 8, obs_shape[-1], 32)) * 0.05).astype(jnp.bfloat16)

    def conv(obs):
        return jax.lax.conv_general_dilated(obs.astype(jnp.bfloat16) / 255.0, kernel_w, (4, 4), "VALID",
                                            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def shipped(slots, tiled):     # both sides through the program's own entry point
        sampled = PrioritizedBatch(
            transition=NStepTransition(obs=slots, action=None, reward=None, discount=None,
                                       next_obs=slots[::-1]), indices=None, is_weights=None)
        got = dedup_fetch(DedupDeviceReplayState(rows=tiled, fmt=fmt), sampled).transition
        return got.obs, got.next_obs

    def three_ops(slots, runs):
        taken = runs[slots][:, :words]
        return jax.lax.bitcast_convert_type(taken, jnp.uint8).reshape(slots.shape[0], *obs_shape)

    def chained(fetch, then):     # the next execution's slots hang on this one's bytes
        def step(carry, ring):
            slots, _ = carry
            y = then(fetch(slots, ring))
            moved = jnp.isnan(y.reshape(-1)[0].astype(jnp.float32)).astype(jnp.int32)
            return (slots + moved) % frames, y
        return step

    rows = []
    for batch in batches:
        slots = jax.random.randint(key[2], (batch,), 0, frames, jnp.int32)
        slots = slots.at[:4].set(jnp.array([0, frames - 1, 7, 7]))
        got, got_next = jax.jit(shipped)(slots, tiled)
        want = jax.jit(three_ops)(slots, runs)
        host = np.asarray(runs[slots[:16]])[:, :words].view(np.uint8).reshape(16, *obs_shape)
        equal = (bool(jnp.array_equal(got, want)) and bool(jnp.array_equal(got_next, want[::-1]))
                 and bool(np.array_equal(np.asarray(got[:16]), host)))
        assert equal, f"the kernel's bytes are not the gather's at {batch} rows of {obs_shape}"
        kernel_side = lambda s, ring: shipped(s, ring)[0]  # noqa: E731
        laid_out = lambda s, obs: obs ^ (s[0] >> 30).astype(jnp.uint8)  # noqa: E731
        timed = {}
        for name, fetch, then, ring in (
                ("kernel_then_conv", kernel_side, conv, tiled),
                ("three_ops_then_conv", three_ops, conv, runs), ("conv", laid_out, conv, want)):
            step = chained(fetch, then)
            carry = (slots, jax.jit(lambda s, r, step=step: step((s, None), r)[1])(slots, ring))
            timed[name + "_us"] = round(_device_microseconds(step, carry, repeats, ring), 2)
            if name != "conv":
                timed[name + "_ops_us"] = _device_op_microseconds(step, carry, repeats, ring)
        kernel_us = next((us for name, us in timed["kernel_then_conv_ops_us"].items()
                          if name.startswith("fetch_turned")), None)    # none off the chip: no device trace
        rows.append({
            "rows": batch, "obs": list(obs_shape), "ring": list(tiled.shape), "equal_bits": equal, **timed,
            "kernel_side_us": round(timed["kernel_then_conv_us"] - timed["conv_us"], 2),
            "three_ops_side_us": round(timed["three_ops_then_conv_us"] - timed["conv_us"], 2),
            "kernel_us": kernel_us,
            "kernel_gb_per_s": kernel_us and round(batch * fmt.row_stride * 4 / kernel_us / 1e3, 1)})
    return rows


def leg_fetch() -> None:
    import collections

    from ape_x_dqn_tpu.utils import profiling

    for row in fetch_against_three_ops_on_the_chip():
        say(f"fetch: a side in one kernel against the gather, the copy and the unpack: {row}")
    traced = collections.Counter(tuple(side.items()) for side in profiling.launch.attrs_of("gather_path"))
    for side, times in traced.items():
        say(f"fetch: gather_path {dict(side)}: {times} traced sides")


OLMO_WALK_REL = 0.03    # of the largest |value|: bfloat16 operands against a float32 recurrence


def walk_against_the_recurrence(rows=2, heads=30, tokens=1568, kw=96, vw=192, chunk=64,
                                repeats=5, per_channel=False):
    """{name: (relative distance, the same with ``beta`` held to 1)} of the
    delta walk's output and five gradients from the literal recurrence's
    (float32, a token a step), and the walk's microseconds: the scalar-gate
    form, or with ``per_channel`` a log decay a key channel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta

    ks = jax.random.split(jax.random.PRNGKey(51), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, heads, tokens, kw))) / np.sqrt(kw)
    k = unit(jax.random.normal(ks[1], (rows, heads, tokens, kw)) + 0.5)
    v = jax.random.normal(ks[2], (rows, heads, tokens, vw))
    g = -0.1 * jax.random.uniform(ks[3], (rows, heads, tokens, *([kw] if per_channel else [])))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, heads, tokens)))
    cot = jax.random.normal(ks[5], v.shape)
    low = lambda x: x.astype(jnp.bfloat16)  # noqa: E731

    def literal(q, k, v, g, beta):
        def step(state, token):
            qt, kt, vt, gt, bt = token
            state = jnp.exp(gt).reshape(*state.shape[:2], -1, 1) * state     # a head's decay, or a channel's
            read = jnp.sum(kt[..., None] * state, axis=-2)
            state = state + (bt[..., None] * kt)[..., None] * (vt - read)[..., None, :]
            return state, jnp.sum(qt[..., None] * state, axis=-2)

        # the backward pass keeps a state a segment and steps the segment again:
        # a state a token would be 6.9 GB at these sizes
        seg = max(s for s in range(1, 57) if tokens % s == 0)
        segment = jax.checkpoint(lambda state, part: jax.lax.scan(step, state, part))
        by_time = tuple(jnp.moveaxis(x, 2, 0).reshape(tokens // seg, seg, *x.shape[:2], *x.shape[3:])
                        for x in (q, k, v, g, beta))
        first = jnp.zeros((*q.shape[:2], q.shape[-1], v.shape[-1]), jnp.float32)
        _, o = jax.lax.scan(segment, first, by_time)
        return jnp.moveaxis(o.reshape(tokens, *o.shape[2:]), 0, 2)

    def pulled(fn):
        def run(*args):
            out, pull = jax.vjp(fn, *args)
            return (out, *pull(cot.astype(out.dtype)))
        return jax.jit(run)

    walk = lambda q, k, v, g, beta: chunked_delta(low(q), low(k), low(v), g, beta, chunk)  # noqa: E731
    held = lambda q, k, v, g, beta: walk(q, k, v, g, jnp.minimum(beta, 1.0))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = pulled(lambda *z: literal(*(low(x).astype(jnp.float32) for x in z[:3]), *z[3:]))(
            q, k, v, g, beta)
    got, lost = pulled(walk)(q, k, v, g, beta), pulled(held)(q, k, v, g, beta)
    rel = lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / jnp.max(jnp.abs(b)))  # noqa: E731
    out = {name: (rel(a, w), rel(b, w)) for name, a, b, w in
           zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, lost, want)}
    forward, both = jax.jit(walk), pulled(walk)
    times = {}
    for name, fn in (("forward_us", forward), ("forward_and_backward_us", both)):
        jax.block_until_ready(fn(q, k, v, g, beta))
        t0 = time.perf_counter()
        for _ in range(repeats):
            done = fn(q, k, v, g, beta)
        jax.block_until_ready(done)
        times[name] = round((time.perf_counter() - t0) / repeats * 1e6, 1)
    return out, times


# The whole ungated latent mixer in bfloat16 against the mixer written out in
# float32 on the same weights, ||got - want|| / ||want||: its five projections
# round to bfloat16 (read in Pallas' interpreter on the CPU at 4 heads and 300
# tokens: 0.006; on the chip at the published widths: the leg prints it), and
# how far at least a mixer that lost the latent's norm lies (the input's RMS is
# 1.5 and the norm's weights spread 0.3 round 1, so that a lost norm is no
# rounding).
MIXER_REL = 0.03
MIXER_REL_WITHOUT_LATENT_NORM = 0.2
# The gates against the host's: float32 on both sides; without the scaling
# factor they are off by 1 - 1/2.448.
GATE_ABS = 1e-5


def _committed_spec(config: str, **over):
    """The spec of ``configs/<config>``'s torso, its family's ``spec_from_config``."""
    import importlib

    from ape_x_dqn_tpu.config import load_config

    here = os.path.dirname(os.path.abspath(__file__))
    cfg = load_config(os.path.join(here, "configs", config))
    family = importlib.import_module("ape_x_dqn_tpu.models." + cfg.network)
    return family.spec_from_config(
        dict({k: v for k, v in cfg.torso.items() if not k.startswith("_")}, **over))


def _kanana_spec(**over):
    return _committed_spec("config12_kanana2_q_ep8.json", **over)


def latent_mixer_against_plain(rows: int = 2, tokens: int = 1568, **over) -> dict:
    """``ling_hybrid.LatentAttention`` under the Kanana spec (no head gate),
    bfloat16 compute, against the mixer of ISSUE 56's section 1 written out in
    float32 on the same weights and input: within ``MIXER_REL``; and the same
    mixer written out without the latent's norm lies further than
    ``MIXER_REL_WITHOUT_LATENT_NORM`` from the program.  -> {"mixer": (with,
    without)}."""
    import math

    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.models.ling_hybrid import LatentAttention

    spec = _kanana_spec(**over)
    m, d, eps = spec.arg("latent"), spec.hidden_size, spec.norm_eps
    h, dn, dr, dv, r = m.heads, m.nope, m.rope, m.v, m.kv_rank
    mixer = LatentAttention(spec, "latent_attention", jnp.bfloat16, jnp.float32)
    ku, kp, kn = jax.random.split(jax.random.PRNGKey(56), 3)
    u = (1.5 * jax.random.normal(ku, (rows, tokens, d))).astype(jnp.bfloat16)
    params = jax.jit(mixer.init)(kp, u)["params"]
    assert "w_g" not in params, "kanana's latent mixer has no head gate"
    params = dict(params, kv_norm=1.0 + 0.3 * jax.random.normal(kn, (r,)))

    def turned(x):          # [B, T, n, R], pairs (2j, 2j + 1) by t theta^(-2j / R)
        ang = (jnp.arange(tokens, dtype=jnp.float32)[:, None]
               * m.theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)[None, :])
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1).reshape(x.shape)

    def plain(p, u, normed=True):
        with jax.default_matmul_precision("highest"):
            u = u.astype(jnp.float32)
            q = (u @ p["w_q"]).reshape(rows, tokens, h, dn + dr)
            down = u @ p["w_dkv"]
            c = down[..., :r]
            if normed:
                c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + eps) * p["kv_norm"]
            kv = (c @ p["w_ukv"]).reshape(rows, tokens, h, dn + dv)
            score = (jnp.einsum("bthd,bshd->bhts", q[..., :dn], kv[..., :dn])
                     + jnp.einsum("bthd,bsd->bhts", turned(q[..., dn:]),
                                  turned(down[..., None, r:])[:, :, 0])) / math.sqrt(dn + dr)
            prob = jax.nn.softmax(
                jnp.where(jnp.tril(jnp.ones((tokens, tokens), bool)), score, -jnp.inf), -1)
            a = jnp.einsum("bhts,bshd->bthd", prob, kv[..., dn:]).reshape(rows, tokens, h * dv)
            return a @ p["w_o"]

    got = jax.jit(lambda p, u: mixer.apply({"params": p}, u))(params, u).astype(jnp.float32)
    want = jax.jit(plain)(params, u)
    wrong = jax.jit(lambda p, u: plain(p, u, normed=False))(params, u)
    near = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    far = float(jnp.linalg.norm(got - wrong) / jnp.linalg.norm(got))
    assert near <= MIXER_REL, f"mixer: {near} from the mixer written out"
    assert far >= MIXER_REL_WITHOUT_LATENT_NORM, (
        f"mixer: {far}: the check would not see a lost latent norm")
    return {"mixer": (near, far)}


def gates_against_sorting(tokens: int = 12544, spec=None, **over) -> dict:
    """``expert_torso.route`` under ``spec`` (default the Kanana spec: 6 of 128
    sigmoid scores by ``score + bias``, gates the chosen scores over their
    sum, times 2.448) against a sort on the host: the same experts for every
    token, the gates within ``GATE_ABS``; the gates without the factor are
    not."""
    import jax
    import numpy as np

    from ape_x_dqn_tpu.models import expert_torso

    spec = spec or _kanana_spec(**over)
    outputs, k = spec.router_outputs, spec.num_experts_per_tok
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(7), (tokens, outputs)))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(8), (outputs,))
    chosen, gates = jax.jit(lambda s, b: expert_torso.route(s, b, spec))(scores, bias)
    s, b = np.asarray(scores, np.float32), np.asarray(bias, np.float32)
    want = np.argsort(-(s + b), axis=-1, kind="stable")[:, :k]
    taken = np.take_along_axis(s, want, -1)
    unscaled = taken / (taken.sum(-1, keepdims=True) + 1e-20)
    differing = int(np.sum(np.any(np.asarray(chosen) != want, axis=-1)))
    off = float(np.max(np.abs(np.asarray(gates) - unscaled * spec.routed_scaling_factor)))
    off_unscaled = float(np.max(np.abs(np.asarray(gates) - unscaled)))
    assert differing == 0, f"{differing} of {tokens} tokens choose other experts than sorting"
    assert off <= GATE_ABS, f"gates {off} from the host's"
    assert off_unscaled > 100 * GATE_ABS, "the check would not see gates without their factor"
    return {"tokens": tokens, "differing": differing, "gates_max_abs": off,
            "without_the_factor": off_unscaled}


def leg_kanana_kernels() -> None:
    for name, (near, far) in latent_kernels_against_plain(rows=2, heads=32).items():
        say(f"kanana_kernels: {name} {near:.5f} from plain attention at 32 heads (limit "
            f"{KERNEL_REL}), {far:.4f} from plain attention without the shared key "
            f"(at least {KERNEL_REL_WITHOUT_SHARED_KEY})")
    near, far = latent_mixer_against_plain()["mixer"]
    say(f"kanana_kernels: the ungated mixer {near:.5f} from the mixer written out in float32 "
        f"(limit {MIXER_REL}), {far:.4f} from one without the latent's norm (at least "
        f"{MIXER_REL_WITHOUT_LATENT_NORM})")
    say(f"kanana_kernels: route against sorting {gates_against_sorting()}")


def leg_kanana() -> None:
    steps = 12

    def inspect(pipe, final):
        check_run("kanana", pipe, final, steps)
        assert type(pipe.comps.network).__name__ == "KananaMoeQ"
        assert final["param_version"] >= 1, "kanana: nothing was published"
        attention, routing = final.get("attention") or {}, final.get("routing") or {}
        assert "scan" not in final and "delta" not in final, (
            f"kanana: recurrent counters without such layers: {final}")
        # batch 2, three forwards, six latent layers of 32 heads
        assert 0 < attention.get("blocks_visited_latent", 0) < attention.get(
            "blocks_total_latent", 0) and abs(attention.get(
                "pairs_in_mask_latent", 0) - 2 * 3 * 6 * (1568 * 1569 // 2)) <= 64, (
            f"kanana: no latent attention counters: {final}")
        assert routing.get("held_pairs", 0) > 0 and "groups_kept_hold_share" not in routing, (
            f"kanana: no routing counters: {final}")
        say(f"kanana: attention a step {attention}; routing a step {routing}; actors adopted "
            f"param_version {pipe.worker.param_version} of {final['param_version']}")

    _train_on_histories("kanana", "config12_kanana2_q_ep8.json", steps, inspect)


# The whole Mamba-2 mixer and the whole LatentMoE layer in bfloat16 against the
# same written out in float32 on the same weights and input, ||got - want|| /
# ||want||: their projections round to bfloat16 (read in Pallas' interpreter on
# the CPU at the toy widths: 0.01 and 0.01; on the chip at the held widths the
# leg prints them), and how far at least each lost mechanism lies.
NEMOTRON_REL = 0.05
NEMOTRON_REL_LOST = 0.15


def _nemotron_spec():
    return _committed_spec("config13_nemotron3s_q_ep32.json")


def _near_and_far(got, want, wrongs: dict, what: str) -> dict:
    import jax.numpy as jnp

    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))  # noqa: E731
    near = rel(got, want)
    assert near <= NEMOTRON_REL, f"{what}: {near} from {what} written out"
    far = {name: rel(got, wrong) for name, wrong in wrongs.items()}
    for name, v in far.items():
        assert v >= NEMOTRON_REL_LOST, f"{what}: {v}: the check would not see {name}"
    return {"near": near, **far}


def grouped_mixer_against_plain(rows: int = 2, tokens: int = 1568, spec=None) -> dict:
    """``granite_hybrid.Mamba2`` under the Nemotron spec (the held groups,
    chunked scan, the kernels of ``scan_layout``), bfloat16 compute, against
    the mixer of ISSUE 59's section 1 written out in float32 with the
    recurrence a token at a time: within ``NEMOTRON_REL``; the same written out
    with every head on group 0's ``B`` and ``C``, and with the norm over all
    held channels, each lie further than ``NEMOTRON_REL_LOST``."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.models.granite_hybrid import Mamba2

    spec = spec or _nemotron_spec()
    m, d, eps = spec.arg("mamba"), spec.hidden_size, spec.norm_eps
    heads, groups = m.share
    inner, n, k = heads * m.head_dim, m.state, m.conv
    mixer = Mamba2(spec, "mamba", jnp.bfloat16, jnp.float32)
    ku, kp, kn, kc = jax.random.split(jax.random.PRNGKey(59), 4)
    u = jax.random.normal(ku, (rows, tokens, d)).astype(jnp.bfloat16)
    params = jax.jit(mixer.init)(kp, u)["params"]
    # no skip and steps of about 0.3: what the layer writes is the state's readout, not ``D x``
    # (at the published initialisation, steps of 0.001-0.1, the state's part is a hundredth of it);
    # taps of 0.5 (the module draws them at 0.02 at these widths): the convolution's outputs are of
    # order one and the gated row's mean square stands over the norm's eps, or there is no norm to lose
    # and the last group's x three times the others', so that a group's mean square is its own
    last = jnp.arange(params["w_in"].shape[1])
    last = (last >= 2 * inner - inner // groups) & (last < 2 * inner)
    params = dict(params, norm=1.0 + 0.3 * jax.random.normal(kn, (inner,)),
                  D=jnp.zeros((heads,)), dt_bias=jnp.full((heads,), -1.0),
                  conv_kernel=0.5 * jax.random.normal(kc, params["conv_kernel"].shape),
                  w_in=jnp.where(last, 3.0, 1.0) * params["w_in"])

    def plain(p, u, shared=False, over=groups):
        with jax.default_matmul_precision("highest"):
            f32 = jnp.float32
            z, xbc, dt = jnp.split(u.astype(f32) @ p["w_in"], (inner, 2 * inner + 2 * groups * n), -1)
            padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
            xbc = jax.nn.silu(sum(padded[:, j:j + tokens] * p["conv_kernel"][:, j] for j in range(k))
                              + p["conv_bias"])
            x, b, c = jnp.split(xbc, (inner, inner + groups * n), -1)
            b, c = (v.reshape(rows, tokens, groups, n) for v in (b, c))
            if shared:
                b, c = (jnp.broadcast_to(v[:, :, :1], v.shape) for v in (b, c))
            b, c = (jnp.repeat(v, heads // groups, axis=2) for v in (b, c))        # [B, T, H, N]
            dt, a = jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"])
            x = x.reshape(rows, tokens, heads, m.head_dim)

            def step(state, token):
                xt, dtt, bt, ct = token
                state = (jnp.exp(dtt * a)[..., None, None] * state
                         + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
                return state, jnp.sum(state * ct[:, :, None, :], -1) + p["D"][:, None] * xt

            _, y = jax.lax.scan(step, jnp.zeros((rows, heads, m.head_dim, n), f32),
                                tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
            g = jnp.moveaxis(y, 0, 1).reshape(rows, tokens, inner) * jax.nn.silu(z)
            g = g.reshape(rows, tokens, over, inner // over)
            g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
            return (g.reshape(rows, tokens, inner) * p["norm"]) @ p["w_out"]

    got = jax.jit(lambda p, u: mixer.apply({"params": p}, u))(params, u).astype(jnp.float32)
    return _near_and_far(got, jax.jit(plain)(params, u), {
        "group 0's B and C on every head": jax.jit(lambda p, u: plain(p, u, shared=True))(params, u),
        "a norm over all channels": jax.jit(lambda p, u: plain(p, u, over=1))(params, u)},
        "the Mamba-2 mixer")


def latent_experts_against_plain(rows: int = 2, tokens: int = 1568, spec=None) -> dict:
    """``expert_torso.ExpertShare`` under the Nemotron spec (the router's 22 of
    512, the walk over the 16 held experts' pairs in the latent), bfloat16
    compute, against the layer written out in float32 over masks: within
    ``NEMOTRON_REL``; the same written out with ``silu`` experts, and with the
    router reading the latent, each lie further than ``NEMOTRON_REL_LOST``."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.models.expert_torso import ExpertShare

    spec = spec or _nemotron_spec()
    lo, hi = spec.experts_held
    layer = ExpertShare(spec, jnp.bfloat16, jnp.float32)
    ku, kp = jax.random.split(jax.random.PRNGKey(60))
    u = jax.random.normal(ku, (rows, tokens, spec.hidden_size)).astype(jnp.bfloat16)
    params = jax.jit(layer.init)(kp, u)["params"]

    def plain(p, u, rule="relu2", reads_latent=False):
        with jax.default_matmul_precision("highest"):
            u = u.astype(jnp.float32)
            v = u @ p["w_down"]
            scores = jax.nn.sigmoid(v @ p["router"][:v.shape[-1]] if reads_latent else u @ p["router"])
            _, chosen = jax.lax.top_k(scores + p["expert_bias"], spec.num_experts_per_tok)
            gates = jnp.take_along_axis(scores, chosen, -1)
            gates = spec.routed_scaling_factor * gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)

            def one(y, e_w):
                e, w1, w2 = e_w
                h = v @ w1
                a = jax.nn.silu(h) if rule == "silu" else jnp.square(jax.nn.relu(h))
                return y + jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)[..., None] * (a @ w2), None

            y, _ = jax.lax.scan(one, jnp.zeros_like(v), (jnp.arange(lo, hi), p["w1"], p["w2"]))
            return y @ p["w_up"]

    got = jax.jit(lambda p, u: layer.apply({"params": p}, u))(params, u).astype(jnp.float32)
    return _near_and_far(got, jax.jit(plain)(params, u), {
        "silu experts": jax.jit(lambda p, u: plain(p, u, rule="silu"))(params, u),
        "a router that reads the latent": jax.jit(lambda p, u: plain(p, u, reads_latent=True))(params, u)},
        "the LatentMoE layer")


def leg_nemotron_kernels() -> None:
    spec = _nemotron_spec()
    say(f"nemotron_kernels: the Mamba-2 mixer from the mixer written out in float32 "
        f"(near: limit {NEMOTRON_REL}; each lost mechanism: at least {NEMOTRON_REL_LOST}) "
        f"{grouped_mixer_against_plain(spec=spec)}")
    say(f"nemotron_kernels: the LatentMoE layer from the layer written out in float32 "
        f"{latent_experts_against_plain(spec=spec)}")
    say(f"nemotron_kernels: route against sorting {gates_against_sorting(spec=spec)}")


def leg_nemotron() -> None:
    steps = 12

    def inspect(pipe, final):
        check_run("nemotron", pipe, final, steps)
        assert type(pipe.comps.network).__name__ == "NemotronHQ"
        assert final["param_version"] >= 1, "nemotron: nothing was published"
        attention, routing, scan = (final.get(k) or {} for k in ("attention", "routing", "scan"))
        assert "delta" not in final, f"nemotron: delta-rule counters without such layers: {final}"
        # batch 2, three forwards: five Mamba-2 layers of 13 chunks, one attention layer
        assert scan.get("chunks", 0) == 2 * 3 * 5 * 13 and scan.get("tokens_padded", 0) == 2 * 3 * 5 * 1664, (
            f"nemotron: no scan counters: {final}")
        assert abs(attention.get("pairs_in_mask_full", 0) - 2 * 3 * (1568 * 1569 // 2)) <= 64, (
            f"nemotron: no attention counters: {final}")
        assert routing.get("held_pairs", 0) > 0 and routing.get("rows_walked", 0) >= routing["held_pairs"], (
            f"nemotron: no routing counters: {final}")
        say(f"nemotron: scan a step {scan}; attention a step {attention}; routing a step {routing}; "
            f"actors adopted param_version {pipe.worker.param_version} of {final['param_version']}")

    _train_on_histories("nemotron", "config13_nemotron3s_q_ep32.json", steps, inspect)


def leg_olmo_kernels() -> None:
    from ape_x_dqn_tpu.utils import profiling

    for form, sizes in (("scalar", {}), ("per_channel", dict(per_channel=True, heads=16, kw=128, vw=128))):
        near, times = walk_against_the_recurrence(**sizes)
        for name, (rel, lost) in near.items():
            say(f"olmo_kernels: {form} {name} {rel:.5f} from the token-by-token recurrence (limit "
                f"{OLMO_WALK_REL}), {lost:.4f} with beta held to 1")
            assert rel <= OLMO_WALK_REL, f"the {form} walk's {name} is {rel} from the recurrence"
        assert near["o"][1] > 3 * OLMO_WALK_REL, "a walk with beta held to 1 passes the limit"
        say(f"olmo_kernels: the {form} walk, 2 rows, chunk 64, host clock: {times}")
    paths = sorted({(walk["path"], walk["inverse"]) for walk in profiling.launch.attrs_of("scan_path")})
    say(f"olmo_kernels: scan_path {paths}")


def leg_ling_kernels() -> None:
    for name, (near, far) in latent_kernels_against_plain().items():
        say(f"ling_kernels: {name} {near:.5f} from plain attention (limit {KERNEL_REL}), "
            f"{far:.4f} from plain attention without the shared key "
            f"(at least {KERNEL_REL_WITHOUT_SHARED_KEY})")
    say(f"ling_kernels: route against sorting {route_against_sorting()}")
    for row in choice_against_sorting_on_the_chip():
        say(f"ling_kernels: the choice against the sort on the chip, bit for bit: {row}")
    for row in combine_against_whole_rows_on_the_chip():
        say(f"ling_kernels: the walk's combine against one scatter-add of whole rows: {row}")


def main() -> int:
    t_start = time.perf_counter()
    try:
        from ape_x_dqn_tpu.utils.compile_cache import (
            cache_dir,
            enable_compile_cache,
        )
    except ImportError as e:
        print(f"chip_smoke: the repo is not here ({e})", file=sys.stderr)
        return 2
    import jax

    from ape_x_dqn_tpu.utils import profiling

    with profiling.launch.span("backend"):  # the chip's start-up
        backend = jax.default_backend()
    if backend != "tpu":
        print("chip_smoke: needs a TPU, jax's default backend is "
              f"{backend!r}; nothing compiled, nothing run",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    import importlib.metadata as md

    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    entries_before = cache_entries(cache_dir())
    say(f"device={device} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={md.version('libtpu')}")
    say(f"compile cache: {cache_dir()} entries_before={entries_before}")

    from ape_x_dqn_tpu.replay.native import native_available, native_error
    from ape_x_dqn_tpu.replay.native_dedup import (
        native_dedup_available,
        native_dedup_error,
    )

    failed = []
    if not native_available():
        failed.append(f"native sum_tree core: {native_error()}")
    if not native_dedup_available():
        failed.append(f"native replay_core: {native_dedup_error()}")
    if not failed:
        say("native cores: sum_tree and replay_core built and loaded")

    legs = [("ring_footprint", ring_footprint), ("thread", leg_thread),
            ("process", leg_process), ("serving", leg_serving)]
    if len(devs) >= 4:
        legs.append(("dp4", leg_dp4))
    if "--lfm2moe" in sys.argv[1:]:
        legs = [("lfm2moe", leg_lfm2moe)]
    if "--laguna" in sys.argv[1:]:
        legs = [("laguna", leg_laguna)]
    if "--granite" in sys.argv[1:]:
        legs = [("granite", leg_granite)]
    if "--solar" in sys.argv[1:]:
        legs = [("solar", leg_solar)]
    if "--ling" in sys.argv[1:]:
        legs = [("ling_kernels", leg_ling_kernels), ("ling", leg_ling)]
    if "--ling-kernels" in sys.argv[1:]:
        legs = [("ling_kernels", leg_ling_kernels)]
    if "--kanana" in sys.argv[1:]:
        legs = [("kanana_kernels", leg_kanana_kernels), ("kanana", leg_kanana)]
    if "--kanana-kernels" in sys.argv[1:]:
        legs = [("kanana_kernels", leg_kanana_kernels)]
    if "--nemotron" in sys.argv[1:]:
        legs = [("nemotron_kernels", leg_nemotron_kernels), ("nemotron", leg_nemotron)]
    if "--nemotron-kernels" in sys.argv[1:]:
        legs = [("nemotron_kernels", leg_nemotron_kernels)]
    if "--olmo-kernels" in sys.argv[1:]:
        legs = [("olmo_kernels", leg_olmo_kernels)]
    if "--first-conv" in sys.argv[1:]:
        legs = [("first_conv", leg_first_conv)]
    if "--fetch" in sys.argv[1:]:
        legs = [("fetch", leg_fetch)]
    for name, fn in legs:
        t0 = time.perf_counter()
        say(f"leg {name}: starts with bytes_in_use per device "
            f"{[d.memory_stats()['bytes_in_use'] for d in devs]}")
        ok = True
        try:
            fn()
        except Exception:  # noqa: BLE001 — reported, and the run fails
            traceback.print_exc()
            failed.append(f"leg {name}")
            ok = False
        gc.collect()
        say(f"leg {name}: wall {time.perf_counter() - t0:.1f} s"
            + ("" if ok else " FAILED"))
    if len(devs) < 4:
        say(f"leg dp4: DID NOT RUN — needs >= 4 devices, jax found {len(devs)}")

    import multiprocessing as mp

    for child in mp.active_children():
        failed.append(f"child process {child.pid} left running")
        child.kill()
    say(f"compile cache: entries_before={entries_before} "
        f"entries_after={cache_entries(cache_dir())}")
    say(f"total wall {time.perf_counter() - t_start:.1f} s")
    if failed:
        print(f"chip_smoke: FAILED: {failed}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

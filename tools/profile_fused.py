"""Subtractive profile of the fused learner step on the real chip.

The breakdown is measured by *ablation*: build K-step scan variants of the
fused program with trailing stages deleted, time each with a host read of a
value that depends on every step, and difference them:

    noop scan            -> scan + dispatch floor
    + sampler            -> two-level inverse-CDF cost
    + batch gather       -> HBM gather of 32 (obs, next_obs) rows
    + forward            -> online (2B) + target (B) forwards
    + backward           -> grad pass
    + optimizer          -> RMSProp traffic (the HBM suspect)
    + restamp            -> priority scatter
    == full fused step

Every variant's outputs are threaded into a scalar the host reads, so XLA
cannot dead-code-eliminate the stage under test.  Writes a markdown report
(``--out``, default ``profiles/profile_fused.md`` — gitignored).

Usage:  python tools/profile_fused.py [--steps-per-call 1024] [--capacity 100000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure_roofline(repeats: int = 5, size_elems: int = 1 << 28,
                     scan_len: int = 8) -> dict:
    """Effective HBM bandwidth on THIS chip, slope-timed.

    Two kernels over a 1 GiB float32 array inside a ``lax.scan`` (so the
    compiler cannot batch or elide iterations — each consumes the last):

      * stream:  x = x * c       (reads + writes 4·N bytes per iteration)
      * reduce:  s += sum(x)·c   (reads 4·N bytes per iteration)

    GB/s = bytes/iteration · scan_len / slope-timed seconds-per-call —
    the number the fused step's per-step HBM-bytes floor must be divided
    by (replacing the datasheet figure the round-3 verdict flagged as
    asserted-not-measured).
    """
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.utils.profiling import slope_timing

    n = size_elems
    gib = n * 4 / (1 << 30)

    @jax.jit
    def stream(x, s):
        def body(carry, _):
            x, s = carry
            x = x * jnp.float32(1.0000001)
            return (x, s + x[0]), None
        (x, s), _ = jax.lax.scan(body, (x, s), None, length=scan_len)
        return x, s

    @jax.jit
    def reduce(x, s):
        def body(s, _):
            # The reduction's OPERAND depends on the carry (a dynamic
            # slice offset computed from s), so loop-invariant code motion
            # cannot hoist the 1 GiB sum out of the scan — summing a
            # closed-over x (even scaled by the carry afterwards) would
            # let XLA compute it once and report scan_len x the real
            # bandwidth.
            off = jnp.abs(s.astype(jnp.int32)) & 7
            window = jax.lax.dynamic_slice(x, (off,), (n - 8,))
            return jnp.sum(window) * jnp.float32(1e-7) \
                + s * jnp.float32(1e-9), None
        s, _ = jax.lax.scan(body, s, None, length=scan_len)
        return x, s

    env = {"x": jnp.ones((n,), jnp.float32), "s": jnp.zeros(())}

    def run(prog):
        def fn():
            env["x"], env["s"] = prog(env["x"], env["s"])
        return fn

    def force():
        _ = float(np.asarray(env["s"]))

    secs = slope_timing(
        {"stream": run(stream), "reduce": run(reduce)},
        force, n_small=2, n_big=8, repeats=repeats,
    )
    out = {
        "array_gib": round(gib, 2),
        "scan_len": scan_len,
        # stream moves read+write = 2 passes; reduce reads 1 pass.
        "stream_gbps": round(2 * gib * scan_len / secs["stream"], 1),
        "reduce_gbps": round(gib * scan_len / secs["reduce"], 1),
        "seconds_per_call": {k: round(v, 4) for k, v in secs.items()},
    }
    del env["x"]
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--steps-per-call", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--capacity", type=int, default=100_000)
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--out", default="profiles/profile_fused.md")
    p.add_argument("--try-trace", action="store_true",
                   help="also attempt a jax.profiler trace into ./profiles/")
    p.add_argument("--skip-roofline", action="store_true",
                   help="skip the HBM bandwidth microbench (~30s)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step,
        init_train_state,
        make_optimizer,
    )
    from ape_x_dqn_tpu.models.dueling import build_network
    from ape_x_dqn_tpu.ops import losses
    from ape_x_dqn_tpu.replay.device import (
        device_replay_add,
        device_replay_sample,
        device_replay_update_priorities,
        init_device_replay,
    )
    from ape_x_dqn_tpu.utils.profiling import slope_timing, trace

    B, K, C = args.batch_size, args.steps_per_call, args.capacity
    obs_shape, A = (84, 84, 1), 4
    net = build_network("conv", A)
    opt = make_optimizer("rmsprop", max_grad_norm=None,
                         second_moment_dtype=jnp.bfloat16)
    step_fn = build_train_step(net, opt, sync_in_step=False, jit=False)

    rng = np.random.default_rng(0)
    replay = init_device_replay(C, obs_shape)
    add = jax.jit(device_replay_add, donate_argnums=(0,))
    from ape_x_dqn_tpu.types import NStepTransition

    M = 2048
    chunk = jax.device_put(NStepTransition(
        obs=jnp.asarray(rng.integers(0, 255, (M, *obs_shape), dtype=np.uint8)),
        action=jnp.asarray(rng.integers(0, A, (M,), dtype=np.int32)),
        reward=jnp.asarray(rng.normal(size=(M,)).astype(np.float32)),
        discount=jnp.full((M,), 0.97, jnp.float32),
        next_obs=jnp.asarray(rng.integers(0, 255, (M, *obs_shape), dtype=np.uint8)),
    ))
    for _ in range(C // M + 1):
        replay = add(replay, chunk, jnp.ones((M,), jnp.float32))
    state = init_train_state(
        net, opt, jax.random.PRNGKey(0),
        jnp.zeros((1, *obs_shape), jnp.uint8), target_dtype=jnp.bfloat16,
    )

    def loss_only(t_state, batch):
        t = batch.transition
        q_both = net.apply(
            t_state.params, jnp.concatenate([t.obs, t.next_obs], axis=0)
        )[2]
        q_values, q_next_online = q_both[:B], q_both[B:]
        q_next_target = net.apply(t_state.target_params, t.next_obs)[2]
        targets = losses.double_q_target(
            q_next_online, q_next_target, t.reward, t.discount
        )
        delta = losses.td_error(q_values, t.action, targets)
        return losses.td_loss(delta, batch.is_weights, kind="huber")

    # --- scan variants.  Each body returns a scalar metric that depends on
    # every stage it contains (anti-DCE), and each program has signature
    # (state, replay, rng) -> (state, replay, metric_sum).
    def make_scan(body):
        def prog(t_state, r_state, rng_key):
            def wrapped(carry, step_rng):
                t, r = carry
                t, r, m = body(t, r, step_rng)
                return (t, r), m
            rngs = jax.random.split(rng_key, K)
            (t_state, r_state), ms = jax.lax.scan(
                wrapped, (t_state, r_state), rngs
            )
            return t_state, r_state, jnp.sum(ms)
        return jax.jit(prog, donate_argnums=(0, 1))

    def b_noop(t, r, k):
        return t, r, jax.random.uniform(k, ())

    def b_sampler(t, r, k):
        # Sampler indices + IS weights, but no row gather of frames.
        from ape_x_dqn_tpu.ops.pallas.sampling import sample_indices
        total = jnp.sum(r.mass)
        u = jax.random.uniform(k, (B,))
        targets = (jnp.arange(B, dtype=jnp.float32) + u) * (total / B)
        idx = sample_indices(r.mass, jnp.minimum(targets, total * (1 - 1e-7)))
        return t, r, jnp.sum(idx) + jnp.sum(r.mass[idx])

    def b_gather(t, r, k):
        batch = device_replay_sample(r, k, B, 0.4)
        m = (jnp.sum(batch.transition.obs.astype(jnp.float32))
             + jnp.sum(batch.transition.next_obs.astype(jnp.float32))
             + jnp.sum(batch.is_weights))
        return t, r, m

    def b_forward(t, r, k):
        batch = device_replay_sample(r, k, B, 0.4)
        return t, r, loss_only(t, batch)

    def b_backward(t, r, k):
        batch = device_replay_sample(r, k, B, 0.4)
        loss, grads = jax.value_and_grad(
            lambda p: loss_only(t.replace(params=p), batch)
        )(t.params)
        # One reduction pass keeps all grads alive (adds ~one grad read).
        gsum = sum(jnp.sum(g) for g in jax.tree_util.tree_leaves(grads))
        return t, r, loss + gsum * 1e-12

    def b_train(t, r, k):
        batch = device_replay_sample(r, k, B, 0.4)
        t, metrics = step_fn(t, batch)
        return t, r, metrics.loss

    def b_full(t, r, k):
        batch = device_replay_sample(r, k, B, 0.4)
        t, metrics = step_fn(t, batch)
        r = device_replay_update_priorities(r, batch.indices, metrics.priorities)
        return t, r, metrics.loss

    stages = [
        ("noop", b_noop), ("sampler", b_sampler), ("gather", b_gather),
        ("forward", b_forward), ("backward", b_backward),
        ("train", b_train), ("full", b_full),
    ]
    progs = {name: make_scan(body) for name, body in stages}

    env = {"state": state, "replay": replay, "key": jax.random.PRNGKey(1)}

    def run_variant(name):
        def fn():
            env["key"], sub = jax.random.split(env["key"])
            env["state"], env["replay"], env["m"] = progs[name](
                env["state"], env["replay"], sub
            )
        return fn

    def force():
        _ = float(np.asarray(env["m"]))

    t0 = time.perf_counter()
    seconds = slope_timing(
        {name: run_variant(name) for name, _ in stages},
        force, n_small=2, n_big=8, repeats=args.repeats,
    )
    wall = time.perf_counter() - t0

    us = {name: s / K * 1e6 for name, s in seconds.items()}
    deltas = {
        "scan+dispatch floor": us["noop"],
        "sampler (two-level CDF)": us["sampler"] - us["noop"],
        "batch gather (rows from ring)": us["gather"] - us["sampler"],
        "forward (online 2B + target B)": us["forward"] - us["gather"],
        "backward (+1 grad-read pass)": us["backward"] - us["forward"],
        "optimizer (RMSProp update)": us["train"] - us["backward"],
        "priority restamp (scatter)": us["full"] - us["train"],
    }

    roofline = None
    if not args.skip_roofline:
        roofline = measure_roofline(repeats=args.repeats)

    trace_note = "not attempted"
    if args.try_trace:
        os.makedirs("profiles", exist_ok=True)
        with trace("profiles"):
            run_variant("full")()
            force()
        trace_note = "written to profiles/ (TensorBoard)"

    dev = jax.devices()[0].device_kind
    lines = [
        "# PROFILE — fused learner step breakdown (subtractive ablation)",
        "",
        f"Chip: **{dev}** · batch {B} · K={K} steps/dispatch · replay C={C:,}",
        f"· repeats={args.repeats} (min) · measured {time.strftime('%Y-%m-%d')}"
        f" · total wall {wall:.0f}s",
        "",
        "Method: K-step `lax.scan` variants with trailing stages deleted,",
        "each output data-threaded to a host-read scalar (anti-DCE) that",
        "forces the calls, and **slope timing**: each variant is timed as",
        "the marginal cost of chained calls (T(8 calls) − T(2 calls)) / 6,",
        "which cancels whatever fixed cost the sync itself carries.",
        "Stage cost = difference of adjacent variants.",
        "`tools/profile_fused.py` regenerates this file.",
        "",
        "| cumulative variant | µs/step |",
        "|---|---|",
    ]
    for name, _ in stages:
        lines.append(f"| {name} | {us[name]:.1f} |")
    lines += ["", "| stage (delta) | µs/step |", "|---|---|"]
    for k, v in deltas.items():
        lines.append(f"| {k} | {v:.1f} |")
    if roofline is not None:
        # Bold, NOT a markdown heading: regeneration preserves everything
        # from the first heading (hand-written appendices) — a generated
        # heading here would get double-preserved on the next run.
        lines += [
            "",
            "**Measured HBM roofline (this chip, slope-timed):**",
            "",
            f"| kernel ({roofline['array_gib']} GiB f32, scan×"
            f"{roofline['scan_len']}) | effective GB/s |",
            "|---|---|",
            f"| stream (read+write) | {roofline['stream_gbps']} |",
            f"| reduce (read-only) | {roofline['reduce_gbps']} |",
            "",
            "The per-step byte floor below divides by THESE numbers, not "
            "the datasheet figure.",
        ]
    lines += [
        "",
        f"jax.profiler trace: {trace_note}",
        "",
        "Raw seconds-per-variant: `" + json.dumps(
            {k: round(v, 4) for k, v in seconds.items()}) + "`",
        "",
    ]
    # Preserve hand-written analysis sections (everything from the first
    # "## " heading on): this tool owns only the generated ablation block
    # above them — a rerun must not wipe the round-notes appendices.
    preserved = ""
    if os.path.exists(args.out):
        import re

        old = open(args.out).read()
        # Any heading level counts as "hand-written starts here" — the
        # generated block's own "# PROFILE" title is line 1, so skip it.
        m = re.search(r"\n#{1,6} ", old)
        if m:
            preserved = old[m.start():]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + preserved)
    print(json.dumps({"us_per_step": {k: round(v, 1) for k, v in us.items()},
                      "deltas": {k: round(v, 1) for k, v in deltas.items()}}))


if __name__ == "__main__":
    main()

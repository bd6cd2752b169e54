"""Traffic driver ``learner_feed_by_name``: ``learner_feed``'s traffic, value
for value, for a configuration that names its network, its reference and its
operation count (``network``, ``reference``, ``ops_count``).

What takes any network is imported from ``drivers/learner_feed.py``,
``correctness.py`` and ``program.py`` unedited: the ring and chunk makers,
the per-call dispatches, ``_pump`` and ``_force``, ``build_fused``,
``seed_key``, ``verdict``, ``rel_l2`` and the ring's reference.  What is
bound to the dueling network there is copied here with the network, the
reference module and the parameter map as arguments:
``build_learner``, ``Feed.__init__``, ``run``, ``check_shots``,
``make_inputs``, ``state_from_inputs``, ``reference_run``, ``sampled_rows``
(with its walk replaced: see there) and ``compare`` (leaf by leaf and on the
host: the flat copies of 455 M parameters do not fit beside the reference on
the chip).  PERF.md lists them, for the ``benchmark`` issue that folds the two
drivers into one.

Two things are this driver's own.  The network is built from the
configuration file through the program's ``build_network``, and the timed
state is ``program.init_state``'s as in ``learner_feed`` (run as one compiled
program: op by op, 455 M parameters initialise for a minute).  And the fused
call's routing counters are read: the pairs routed to held experts and their
largest and mean load go to the per-layer readers.

    python3 benchmark/drivers/learner_feed_by_name.py --config lfm2moe_q_ep8 --seeds 10

prints the readings the limits are set from, as ``check_control.py`` does
for the dueling configurations.
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import program  # noqa: E402
import timing  # noqa: E402
from correctness import EXACT, MASS_RTOL, NU0, STRATUM_SLACK, rel_l2, ring_ref  # noqa: E402
from drivers import learner_feed as base  # noqa: E402
from spans import Spans  # noqa: E402

ROUTING_KEYS = ("held_pairs", "load_max", "load_mean")


def reference_of(cfg: dict):
    """The plain reference the configuration names, ``reference/<name>.py``."""
    return importlib.import_module("reference." + cfg["reference"])


def network_kwargs(cfg: dict) -> dict:
    """What ``build_network`` takes beside kind and actions: the stem's and
    head's widths, and for a torso of blocks the configuration itself, whose
    keys are the published ones."""
    prec = cfg["precision"]
    kw = dict(channels=tuple(cfg["channels"]), hidden=cfg["hidden"],
              compute_dtype=jnp.dtype(prec["compute"]), param_dtype=jnp.dtype(prec["params"]))
    if "layer_types" in cfg:
        kw["torso"] = cfg
    return kw


def build_learner(cfg: dict):
    """``program.build_learner`` with the network taken by name."""
    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step, make_optimizer, with_float32_master,
    )
    from ape_x_dqn_tpu.models.dueling import build_network

    prec = cfg["precision"]
    net = build_network(cfg["network"], cfg["num_actions"], **network_kwargs(cfg))
    opt = make_optimizer(
        cfg["optimizer"], learning_rate=cfg["learning_rate"],
        rmsprop_decay=cfg["rmsprop_decay"], rmsprop_eps=cfg["rmsprop_eps"],
        max_grad_norm=cfg["max_grad_norm"],
        second_moment_dtype=jnp.dtype(prec["second_moment"]),
    )
    if prec["params"] == "bfloat16":
        opt = with_float32_master(opt)
    step_fn = build_train_step(
        net, opt, loss_kind=cfg["loss"], sync_in_step=False, jit=False,
        grad_reduce_axis=program.AXIS if int(cfg.get("data_parallel", 1)) > 1 else None,
    )
    return net, opt, step_fn


def warm_second_moment(opt_state, value: float = NU0):
    """``opt_state`` with every second-moment leaf at ``value``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.full_like(x, value)
        if any("nu" in str(p) for p in path) else x, opt_state)


class Feed(base.Feed):
    """``learner_feed.Feed`` with the learner built by name.  The ring is
    made before the train state, so that the fill's temporaries and the
    state are never held together."""

    def __init__(self, cfg: dict, traffic: dict, key, spans, state=None):
        self.cfg, self.spans = cfg, spans
        self.mesh = program.make_mesh(cfg)
        self.n = int(cfg.get("data_parallel", 1))
        self.net, self.opt, self.step_fn = build_learner(cfg)
        self.fused = program.build_fused(cfg, self.step_fn, self.mesh)
        self.fused_name = program.program_name(self.fused)
        k_state, k_ring, k_chunks, k_run = jax.random.split(key, 4)
        self.key = k_run
        self.beta = float(traffic["beta"])
        self.resident = int(traffic["resident_chunks"])
        self.chunk_priority = tuple(traffic["chunk_priority"])
        self.rows = int(cfg["ingest_block"])
        if self.rows % self.n or cfg["replay_capacity"] % self.n:
            raise ValueError("ingest_block and replay_capacity must divide by data_parallel")
        self._compiled = None
        self.routing = []
        t0 = time.perf_counter()
        getattr(self, "_setup_" + cfg["replay_layout"])(k_ring, k_chunks)
        jax.block_until_ready(self.replay)
        t1 = time.perf_counter()
        if state is None:
            state = self._seeded_state(k_state)
            print(f"[bench] set-up: ring and chunks {t1 - t0:.1f} s, train state "
                  f"{time.perf_counter() - t1:.1f} s", flush=True)
        self.state = state

    def _seeded_state(self, key):
        """``program.init_state``, as ``learner_feed`` seeds its state, run
        as one compiled program."""
        if self.mesh is not None:
            raise ValueError("this driver seeds its state on one chip")
        return jax.jit(lambda k: program.init_state(
            self.cfg, self.net, self.opt, k, None))(key)

    def call(self, i: int):
        metrics = super().call(i)
        if getattr(metrics, "routing", None) is not None:
            self.routing.append(metrics.routing)
        return metrics

    def routing_totals(self, calls: slice) -> dict:
        """{key: sum over the steps of the calls in ``calls``}."""
        taken = self.routing[calls]
        return {k: float(sum(np.sum(np.asarray(r[k], np.float64)) for r in taken))
                for k in ROUTING_KEYS} if taken else {}


def run(ctx) -> dict:
    """``learner_feed.run`` on this file's ``Feed``, with the routing
    counters of the window's calls."""
    cfg, traffic, spans = ctx.cell.config, ctx.cell.traffic, ctx.spans
    feed = Feed(cfg, traffic, program.seed_key(ctx.seed), spans)
    k_steps, batch = cfg["steps_per_call"], cfg["batch_size"]
    in_flight = int(traffic["in_flight"])

    warm = base._pump(feed, spans, 0, 1, lambda c: len(c) >= int(traffic["warmup_calls"]))
    compiles_before = ctx.compile_events()
    start = warm["completions"][-1]
    ctx.mark_setup_done(start)
    win = base._pump(feed, spans, warm["next_call"], in_flight,
                     lambda c: timing.window_done(start, c, ctx.seconds))
    compiles_in_window = ctx.compile_events() - compiles_before
    interval = timing.call_boundary_interval(start, win["completions"], ctx.seconds)
    calls_done = win["next_call"]
    routed = feed.routing_totals(slice(warm["next_call"], calls_done))
    if feed.routing:
        print("[bench] routing by call (pairs on held experts a step, largest over mean "
              "load): " + ", ".join(
                  f"{np.mean(np.asarray(r['held_pairs'])):.0f} "
                  f"{np.sum(np.asarray(r['load_max'])) / np.sum(np.asarray(r['load_mean'])):.2f}"
                  for r in feed.routing[:calls_done]), flush=True)
    steps = (calls_done - warm["next_call"]) * k_steps

    obs = dict(
        end_to_end={"learn_samples_per_s": interval.rate(k_steps * batch)},
        attempted=warm["attempted"] + win["attempted"],
        failed=warm["failed"] + win["failed"],
        window=(start, start + interval.elapsed_s),
        counters=dict(calls_in_window=interval.calls, steps_per_call=k_steps,
                      batch_size=batch, compiles_in_window=compiles_in_window,
                      last_loss=win["last_loss"],
                      **{k + "_per_step": v / steps for k, v in routed.items()}),
        fused_program=feed.fused_name,
    )

    if ctx.trace:
        mean_call = interval.elapsed_s / interval.calls
        n_trace = max(in_flight + 1, int(np.ceil(float(traffic["trace_seconds"]) / mean_call)))
        with ctx.profiler():
            tr = base._pump(feed, spans, calls_done, in_flight, lambda c: len(c) >= n_trace)
        traced = feed.routing_totals(slice(calls_done, tr["next_call"]))
        obs["counters"].update({"traced_" + k + "_per_step":
                                v / ((tr["next_call"] - calls_done) * k_steps)
                                for k, v in traced.items()})
        calls_done = tr["next_call"]
        obs["attempted"] += tr["attempted"]
        obs["failed"] += tr["failed"]
        obs["counters"]["traced_calls"] = tr["attempted"]

    step = int(jax.device_get(feed.state.step))
    obs["exact_checks"] = [
        ("step counter", step, calls_done * k_steps),
        ("compilations inside the window", compiles_in_window, 0),
        ("calls that raised or lost a finite loss", obs["failed"], 0),
    ]
    obs["program_temp_bytes"] = feed.temp_bytes()
    seed = ctx.seed
    obs["check"] = lambda: program_numbers(
        cfg, float(traffic["beta"]), *check_shots(cfg, traffic, seed))[:2]
    return obs


# ------------------------------------------- the comparison, by reference name

def make_inputs(seed_key, cfg: dict) -> dict:
    """``correctness.make_inputs`` with the reference taken by name: weights
    and target weights from the seed.  The target is held in the type the
    configuration stores it in (the same numbers, half the bytes) and the
    second moment is the one number NU0."""
    ref = reference_of(cfg)
    kw, kt = jax.random.split(seed_key)
    weights = jax.jit(lambda k: ref.make_weights(k, cfg))(kw)
    tdtype = jnp.dtype(cfg["precision"]["target_params"])
    # The router's weights and the bias stay float32 in the target network
    # (the program's ``float32_leaves``), and the bias is a buffer: copied.
    def target_leaf(i, path, w):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            return w
        noisy = w + 0.1 * jnp.std(w) * jax.random.normal(jax.random.fold_in(kt, i), w.shape, w.dtype)
        return noisy if "router" in name else noisy.astype(tdtype)

    paths, tree = jax.tree_util.tree_flatten_with_path(weights)
    target = jax.tree_util.tree_unflatten(
        tree, [target_leaf(i, path, w) for i, (path, w) in enumerate(paths)])
    return {"weights": weights, "target": target, "nu0": NU0}


def state_from_inputs(cfg: dict, opt, inputs: dict, mesh):
    """``program.state_from_inputs`` with the parameter map taken from the
    reference the configuration names."""
    from ape_x_dqn_tpu.types import TrainState

    ref, prec = reference_of(cfg), cfg["precision"]
    own = lambda t: jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), t)  # noqa: E731
    params = own(ref.to_program_params(inputs["weights"], cfg, jnp.dtype(prec["params"])))
    opt_state = warm_second_moment(opt.init(params), inputs["nu0"])
    state = TrainState(
        params=params,
        target_params=own(ref.to_program_params(
            inputs["target"], cfg, jnp.dtype(prec["target_params"]))),
        opt_state=opt_state, step=jnp.zeros((), jnp.int32), rng=jax.random.PRNGKey(0))
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        state = jax.device_put(jax.device_get(state), NamedSharding(mesh, P()))
    return state


def check_shots(cfg: dict, traffic: dict, seed: int) -> tuple:
    """``learner_feed.check_shots`` on this file's pieces.  The program's
    final parameters come back as host arrays in the reference's names, and
    its state is let go before the reference runs."""
    t0 = time.perf_counter()
    spec, n = traffic["check"], int(cfg.get("data_parallel", 1))
    small = dict(cfg, steps_per_call=1, replay_capacity=spec["ring_rows_per_chip"] * n,
                 ingest_block=spec["ingest_rows_per_chip"] * n)
    k_inputs, k_feed = jax.random.split(
        jax.random.fold_in(program.seed_key(seed), 0xC0FFEE))
    inputs = make_inputs(k_inputs, small)
    net, opt, _ = build_learner(small)
    feed = Feed(small, dict(traffic, chunk_priority=spec["chunk_priority"]), k_feed, Spans(),
                state=state_from_inputs(small, opt, inputs, program.make_mesh(small)))
    inputs["weights"] = to_host(inputs["weights"])  # the chip holds one copy fewer
    shots = dict(rings=[feed.host_ring()], chunks=[], priorities=[])
    for i in range(int(spec["calls"])):
        metrics = feed.call(i)
        shots["chunks"].append(feed.host_chunk())
        shots["rings"].append(feed.host_ring())
        shots["priorities"].append(np.asarray(metrics.priorities).reshape(-1))
    shots["routing"] = feed.routing_totals(slice(0, None))
    shots["seconds"] = time.perf_counter() - t0
    shots["weights"] = to_host(reference_of(cfg).from_program_params(feed.state.params, small))
    return inputs, shots


def to_host(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), tree)


@functools.lru_cache(maxsize=None)
def _reference_program(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    ref = reference_of(cfg)
    return jax.jit(lambda w, t, v, b, r: ref.learner_step(w, t, v, b, cfg, precision, r),
                   donate_argnums=(0, 2))


def _reference_fn(cfg_json: str, precision: str):
    """The reference's step in ``precision``.  The stated precision and its
    control with e5m2 activations are one compiled program, told apart by a
    value (``learner_step``'s ``round_activations``)."""
    fp8 = precision == "fp8_activations"
    step = _reference_program(cfg_json, "stated" if fp8 else precision)
    return lambda w, t, v, b: step(w, t, v, b, jnp.asarray(fp8))


def reference_run(cfg: dict, beta: float, inputs: dict, shots: dict,
                  precision: str = "stated", row_shift: int = 0) -> dict:
    """``correctness.reference_run``'s loop with the reference taken by name;
    weights and second moment are donated from step to step, and each
    call's strata are read from the masses the program held (below)."""
    layout, alpha = cfg["replay_layout"], cfg["priority_exponent"]
    n = len(shots["rings"][0])
    rings = [{k: np.array(v) for k, v in shard.items()} for shard in shots["rings"][0]]
    counts = dict.fromkeys(EXACT, 0)
    step = _reference_fn(json.dumps(cfg, sort_keys=True), precision)
    second = jnp.dtype(cfg["precision"]["second_moment"])
    weights = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), inputs["weights"])
    nu = jax.tree_util.tree_map(
        lambda x: jnp.full(x.shape, inputs["nu0"], second).astype(jnp.float32), weights)
    priorities = []
    for call, chunks in enumerate(shots["chunks"]):
        before, after = shots["rings"][call], shots["rings"][call + 1]
        prio = shots["priorities"][call].reshape(n, -1)
        rows = []
        for d in range(n):
            ring = rings[d]
            # The strata of a call are those of the masses the program held
            # before it, not of the reference's own restamps.  Each of those
            # masses was held to the priority the program returned when it
            # was written, and each priority to the reference's by the
            # limits; but the two sides' priorities differ by 3-4% here, 512
            # restamped masses a call shift a stratum's edge by more than
            # the slack, and rows drawn rightly would then read as outside.
            ring["mass"] = np.array(before[d]["mass"])
            took = ring_ref.ingest(ring, chunks[d], layout, alpha)
            for f in ring_ref.DATA_FIELDS[layout] + ("cursor",) + \
                    (("fcount",) if layout == "dedup" else ()):
                counts["ring_rows_differing"] += int(np.sum(after[d][f] != ring[f]))
            cap = ring["mass"].shape[0]
            counts["ring_rows_differing"] += int(
                min(int(after[d]["count"]), cap) != min(ring["count"], cap))
            floor = 0.9 * float(chunks[d]["priority"].min()) ** alpha
            first, last = ring_ref.strata(ring["mass"], prio.shape[1], STRATUM_SLACK)
            got, bad = sampled_rows(before[d]["mass"], after[d]["mass"], ring["mass"],
                                    took, prio[d], alpha, floor, first)
            counts["masses_unexplained"] += bad
            counts["rows_outside_stratum"] += int(np.sum(
                (got < first) | (got > last) | (ring["mass"][got] <= 0)))
            rows.append(got)
        held = [min(r["count"], r["mass"].shape[0]) for r in rings]
        weights_is = ring_ref.importance_weights(
            [r["mass"] for r in rings], rows, held, beta)
        parts = [dict(ring_ref.gather(r, (g + row_shift) % r["mass"].shape[0], layout),
                      is_weights=w) for r, g, w in zip(rings, rows, weights_is)]
        batch = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        weights, nu, _delta, ref_prio, _loss = step(weights, inputs["target"], nu, batch)
        nu = jax.tree_util.tree_map(lambda x: x.astype(second).astype(jnp.float32), nu)
        ref_prio = np.asarray(ref_prio)
        priorities.append(ref_prio)
        for r, g, p in zip(rings, rows, ref_prio.reshape(n, -1)):
            r["mass"][g] = p.astype(np.float64) ** alpha
    return {"counts": counts, "priorities": priorities, "weights": to_host(weights)}


def sampled_rows(old_mass, new_mass, ref_mass, took, priorities, alpha, floor, first):
    """``correctness.sampled_rows``: (rows, masses unexplained), which slot
    each of the batch's rows was drawn from, read from the masses that
    moved.  That function walks rows and moved slots together and takes a
    slot for a second row as soon as its mass fits that row's priority; two
    neighbouring rows whose priorities agree to 1e-4 (one call in about 30
    here: seeds 2800031677 and 2800023758 on the chip, 13 of 3,000 drawn
    batches on the host) then share a slot they did not share, and every
    later row is read one slot off.  Here the rows are laid on the slots by
    the assignment that leaves the fewest masses unexplained: in order,
    every slot taken, a slot taken again only where it reaches into the next
    row's stratum (``first``)."""
    want = np.maximum(priorities.astype(np.float64), 1e-12) ** alpha
    fresh = np.zeros(old_mass.shape, bool)
    fresh[took] = True
    dead = ref_mass <= 0     # swept: the program's mass has to be 0 too
    moved = np.where(fresh, new_mass < floor, new_mass != old_mass) & ~dead
    still = fresh & ~moved
    bad = int(np.sum(np.abs(new_mass[still] - ref_mass[still]) > MASS_RTOL * ref_mass[still]))
    bad += int(np.sum(new_mass[dead] != 0)) + int(np.sum(want >= floor))
    slots = np.flatnonzero(moved)
    n, m = want.shape[0], slots.shape[0]
    if not 0 < m <= n:
        return np.zeros(n, np.int64), bad + n
    wrote = new_mass[slots].astype(np.float64)
    # cost[b, d]: fewest unexplained masses with rows 0..b laid down and d
    # slots taken again so far, row b on slot b - d
    d = np.arange(n - m + 1)
    cost = np.full((n, n - m + 1), n + 1)
    again = np.zeros((n, n - m + 1), bool)
    cost[0, 0] = abs(wrote[0] - want[0]) > MASS_RTOL * want[0]
    for b in range(1, n):
        j = b - d
        on = (j >= 0) & (j < m)
        jj = np.clip(j, 0, m - 1)
        step = cost[b - 1]                                   # from slot j - 1
        stay = np.concatenate([[n + 1], cost[b - 1, :-1]])   # the same slot again
        stay = np.where(slots[jj] >= first[b], stay, n + 1)
        again[b] = stay < step
        miss = np.abs(wrote[jj] - want[b]) > MASS_RTOL * want[b]
        cost[b] = np.where(on, np.minimum(step, stay) + miss, n + 1)
    if cost[n - 1, n - m] > n:
        return np.zeros(n, np.int64), bad + n
    rows, taken = np.zeros(n, np.int64), n - m
    for b in range(n - 1, -1, -1):
        rows[b] = slots[b - taken]
        taken -= again[b, taken]
    return rows, bad + int(cost[n - 1, n - m])


def compare(old_weights, new_weights, priorities, reference: dict) -> dict:
    """``correctness.compare``'s three numbers, the parameter change taken
    leaf by leaf on the host."""
    got, want = (np.concatenate([np.asarray(p, np.float64) for p in ps])
                 for ps in (priorities, reference["priorities"]))
    num = den = 0.0
    for old, new, ref in zip(*(jax.tree_util.tree_leaves(t)
                               for t in (old_weights, new_weights, reference["weights"]))):
        old = np.asarray(old, np.float64)
        want_change = np.asarray(ref, np.float64) - old
        num += float(np.sum(np.square(np.asarray(new, np.float64) - old - want_change)))
        den += float(np.sum(np.square(want_change)))
    return {
        "fused_priority_rel": max(
            rel_l2(p, r) for p, r in zip(priorities, reference["priorities"])),
        "fused_priority_median_rel": float(np.median(np.abs(got - want) / want)),
        "fused_update_rel": float(np.sqrt(num) / max(np.sqrt(den), 1e-300)),
    }


def program_numbers(cfg: dict, beta: float, inputs: dict, shots: dict) -> tuple:
    t0 = time.perf_counter()
    reference = reference_run(cfg, beta, inputs, shots)
    print(f"[bench] check: the program's two calls took {shots['seconds']:.1f} s with "
          f"their set-up, the reference's replay {time.perf_counter() - t0:.1f} s; "
          f"routing over the program's calls {shots['routing']}", flush=True)
    numbers = compare(inputs["weights"], shots["weights"], shots["priorities"], reference)
    return reference["counts"], numbers, reference


def control_numbers(cfg: dict, beta: float, inputs: dict, shots: dict, reference: dict,
                    precision: str = "stated", row_shift: int = 0) -> dict:
    control = reference_run(cfg, beta, inputs, shots, precision, row_shift)
    return compare(inputs["weights"], control["weights"], control["priorities"], reference)


# --------------------------------------------------- readings for the limits

CONTROLS = {"bf16_held": ("bf16_held", 0), "fp8_activations": ("fp8_activations", 0),
            "gather_one_row_on": ("stated", 1)}


def main(argv=None) -> int:
    import argparse

    import manifest as mf

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="learner_feed_by_name")
    ap.add_argument("--seeds", type=int, default=10)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_800_000_001)
    args = ap.parse_args(argv)
    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    if jax.default_backend() != "tpu":
        print(f"needs a TPU, jax's default backend is {jax.default_backend()!r}; nothing read",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    entry = [c for c in mf.load_manifest()["configs"] if c["name"] == args.config][0]
    cfg = mf.load_json(os.path.join(mf.ROOT, entry["file"]))
    traffic = mf.load_json(os.path.join(HERE, "traffic", args.traffic + ".json"))
    beta = float(traffic["beta"])
    sound, controls = [], {name: [] for name in CONTROLS}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        inputs, shots = check_shots(cfg, traffic, seed)
        counts, got, reference = program_numbers(cfg, beta, inputs, shots)
        sound.append(got)
        print(f"seed {seed} program {json.dumps(got)} exact {json.dumps(counts)} "
              f"routing {json.dumps(shots['routing'])}", flush=True)
        if i < args.control_seeds:
            for name, (precision, shift) in CONTROLS.items():
                ctl = control_numbers(cfg, beta, inputs, shots, reference, precision, shift)
                controls[name].append(ctl)
                print(f"seed {seed} control {name} {json.dumps(ctl)}", flush=True)
    for number in sound[0]:
        print(f"READING {args.config} {number}: program max "
              f"{max(s[number] for s in sound):.6g} over {len(sound)} seeds; " + "; ".join(
                  f"{name} min {min(c[number] for c in rows):.6g}"
                  for name, rows in controls.items() if rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())

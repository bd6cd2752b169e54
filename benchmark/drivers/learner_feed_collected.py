"""Traffic driver ``learner_feed_collected``: ``learner_feed_by_name``'s run,
value for value, for a network whose state fills most of the chip.

The by-name driver's timed ``Feed`` holds a bound method of itself, so only a
full garbage collection frees its state and ring (PERF.md, Open question 11);
its comparison then makes the reference's float32 inputs, a second train state
and a second ring beside whatever of the first is still there, and keeps its
own ``Feed`` the same way while the reference replays.  At 737 M parameters
(5.9 GB of state, 3 GB of float32 inputs, 8 GB of the program's temporaries)
a run's fate would hang on when the interpreter collects.  Here the same
functions run in the same order with three things between them: a collection
before the comparison's inputs are made; the inputs (weights and target
weights) moved to the host before the program's state is made from them, so
that inputs, state and the state's making never stand on the chip together
and only the reference reads them again; and a collection before the
reference replays.  Nothing of the program or of what is compared differs.

Two things of the timed window are this driver's own (``Feed``).  The timed
state is ``program.init_state``'s with its second moment at the traffic
file's ``settled_second_moment`` (the comparison's ``NU0``), not at zero: from
zero RMSProp's first updates are sign steps of 4.5 times the learning rate on
every parameter, a state no learner is in after its first calls, and a
softmax router with no balancing rule collapses under them inside the window,
by seed onto more or fewer of the held experts (PERF.md, section 6).  From a
settled moment an update is ``lr * g / sqrt(nu)``, the window's routing is
the fresh router's, and the held experts see the share the cell's ``why``
gives.  And the fused calls' attention counters (``StepMetrics.attention``)
are kept beside the routing counters, for the per-layer reader.

    python3 benchmark/drivers/learner_feed_collected.py --config laguna_q_ep32 --seeds 9

prints the readings the limits are set from (the by-name driver's ``main``).
"""

from __future__ import annotations

import gc
import os
import sys

import jax
import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from drivers import learner_feed_by_name as base  # noqa: E402

_check_shots, _state_from_inputs, _Feed = base.check_shots, base.state_from_inputs, base.Feed
_program_numbers, _reference_fn = base.program_numbers, base._reference_fn


made = []   # the timed Feed of the run in hand; emptied before the comparison


class Feed(_Feed):
    """The by-name driver's ``Feed``, seeded with a settled second moment,
    keeping each call's attention counters."""

    def __init__(self, cfg: dict, traffic: dict, key, spans, state=None):
        self.second_moment = float(traffic["settled_second_moment"])
        self.attention = []
        made.append(self)
        super().__init__(cfg, traffic, key, spans, state)

    def _seeded_state(self, key):
        if self.mesh is not None:
            raise ValueError("this driver seeds its state on one chip")

        def settled(k):
            state = base.program.init_state(self.cfg, self.net, self.opt, k, None)
            return state.replace(opt_state=base.warm_second_moment(
                state.opt_state, self.second_moment))

        return jax.jit(settled)(key)

    def call(self, i: int):
        metrics = super().call(i)
        if getattr(metrics, "attention", None) is not None:
            self.attention.append(metrics.attention)
        return metrics


def attention_counters(taken: list, steps: int) -> dict:
    """{"attention_<key>_per_step": mean over the ``steps`` of the calls ``taken``}."""
    return {f"attention_{k}_per_step":
            float(sum(np.sum(np.asarray(a[k], np.float64)) for a in taken)) / steps
            for k in (taken[0] if taken else ())}


def check_shots(cfg: dict, traffic: dict, seed: int) -> tuple:
    """``learner_feed_by_name.check_shots`` between two collections, its
    inputs on the host from before the program's state is made of them."""

    def state_from_host(cfg, opt, inputs, mesh):
        for k in ("weights", "target"):
            inputs[k] = jax.device_get(inputs[k])
        return _state_from_inputs(cfg, opt, inputs, mesh)

    gc.collect()
    base.state_from_inputs = state_from_host
    try:
        return _check_shots(cfg, traffic, seed)
    finally:
        base.state_from_inputs = _state_from_inputs
        gc.collect()


def program_numbers(cfg: dict, beta: float, inputs: dict, shots: dict) -> tuple:
    """``learner_feed_by_name.program_numbers``, and each row's priority over
    the reference's, less one, printed by call: which rows a large
    ``fused_priority_rel`` comes from."""
    counts, numbers, reference = _program_numbers(cfg, beta, inputs, shots)
    for call, (got, want) in enumerate(zip(shots["priorities"], reference["priorities"])):
        print(f"[bench] check: call {call}, priorities over the reference's, less one: "
              + " ".join(f"{g / w - 1:+.4f}" for g, w in zip(got, want.reshape(-1))), flush=True)
    return counts, numbers, reference


def run(ctx) -> dict:
    base.Feed = Feed
    try:
        obs = base.run(ctx)
        feed = made.pop()
    finally:
        base.Feed = _Feed
        made.clear()
    cfg, traffic, seed = ctx.cell.config, ctx.cell.traffic, ctx.seed
    timed = feed.attention[int(traffic["warmup_calls"]):]
    del feed
    counted = attention_counters(timed, len(timed) * cfg["steps_per_call"])
    obs["counters"].update(counted)
    kinds = sorted(k[len("attention_blocks_total_"):-len("_per_step")]
                   for k in counted if k.startswith("attention_blocks_total_"))
    if kinds:
        print("[bench] attention a step, as the fused calls after the warm-up count it: "
              + ", ".join(
                  f"{kind} {counted[f'attention_blocks_visited_{kind}_per_step']:.0f} of "
                  f"{counted[f'attention_blocks_total_{kind}_per_step']:.0f} blocks visited, "
                  f"{counted[f'attention_pairs_in_mask_{kind}_per_step']:.0f} pairs in the mask"
                  for kind in kinds), flush=True)
    obs["check"] = lambda: program_numbers(
        cfg, float(traffic["beta"]), *check_shots(cfg, traffic, seed))[:2]
    return obs


def with_bootstrap(reference_fn):
    """``learner_feed_by_name._reference_fn`` whose step first prints, for
    each row of the batch it replays, what decides the bootstrap: the gap
    between the reference's two largest online Q values at ``next_obs`` and
    what the target network would add to the priority were the second one
    taken (discount x the difference of its Q values at the two actions).  A
    row whose gap is inside bfloat16's rounding of Q, and whose priority in
    the program is off by that difference, had its argmax flipped."""

    def named(cfg_json: str, precision: str):
        import json

        cfg, step = json.loads(cfg_json), reference_fn(cfg_json, precision)
        ref = base.reference_of(cfg)
        f32 = lambda tree: jax.tree_util.tree_map(lambda x: x.astype("float32"), tree)  # noqa: E731
        q_next = jax.jit(lambda w, t, o: (ref.forward(f32(w), o, cfg)[0],
                                          ref.forward(f32(t), o, cfg)[0]))

        def stepped(w, t, v, b):
            with jax.default_matmul_precision("highest"):
                online, target = (np.asarray(q, np.float64) for q in q_next(w, t, b["next_obs"]))
            rows = np.arange(online.shape[0])
            second, first = np.argsort(online, axis=1)[:, -2:].T
            moved = np.asarray(b["discount"], np.float64) * (
                target[rows, second] - target[rows, first])
            print("[bench] bootstrap by row (gap of the two largest online Q, the target's "
                  "move if the second were taken): " + ", ".join(
                      f"{g:.5f} {m:+.4f}" for g, m in
                      zip(online[rows, first] - online[rows, second], moved)), flush=True)
            return step(w, t, v, b)

        return stepped

    return named


def main(argv=None) -> int:
    """The by-name driver's readings under this driver's collections, with
    each row's priorities and bootstrap printed."""
    base.check_shots, base.program_numbers = check_shots, program_numbers
    base._reference_fn = with_bootstrap(_reference_fn)
    try:
        return base.main(["--traffic", "learner_feed_collected", *(argv or sys.argv[1:])])
    finally:
        base.check_shots, base.program_numbers = _check_shots, _program_numbers
        base._reference_fn = _reference_fn


if __name__ == "__main__":
    sys.exit(main())

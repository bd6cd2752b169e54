"""The comparison that decides ``correct``: fused calls of the program on a
small ring made from the seed, against the plain references.

The driver builds the program's own fused learner at the configuration's
widths and batch, with a ring of a few thousand rows and one step per call,
starts it from weights made here, runs two calls the way the timed window
dispatches them (ingest, sampler, gather, train step, restamp; per shard
under a mesh) and hands over host copies of the ring before and after each
call, the chunks it ingested, the priorities each call returned and the
final parameters.  From those alone:

  exact     every ring row after a call is what the reference's ingest makes
            of the ring before it; every mass that moved belongs to a sampled
            row and is the returned priority ^ alpha; every sampled row lies
            in its stratum of the cumulative mass
  compared  ``fused_priority_rel``: the returned priorities against the
            reference's TD errors on the rows the reference gathers at the
            sampled slots, with the reference's importance weights and its
            own chain of parameters (largest of the calls), which one wrong
            row moves; ``fused_priority_median_rel``: the median over all
            rows of the same difference, relative, which a few rows whose
            double-Q argmax flips in bfloat16 do not move and a lower
            precision of the forward does; ``fused_update_rel``: the relative L2 distance between the
            program's and the reference's parameter change over the calls

Which rows a call sampled is read from the masses: only sampled rows move.
A row ingested in the same call moved anyway, so the chunks carry priorities
whose mass is above any a restamp can write, and such a row counts as
sampled when its mass fell below that floor.

The second moment starts at NU0 so an update is about lr*g/sqrt(NU0): far
above float32's resolution at the weights' size, and still linear in g.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from reference import dueling_dqn as ref
from reference import prioritized_ring as ring_ref

NU0 = 1e-4
STRATUM_SLACK = 0.02   # of a stratum: float32 sums of a few thousand masses round
MASS_RTOL = 1e-4       # a float32 power against a float64 one
EXACT = ("ring_rows_differing", "masses_unexplained", "rows_outside_stratum")
_FLAX_NAMES = {  # the program's flax module names, in the order it builds them
    "conv1": "Conv_0", "conv2": "Conv_1", "conv3": "Conv_2",
    "value_hidden": "Dense_0", "advantage_hidden": "Dense_1",
    "value_head": "Dense_2", "advantage_head": "Dense_3",
}
_HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(config_name: str, bench_dir: str = _HERE) -> dict:
    """``limits/<configuration>.json``: the limit of each number compared."""
    with open(os.path.join(bench_dir, "limits", config_name + ".json")) as f:
        return {k: v["limit"] for k, v in json.load(f).items()}


def to_program_params(weights: dict, dtype=None) -> dict:
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    return {"params": {
        _FLAX_NAMES[k]: {"kernel": cast(v["w"]), "bias": cast(v["b"])}
        for k, v in weights.items()
    }}


def from_program_params(params: dict) -> dict:
    p = params["params"]
    return {k: {"w": p[n]["kernel"].astype(jnp.float32),
                "b": p[n]["bias"].astype(jnp.float32)}
            for k, n in _FLAX_NAMES.items()}


def _stored(tree, dtype):
    """``tree`` as the configuration stores it in ``dtype``, back in float32."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.dtype(dtype)).astype(jnp.float32), tree)


def make_inputs(seed_key, cfg: dict) -> dict:
    """Weights, target weights and second moment, from the seed."""
    kw, kt = jax.random.split(seed_key)
    weights = ref.make_weights(kw, cfg)
    noise = ref.make_weights(kt, cfg)
    target = jax.tree_util.tree_map(lambda w, n: w + 0.1 * n, weights, noise)
    # What the configuration stores in a lower type is rounded here, so both
    # sides start from the same numbers.
    target = _stored(target, cfg["precision"]["target_params"])
    nu = _stored(jax.tree_util.tree_map(lambda x: jnp.full_like(x, NU0), weights),
                 cfg["precision"]["second_moment"])
    return {"weights": weights, "target": target, "nu": nu}


def flat(tree) -> jnp.ndarray:
    return jnp.concatenate([jnp.ravel(x) for x in jax.tree_util.tree_leaves(tree)])


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda w, t, v, b: ref.learner_step(w, t, v, b, cfg, precision))


def sampled_rows(old_mass, new_mass, ref_mass, took, priorities, alpha, floor, first):
    """(rows, masses unexplained): which slot each of the batch's rows was
    drawn from, read from the masses that moved.  Strata are ordered, so the
    moved slots in order are the batch's rows in order.  Fewer moved than the
    batch has rows: a slot that reaches into the next stratum (``first`` is
    each stratum's first row) was drawn by both, with one priority."""
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
    rows, j = np.zeros(n, np.int64), 0
    for b in range(n):
        j = min(j, m - 1)
        rows[b] = slots[j]
        bad += int(abs(wrote[j] - want[b]) > MASS_RTOL * want[b])
        again = (b + 1 < n and n - 1 - b > m - 1 - j and slots[j] >= first[b + 1]
                 and abs(wrote[j] - want[b + 1]) <= MASS_RTOL * want[b + 1])
        j += not again
    return rows, bad + m - min(j, m)


def reference_run(cfg: dict, beta: float, inputs: dict, shots: dict,
                  precision: str = "stated", row_shift: int = 0) -> dict:
    """The reference over the calls in ``shots``: its own ring, weights and
    chain of updates.  Returns the exact counts, its priorities per call and
    its final weights.  ``precision`` and ``row_shift`` (gather the rows that
    many slots on) make the controls."""
    layout, alpha = cfg["replay_layout"], cfg["priority_exponent"]
    n = len(shots["rings"][0])
    rings = [{k: np.array(v) for k, v in shard.items()} for shard in shots["rings"][0]]
    counts = dict.fromkeys(EXACT, 0)
    step = _reference_fn(json.dumps(cfg, sort_keys=True), precision)
    weights, nu, priorities = inputs["weights"], inputs["nu"], []
    for call, chunks in enumerate(shots["chunks"]):
        before, after = shots["rings"][call], shots["rings"][call + 1]
        prio = shots["priorities"][call].reshape(n, -1)
        rows = []
        for d in range(n):
            ring = rings[d]
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
        nu = _stored(nu, cfg["precision"]["second_moment"])
        ref_prio = np.asarray(ref_prio)
        priorities.append(ref_prio)
        for r, g, p in zip(rings, rows, ref_prio.reshape(n, -1)):
            r["mass"][g] = p.astype(np.float64) ** alpha
    return {"counts": counts, "priorities": priorities, "weights": weights}


def compare(old_weights, new_weights, priorities, reference: dict) -> dict:
    """The numbers compared: one side's outputs against the reference's."""
    old = flat(old_weights)
    got, want = (np.concatenate([np.asarray(p, np.float64) for p in ps])
                 for ps in (priorities, reference["priorities"]))
    return {
        "fused_priority_rel": max(
            rel_l2(p, r) for p, r in zip(priorities, reference["priorities"])),
        "fused_priority_median_rel": float(np.median(np.abs(got - want) / want)),
        "fused_update_rel": rel_l2(flat(new_weights) - old,
                                   flat(reference["weights"]) - old),
    }


def program_numbers(cfg: dict, beta: float, inputs: dict, shots: dict) -> tuple:
    """(exact counts, numbers compared, the reference's run) for the
    program's calls in ``shots``."""
    reference = reference_run(cfg, beta, inputs, shots)
    numbers = compare(inputs["weights"], from_program_params(shots["params"]),
                      shots["priorities"], reference)
    return reference["counts"], numbers, reference


def control_numbers(cfg: dict, beta: float, inputs: dict, shots: dict, reference: dict,
                    precision: str = "stated", row_shift: int = 0) -> dict:
    """A control in the program's place: the reference in a lower precision,
    or gathering the wrong rows, on the batches the program drew."""
    control = reference_run(cfg, beta, inputs, shots, precision, row_shift)
    return compare(inputs["weights"], control["weights"], control["priorities"], reference)


def verdict(counts: dict, numbers: dict, limits: dict) -> tuple:
    """(ok, printable lines): every number compared beside its limit."""
    lines, ok = [], True
    for name in EXACT:
        good = counts[name] == 0
        ok = ok and good
        lines.append(f"compare {name} = {counts[name]}  limit 0  {'ok' if good else 'FAIL'}")
    for name, limit in limits.items():
        value = numbers[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        lines.append(f"compare {name} = {value:.6g}  limit {limit:g}  {'ok' if good else 'FAIL'}")
    return ok, lines

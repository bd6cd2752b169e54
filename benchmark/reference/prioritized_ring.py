"""Plain reference for the replay ring of one chip: ingest, the liveness
sweep, the stratified sampler's law, the importance weights and the gather.

Straightforward numpy on host copies of the ring, nothing imported from the
program.  A ring is a dict of arrays, one per field, as the configuration's
layout names them:

  double_store  obs, next_obs [C, *obs]; action, reward, discount, mass [C]
  dedup         frames [Cf, *obs]; obs_ref, next_ref [C] (frame sequence
                numbers modulo Q, the largest multiple of Cf under 2^30);
                action, reward, discount, mass [C]; fcount

with ``cursor`` (next transition slot) and ``count`` (transitions added).
Semantics:

  ingest   M transitions take slots cursor..cursor+M-1 (mod C) with mass
           priority^alpha; a dedup ring first writes U frames at sequence
           numbers fcount..fcount+U-1 (slot = seq mod Cf); afterwards a
           transition whose observation frame has been overwritten (its
           sequence number more than Cf behind fcount) has mass 0
  sampler  batch row b is drawn from stratum b: the rows whose stretch of the
           cumulative mass meets [b, b+1) * total / B
  weights  w_i = (N * q_i)^-beta over the largest w of the (global) batch,
           q_i = mass_i / total / shards, N = transitions held in all shards
  gather   a transition's observations are its rows (double_store) or the
           frames its two references name (dedup)
"""

from __future__ import annotations

import numpy as np

DATA_FIELDS = {
    "double_store": ("obs", "next_obs", "action", "reward", "discount"),
    "dedup": ("frames", "obs_ref", "next_ref", "action", "reward", "discount"),
}


def seq_modulus(frame_capacity: int) -> int:
    return ((1 << 30) // frame_capacity) * frame_capacity


def ingest(ring: dict, chunk: dict, layout: str, alpha: float) -> np.ndarray:
    """Apply one chunk to ``ring`` in place; returns the transition slots it
    took.  ``ring['mass']`` becomes float64."""
    cap = ring["mass"].shape[0]
    rows = chunk["priority"].shape[0]
    slots = (int(ring["cursor"]) + np.arange(rows)) % cap
    ring["mass"] = ring["mass"].astype(np.float64)
    if layout == "dedup":
        fcap = ring["frames"].shape[0]
        q = seq_modulus(fcap)
        u = chunk["frames"].shape[0]
        ring["frames"][((int(ring["fcount"]) + np.arange(u)) % q) % fcap] = chunk["frames"]
        ring["fcount"] = (int(ring["fcount"]) + u) % q
    for f in DATA_FIELDS[layout]:
        if f != "frames":
            ring[f][slots] = chunk[f]
    ring["mass"][slots] = np.maximum(chunk["priority"].astype(np.float64), 1e-12) ** alpha
    ring["cursor"] = (int(ring["cursor"]) + rows) % cap
    ring["count"] = int(ring["count"]) + rows
    if layout == "dedup":
        age = (ring["fcount"] - ring["obs_ref"].astype(np.int64)) % q
        ring["mass"][age > fcap] = 0.0
    return slots


def gather(ring: dict, rows: np.ndarray, layout: str) -> dict:
    batch = {f: ring[f][rows] for f in ("action", "reward", "discount")}
    if layout == "dedup":
        fcap = ring["frames"].shape[0]
        batch["obs"] = ring["frames"][ring["obs_ref"][rows] % fcap]
        batch["next_obs"] = ring["frames"][ring["next_ref"][rows] % fcap]
    else:
        batch["obs"], batch["next_obs"] = ring["obs"][rows], ring["next_obs"][rows]
    return batch


def strata(mass: np.ndarray, batch: int, slack: float) -> tuple:
    """(first, last) row of each of the ``batch`` strata, widened by
    ``slack`` of a stratum on both sides (float32 sums round)."""
    cdf = np.cumsum(mass.astype(np.float64))
    width = cdf[-1] / batch
    b = np.arange(batch)
    first = np.searchsorted(cdf, (b - slack) * width, side="right")
    last = np.searchsorted(cdf, (b + 1 + slack) * width, side="left")
    return first, np.minimum(last, mass.shape[0] - 1)


def importance_weights(masses: list, rows: list, held: list, beta: float) -> list:
    """Per shard, the weights of the rows drawn there, normalised by the
    largest of the whole batch."""
    n, total_held = len(masses), float(sum(held))
    raw = [np.maximum(total_held * m[r] / m.sum() / n, 1e-12) ** -beta
           for m, r in zip(masses, rows)]
    top = max(w.max() for w in raw)
    return [(w / top).astype(np.float32) for w in raw]

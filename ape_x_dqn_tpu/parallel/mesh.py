"""Device mesh + sharding utilities — the distributed backend's foundation.

The reference's "distributed backend" is Python ``multiprocessing`` on one
host (manager dict / queue / proxy RPC — reference main.py:18,37-42, SURVEY
§1 L4).  The TPU-native equivalent is laid out here per SURVEY §2's backend
entry: a ``jax.sharding.Mesh`` over the slice, parameters replicated, batches
sharded over the ``data`` axis, and XLA inserting the gradient all-reduce
over ICI — no hand-written collectives, no NCCL translation.

The mesh is 2D ``(data, model)`` by default with ``model=1``: data
parallelism is the capability the learner needs (BASELINE.md config 4), and
the ``model`` axis makes tensor-parallel layouts *expressible* (SURVEY §2
parallelism checklist: "design the param/pytree plumbing on NamedSharding so
TP is expressible") — ``infer_param_sharding`` shards wide dense kernels over
it when it has extent > 1.

Multi-host: all helpers operate on ``jax.devices()``, which under
``jax.distributed.initialize`` spans every host in the slice; shardings laid
out here put the all-reduce on ICI within a slice and DCN across slices
exactly as XLA's device assignment dictates — nothing below changes.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ape_x_dqn_tpu.utils import profiling


def device_info() -> dict:
    """The backend this process runs on, as jax reports it: what every
    entry point stamps on its first record.  Where this is the process's
    first ``jax.devices()`` the span is the chip's start-up."""
    with profiling.launch.span("backend"):
        devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def make_mesh(
    num_devices: Optional[int] = None,
    model_parallel: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a ``(data, model)`` mesh over the first ``num_devices`` devices.

    Args:
      num_devices: devices to use (default: all visible).
      model_parallel: extent of the ``model`` axis; must divide num_devices.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = num_devices if num_devices is not None else len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, only {len(devs)} visible")
    if n % model_parallel != 0:
        raise ValueError(
            f"model_parallel={model_parallel} must divide num_devices={n}"
        )
    grid = np.array(devs[:n]).reshape(n // model_parallel, model_parallel)
    return Mesh(grid, ("data", "model"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading axis sharded over ``data``; all trailing axes replicated."""
    return NamedSharding(mesh, P("data"))


def tree_batch_sharding(tree, mesh: Mesh):
    """Batch sharding for every leaf of a batched pytree."""
    sh = batch_sharding(mesh)
    return jax.tree_util.tree_map(lambda _: sh, tree)


def infer_param_sharding(params, mesh: Mesh, min_dim: int = 512):
    """Tensor-parallel layout rule: shard the trailing dim of any kernel
    whose trailing dim is divisible by the ``model`` axis extent and at
    least ``min_dim``; replicate everything else.

    With ``model=1`` (the default mesh) this replicates every leaf — DP
    exactly.  With ``model>1`` the two 512-wide dueling-stream dense kernels
    and the 3136→512 projections shard over ``model``, demonstrating the
    full 2D layout on the same code path.
    """
    m = mesh.shape["model"]

    def rule(x):
        if (
            m > 1
            and hasattr(x, "ndim")
            and x.ndim >= 2
            and x.shape[-1] >= min_dim
            and x.shape[-1] % m == 0
        ):
            spec = [None] * (x.ndim - 1) + ["model"]
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(rule, params)


def shard_train_state(state, mesh: Mesh, min_dim: int = 512):
    """Sharding pytree for a TrainState: params/target/opt-state follow the
    param rule (optimizer moments mirror their parameters), scalars
    replicated."""
    param_sh = infer_param_sharding(state.params, mesh, min_dim)
    target_sh = infer_param_sharding(state.target_params, mesh, min_dim)

    # Optimizer state leaves mirror param shapes where they match; anything
    # else (counts, scalars) replicates.
    shape_map = {}
    for leaf, sh in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(param_sh),
    ):
        shape_map.setdefault(getattr(leaf, "shape", ()), sh)

    rep = replicated(mesh)

    def opt_rule(x):
        return shape_map.get(getattr(x, "shape", ()), rep)

    opt_sh = jax.tree_util.tree_map(opt_rule, state.opt_state)
    return type(state)(
        params=param_sh,
        target_params=target_sh,
        opt_state=opt_sh,
        step=rep,
        rng=rep,
    )


def place_state(state, state_sharding):
    """Device-put a host train state onto the mesh per its sharding tree."""
    return jax.tree_util.tree_map(
        lambda x, sh: jax.device_put(x, sh), state, state_sharding
    )

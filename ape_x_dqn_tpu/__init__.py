"""ape_x_dqn_tpu — a TPU-native Ape-X DQN framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of the reference
``lefarov/Ape-X-DQN`` (Distributed Prioritized Experience Replay, Horgan et
al. 2018): ε-ladder actor fleets, n-step double-Q learning, central
prioritized replay with a sum-tree, async actor∥replay∥learner pipeline, and
a data-parallel pjit learner over a TPU mesh.

Lazy by contract (PEP 562): importing this package must NOT import jax.
Child processes across the fleet — replay shard servers, remote worker
launchers, the by-path bench producers, the lint gate — import submodules
like ``ape_x_dqn_tpu.replay.service`` and live on sub-second spawns, and
``import ape_x_dqn_tpu.anything`` executes this file first.  An eager
``from .types import ...`` here taxed every one of them with the full
device-runtime import; the re-exports below resolve on first attribute
access instead (``from ape_x_dqn_tpu import TrainState`` still works).
The ``import-light`` checker in ``ape_x_dqn_tpu/analysis`` walks exactly
this chain.

The one thing done eagerly is the launch log's (``utils/profiling.py``,
standard library only at module scope): the process's first stamp is taken
here, and a finder that times the heavy imports that follow goes to the
front of ``sys.meta_path``.
"""

from __future__ import annotations

import importlib

from ape_x_dqn_tpu.utils import profiling as _profiling

_profiling.install()

__version__ = "0.1.0"

# name -> defining submodule, resolved on first attribute access.
_LAZY = {
    "NStepTransition": "ape_x_dqn_tpu.types",
    "PrioritizedBatch": "ape_x_dqn_tpu.types",
    "TrainState": "ape_x_dqn_tpu.types",
}

__all__ = [
    "NStepTransition",
    "PrioritizedBatch",
    "TrainState",
    "__version__",
]


def __getattr__(name):
    target = _LAZY.get(name)
    if target is not None:
        return getattr(importlib.import_module(target), name)
    # `ape_x_dqn_tpu.types` style submodule access after a bare
    # `import ape_x_dqn_tpu` — import it on demand.
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None


def __dir__():
    return sorted(set(globals()) | set(_LAZY))

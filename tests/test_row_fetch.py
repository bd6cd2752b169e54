"""The gather stage's kernel (``ops/pallas/row_fetch.fetch_turned``) against
``RowFormat.unpack(rows[slots])`` bit for bit, in Pallas' interpreter, and the
rule by which ``dedup_fetch`` takes it (``turned_fetch_applies``): from the
shapes alone, the present path and the present bits everywhere else."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ape_x_dqn_tpu.ops.pallas import row_fetch
from ape_x_dqn_tpu.replay import device_dedup
from ape_x_dqn_tpu.replay.device_dedup import (
    DedupDeviceReplayState,
    RowFormat,
    dedup_fetch,
    turned_fetch_applies,
)
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch
from ape_x_dqn_tpu.utils import profiling

PAPER = (84, 84, 4)
CF = 320


def random_frames(seed, rows, obs_shape, dtype=np.uint8):
    return np.random.default_rng(seed).integers(
        0, np.iinfo(dtype).max + 1, (rows, *obs_shape), dtype=dtype)


@pytest.fixture(scope="module")
def paper_ring():
    frames = random_frames(0, CF, PAPER)
    return frames, jnp.asarray(RowFormat.of(PAPER, np.uint8).pack(frames))


def _slots(kind: str, b: int) -> np.ndarray:
    rng = np.random.default_rng(b)
    if kind == "random":
        return rng.integers(0, CF, b).astype(np.int32)
    if kind == "repeated":          # every row of a block the same, and the ends of the ring
        return np.repeat(np.array([7, 0, CF - 1, 7], np.int32), b // 4)
    assert kind == "ends"
    return np.where(np.arange(b) % 2 == 0, 0, CF - 1).astype(np.int32)


FETCHES = [("random", 128), ("random", 256), ("repeated", 128), ("ends", 256), ("sharded", 512)]


@pytest.mark.parametrize("kind,b", FETCHES, ids=[f"{k}-{b}" for k, b in FETCHES])
def test_kernel_gives_the_unpacked_rows_bit_for_bit(paper_ring, kind, b):
    frames, rows = paper_ring
    fmt = RowFormat.of(PAPER, np.uint8)
    fetch = lambda rows, slots: row_fetch.fetch_turned(rows, slots, PAPER, np.uint8)  # noqa: E731
    if kind == "sharded":
        # four shards, each its own quarter of the ring and 128 rows of the batch
        from ape_x_dqn_tpu.parallel import make_mesh

        n = 4
        slots = np.random.default_rng(1).integers(0, CF // n, b).astype(np.int32)
        got = jax.jit(shard_map(fetch, mesh=make_mesh(num_devices=n), in_specs=P("data"),
                                out_specs=P("data")))(rows, jnp.asarray(slots))
        slots = slots + np.repeat(np.arange(n) * (CF // n), b // n)
    else:
        slots = _slots(kind, b)
        got = jax.jit(fetch)(rows, jnp.asarray(slots))
    assert got.shape == (b, *PAPER) and got.dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(got), frames[slots])
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(fmt.unpack(rows[jnp.asarray(slots)])))


def _fetch(fmt, rows, slots, monkeypatch):
    """``dedup_fetch`` of both sides (the second the slots reversed), the
    paths the launch log says were traced, and whether a kernel was."""
    log = profiling.LaunchLog()
    monkeypatch.setattr(device_dedup, "launch", log)
    state = DedupDeviceReplayState(rows=rows, fmt=fmt)
    sampled = PrioritizedBatch(
        transition=NStepTransition(obs=slots, action=None, reward=None, discount=None,
                                   next_obs=slots[::-1]),
        indices=None, is_weights=None)
    fn = lambda st, s: dedup_fetch(st, s).transition  # noqa: E731
    kernels = str(jax.make_jaxpr(fn)(state, sampled)).count("pallas_call")
    out = jax.jit(fn)(state, sampled)
    return out, log.attrs_of("gather_path"), kernels


# (observation shape, dtype, batch): everything the rule leaves on the plain path.
PLAIN = [
    (PAPER, np.uint8, 8),              # a batch that does not fill the lanes
    (PAPER, np.uint8, 96),
    ((84, 84, 4), np.uint16, 128),     # two elements a word
    ((84, 84, 1), np.uint8, 128),      # a word is four pixels, not a pixel's channels
    ((6, 6, 4), np.uint8, 128),        # a toy row: 36 words in 128, no whole tile
    ((84, 84, 32), np.uint8, 128),     # a 32-frame history: 56,448 words = 441 x 128
]


@pytest.mark.parametrize("obs_shape,dtype,b", PLAIN,
                         ids=[f"{'x'.join(map(str, s))}-{np.dtype(d).name}-{b}" for s, d, b in PLAIN])
def test_the_rule_keeps_every_other_shape_on_the_plain_path(obs_shape, dtype, b, monkeypatch):
    cf = 6
    fmt = RowFormat.of(obs_shape, dtype)
    assert not turned_fetch_applies(fmt, (b,))
    if obs_shape != PAPER:   # the ring itself is as it was: a row a run of words
        assert fmt.row_shape == (fmt.row_stride,)
    frames = random_frames(3, cf, obs_shape, dtype)
    slots = np.random.default_rng(4).integers(0, cf, b).astype(np.int32)
    out, paths, kernels = _fetch(fmt, jnp.asarray(fmt.pack(frames)), jnp.asarray(slots), monkeypatch)
    assert kernels == 0
    assert paths == [{"path": "plain", "rows": b, "words": fmt.row_stride}] * 2  # one trace, two sides
    assert out.obs.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out.obs), frames[slots])
    np.testing.assert_array_equal(np.asarray(out.next_obs), frames[slots[::-1]])


def test_the_rule_takes_the_kernel_for_a_full_batch_of_paper_rows(paper_ring, monkeypatch):
    frames, rows = paper_ring
    fmt = RowFormat.of(PAPER, np.uint8)
    assert turned_fetch_applies(fmt, (128,)) and turned_fetch_applies(fmt, (512,))
    assert not turned_fetch_applies(fmt, (2, 128))    # K batches at once: the plain path
    slots = _slots("random", 128)
    out, paths, kernels = _fetch(fmt, rows, jnp.asarray(slots), monkeypatch)
    assert kernels == 2
    assert paths == [{"path": "kernel", "rows": 128, "words": 7168}] * 2
    np.testing.assert_array_equal(np.asarray(out.obs), frames[slots])
    np.testing.assert_array_equal(np.asarray(out.next_obs), frames[slots[::-1]])


def test_fetch_turned_refuses_what_it_cannot_turn(paper_ring):
    _, rows = paper_ring
    with pytest.raises(ValueError, match="no turned fetch"):
        row_fetch.fetch_turned(rows, jnp.zeros((96,), jnp.int32), PAPER, np.uint8)
    with pytest.raises(ValueError, match="no turned fetch"):
        row_fetch.fetch_turned(rows, jnp.zeros((128,), jnp.int32), (84, 84, 2, 2), np.uint8)


def test_the_kernels_import_leaves_the_gpu_interpreter_out():
    """A fresh process that imports a kernel module of the package has Pallas
    and its TPU side, not the GPU interpreter and the LLVM dialect it pulls in
    (two thirds of the import's time, paid in a cell's set-up), and the
    kernel still runs in the interpreter."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import numpy as np, jax.numpy as jnp\n"
         "from ape_x_dqn_tpu.ops.pallas import row_fetch\n"
         "assert 'jax.experimental.pallas.tpu' in sys.modules\n"
         "assert 'jax._src.pallas.mosaic_gpu.core' not in sys.modules\n"
         "assert 'jaxlib.mlir.dialects.llvm' not in sys.modules\n"
         "rows = jnp.arange(4 * 8 * 128, dtype=jnp.uint32).reshape(4, 8, 128)\n"
         "got = row_fetch.fetch_turned(rows, jnp.arange(128, dtype=jnp.int32) % 4, (32, 32, 4), np.uint8)\n"
         "want = np.asarray(rows).view(np.uint8).reshape(4, 32, 32, 4)[np.arange(128) % 4]\n"
         "assert np.array_equal(np.asarray(got), want)\n"],
        env=dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]

"""A side of the dedup ring's gather stage in one kernel: fetch and turn.

A ring row of byte observations whose last axis is four (84x84x4: four
stacked frames a pixel) is ``[T, 128]`` 32-bit words, a word a pixel, the
word's bytes the pixel's four channels (``replay/device_dedup.RowFormat``,
the tiled form: ``T`` a multiple of 8, so a row is whole ``(8, 128)`` tiles
and one piece of HBM).  The first convolution reads a batch of observations
``u8[B, H, W, 4]`` batch-minor with the four channels packed in a word
(``{0,3,2,1:T(4,128)(4,1)}`` on the TPU): for each pixel, ``B`` words, one a
batch row, each the pixel's four channels.  That word is the stored word, so
a side is a ``[B, P] -> [P, B]`` turn of words and nothing else.

``fetch_turned`` does it a block of 128 batch rows at a time: a row a DMA
from the ring, which stays in HBM as it lies, into one of two buffers; while
the next block's rows arrive, this block is turned, 128 x 128 words at a
time (a sublane-strided load, a transpose, a store strided by the number of
blocks), into the output, which is resident in VMEM and is declared as the
bytes the convolution reads: XLA sees a bitcast between the call and the
convolution and keeps the batch where the kernel left it (an optimization
barrier behind the reshape keeps the network's cast inside the convolution's
fusion, where it was).  The padding words of a row are fetched and never
written.

Off the TPU the kernel runs in Pallas' interpreter
(``blocked_attention.INTERPRET``), which cannot store through a reference
viewed as another type: there the output is declared as words and taken
apart after the call (on the TPU that declaration costs a slice, a re-tiling
reshape and an unpack fusion, a third of the stage).  The bytes agree where
element 0 of a packed word is its low byte: ``chip_smoke.py --fetch`` reads
that on the chip bit for bit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked

LANES = 128
BLOCK = LANES   # batch rows a block: what fills the lanes once turned


def _kernel(slots, rows_hbm, out, buf, sem, *, tiles: int, words: int, blocks: int):
    """``slots`` s32[B] in SMEM; ``rows_hbm`` u32[Cf, tiles, 128] in HBM;
    ``out`` the ``words`` first words of every row, pixel-major then block:
    word row ``p * blocks + g`` holds pixel ``p`` of block ``g``'s 128 rows."""
    g = pl.program_id(0)

    def row_copy(slot, i, half):
        return pltpu.make_async_copy(
            rows_hbm.at[slot], buf.at[half, pl.ds(pl.multiple_of(i * tiles, 8), tiles)], sem.at[half])

    def start(block):
        def one(i, _):
            row_copy(slots[block * BLOCK + i], i, block % 2).start()
            return 0
        jax.lax.fori_loop(0, BLOCK, one, 0)

    @pl.when(g == 0)
    def _():
        start(0)

    @pl.when(g + 1 < blocks)
    def _():
        start(g + 1)   # in flight while this block is turned

    half = g % 2

    def wait(i, _):
        row_copy(0, i, half).wait()   # a row's bytes, whichever row: the wait reads the size alone
        return 0
    jax.lax.fori_loop(0, BLOCK, wait, 0)

    o32 = out if out.dtype == jnp.uint32 else out.bitcast(jnp.uint32)
    for t in range(-(-words // LANES)):
        n = min(LANES, words - LANES * t)
        # word 128 t + l of every row of the block: [rows, l] -> [l, rows]
        turned = buf[half, pl.ds(t, BLOCK, stride=tiles), :].T[:n]
        if blocks == 1:
            o32[pl.ds(LANES * t, n), :] = turned
        else:
            o32[pl.ds(LANES * t * blocks + g, n, stride=blocks), :] = turned


def fetch_turned(rows: jax.Array, slots: jax.Array, obs_shape, dtype) -> jax.Array:
    """``rows[slots]`` as observations: ``rows`` u32[Cf, T, 128] (T a multiple
    of 8), ``slots`` int32[B] (B a multiple of 128), ``obs_shape`` [..., 4] of
    a one-byte ``dtype`` -> ``dtype[B, *obs_shape]``."""
    (b,), (_, tiles, lanes) = slots.shape, rows.shape
    words = math.prod(obs_shape[:-1])
    if (b % BLOCK or tiles % 8 or lanes != LANES or obs_shape[-1] != 4
            or jnp.dtype(dtype).itemsize != 1 or words > tiles * LANES):
        raise ValueError(f"no turned fetch of {obs_shape} {dtype} x {b} from {rows.shape}")
    blocks = b // BLOCK
    interpret = jax.default_backend() != "tpu" if blocked.INTERPRET is None else blocked.INTERPRET
    varying = jax.typeof(rows).vma | jax.typeof(slots).vma   # inside a shard_map
    if interpret:
        out = jax.ShapeDtypeStruct((words * blocks, LANES), jnp.uint32, vma=varying)
    else:
        out = jax.ShapeDtypeStruct((words * blocks * 4, LANES), dtype, vma=varying)
    buffers = 2 * BLOCK * tiles * LANES * 4
    x = pl.pallas_call(
        lambda *refs: _kernel(*refs, tiles=tiles, words=words, blocks=blocks),
        out_shape=out,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(out.shape, lambda g, slots: (0, 0)),
            scratch_shapes=[pltpu.VMEM((2, BLOCK * tiles, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + words * b * 4 + (1 << 20)),
        interpret=interpret, name="fetch_turned")(
            # held inside the ring, as a gather holds its indices: a DMA has no bounds check
            jnp.clip(slots.astype(jnp.int32), 0, rows.shape[0] - 1), rows)
    lead = obs_shape[:-1]
    if interpret:
        x = jax.lax.bitcast_convert_type(x, dtype)                 # [P x NB, 128, 4]
        x = x.reshape(*lead, blocks, BLOCK, 4)
        return jnp.moveaxis(x, (-3, -2), (0, 1)).reshape(b, *obs_shape)
    x = jnp.moveaxis(x.reshape(*lead, blocks, 4, BLOCK), (-3, -1), (0, 1)).reshape(b, *obs_shape)
    # Without the barrier the compiler moves the network's cast of the bytes
    # above this reshape, where it is a pass of its own over the side (24 us
    # at 512 rows) and no longer part of the convolution that reads it.
    return jax.lax.optimization_barrier(x)

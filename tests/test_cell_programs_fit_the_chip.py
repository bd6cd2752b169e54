"""Compile-only: each history cell's whole fused program (the train step, the
dedup ring's gather and the K-step loop of ``benchmark/configs/<cell>.json`` at
the cell's shapes) and one layer of a torso at its cell's shapes (latent
attention, a Mamba-2 mixer, a sliding attention layer), compiled for a TPU v5e
that is described and not attached: state, ring and temporaries fit the chip,
no part of the ring is copied, the scans are loops over chunks, the attention
kernels are in the executable once a layer kind, a mixer and an attention
layer write no activation twice.  And the readers of the optimized text on
texts recorded from the programs they were written against.

A file of its own: these compiles are minutes each, and a file is what one
worker of the test run takes (the run hands out files with many tests first,
which is why the readers' quick tests are here and not beside the readers).
The topology, the compile on a thread and the readers themselves are
``tests/test_dedup_ring_layout.py``'s.
"""

import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from ape_x_dqn_tpu.replay.device_dedup import build_dedup_fused_learn_step, init_dedup_device_replay
from tests.test_dedup_ring_layout import (  # noqa: F401 - topo, no_compile_cache: fixtures
    _ARRAY, _COMPUTATION, _INSTRUCTION, _PASS_THROUGH, _arrays, _compile, _compile_text, _with,
    assert_ring_stays_put, convolution_dims, no_compile_cache, ring_parameters,
    ring_sized_instructions, topo,
)


def _cell_fused_program(topo, monkeypatch, name: str, parameters: int):
    """(``benchmark/configs/<name>.json``, the ring's stored observations, its
    fused program compiled for v5e at the cell's shapes); the network holds
    ``parameters``."""
    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step, init_train_state, make_optimizer,
    )
    from ape_x_dqn_tpu.models.dueling import build_network
    from ape_x_dqn_tpu.ops.pallas import blocked_attention

    monkeypatch.setattr(blocked_attention, "INTERPRET", False)   # this process sees the CPU
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / f"{name}.json").read_text())
    prec = cfg["precision"]
    net = build_network(cfg["network"], cfg["num_actions"], torso=cfg,
                        channels=tuple(cfg["channels"]), hidden=cfg["hidden"],
                        compute_dtype=jnp.dtype(prec["compute"]),
                        param_dtype=jnp.dtype(prec["params"]))
    opt = make_optimizer(cfg["optimizer"], learning_rate=cfg["learning_rate"],
                         rmsprop_decay=cfg["rmsprop_decay"], rmsprop_eps=cfg["rmsprop_eps"],
                         max_grad_norm=cfg["max_grad_norm"],
                         second_moment_dtype=jnp.dtype(prec["second_moment"]))
    step_fn = build_train_step(net, opt, loss_kind=cfg["loss"], sync_in_step=False, jit=False)
    fused = build_dedup_fused_learn_step(
        step_fn, cfg["batch_size"], steps_per_call=cfg["steps_per_call"],
        priority_exponent=cfg["priority_exponent"], target_sync_freq=cfg["target_sync_freq"],
        sample_ahead=cfg["sample_ahead"])
    dev = SingleDeviceSharding(topo.devices[0])
    obs = tuple(cfg["obs_shape"])
    state = _with(jax.eval_shape(
        lambda k: init_train_state(net, opt, k, jnp.zeros((1, *obs), jnp.uint8),
                                   target_dtype=jnp.dtype(prec["target_params"])),
        jax.random.PRNGKey(0)), dev)
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state.params)) == parameters
    frames = int(cfg["replay_capacity"] * cfg["frame_ratio"])
    ring = _with(jax.eval_shape(lambda: init_dedup_device_replay(
        cfg["replay_capacity"], obs, frame_capacity=frames)), dev)
    assert ring.rows.shape == (frames, int(np.prod(obs)) // 4)      # an observation a row of words
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
    return cfg, frames, _compile(fused, (state, ring, 0.4, key), limit_s=900.0)   # one to four minutes alone


def test_the_history_torsos_fused_program_fits_the_chip(topo, no_compile_cache, monkeypatch):
    """``benchmark/configs/laguna_q_ep32.json``'s fused program at the cell's
    shapes (737 M parameters, B=8, 1,568 tokens, the 4,096-slot ring of
    56,448-word rows): state, ring and temporaries fit a v5e beside the
    comparison's chunks; no part of the ring is copied for the gather (rows
    wider than the chip's gather takes whole are read one at a time); no
    [T, T] score tensor is made; the attention kernels are in the executable,
    the sliding layers' once for their scanned run."""
    cfg, frames, compiled = _cell_fused_program(topo, monkeypatch, "laguna_q_ep32", 737_371_827)
    assert (frames, int(np.prod(cfg["obs_shape"])) // 4) == (5120, 56448)       # the ring's rows
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    hbm = 16_909_336_064                      # a v5e's, PERF.md Open question 11
    chunks = 4 * 256 * 56448 * 4              # the comparison's resident chunks
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + chunks < 0.95 * hbm, mem
    ring_bytes = frames * 56448 * 4
    assert_ring_stays_put(text, ring_bytes, 0)
    part_of_ring = [line[:160] for name, op, line in ring_sized_instructions(text, ring_bytes // 4)
                    if re.search(rf"\[{frames},\d+\]", line.split(" = ", 1)[1].split("(", 1)[0])]
    assert not part_of_ring and "mini-gather" not in text, part_of_ring
    square = [dims for _, dims, _ in _ARRAY.findall(text)
              if re.search(r"(?:^|,)(1568|1792|2048),\1(?:,|$)", dims)]
    assert not square, sorted(set(square))[:5]
    kernels = re.findall(r"%(attn_\w+?)[.\d]* = ", text)
    assert {"attn_fwd", "attn_fwd_lse", "attn_dq", "attn_dkv"} == set(kernels), kernels
    # two full layers apart and one scanned body of sliding layers: three of
    # each backward kernel, not five
    assert [kernels.count(k) for k in ("attn_dq", "attn_dkv")] == [3, 3], kernels
    assert "splash" not in text
    # the padded copies and the log-sums over 128 lanes are gone: PR 36's program took this much
    assert mem.temp_size_in_bytes <= 8_334_013_440, mem.temp_size_in_bytes


def test_the_state_space_torsos_fused_program_fits_the_chip(topo, no_compile_cache, monkeypatch):
    """``benchmark/configs/granite4h_q_l10.json``'s fused program at the cell's
    shapes (749 M parameters, B=8, 1,568 tokens in 7 chunks of 256, the
    4,096-slot ring): state, ring and temporaries leave over 0.5 GB of a v5e
    (under that the configuration's batch would have to be halved); no part
    of the ring is copied; the scan is a loop over chunks in the executable,
    forward and backward, that builds nothing of all seven chunks' ``[256,
    256]`` a head at once; the attention kernels compile at heads of 64, once:
    one attention layer."""
    _, frames, compiled = _cell_fused_program(topo, monkeypatch, "granite4h_q_l10", 748_781_171)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    hbm = 16_909_336_064                      # a v5e's, PERF.md Open question 11
    assert hbm - mem.argument_size_in_bytes - mem.temp_size_in_bytes > 0.5e9, mem
    # PR 35: 8,471,350,784; since PR 38 3.5 MB more, though every buffer of the attention
    # layer is smaller or gone: the compiler holds one more prefetched activation in flight
    assert mem.temp_size_in_bytes <= 8_474_811_392, mem
    ring_bytes = frames * 56448 * 4
    assert_ring_stays_put(text, ring_bytes, 0)
    assert "mini-gather" not in text
    # one chunk's decays and products a head, never seven chunks' at once
    per_chunk = [dims for _, dims, _ in _ARRAY.findall(text) if dims.endswith("256,256")]
    assert per_chunk and not any(
        int(np.prod([int(d) for d in dims.split(",")])) > 8 * 64 * 256 * 256 for dims in per_chunk), \
        sorted(set(per_chunk))
    kernels = re.findall(r"%(attn_\w+?)[.\d]* = ", text)
    assert "attn_fwd_lse" in kernels
    assert [kernels.count(k) for k in ("attn_dq", "attn_dkv")] == [1, 1], kernels
    assert "splash" not in text


@pytest.mark.slow    # 130-220 s on six workers: the suite's time limit has no room for a third such compile
def test_the_delta_rule_torsos_fused_program_fits_the_chip(topo, no_compile_cache, monkeypatch):
    """``benchmark/configs/solar2_q_ep40.json``'s fused program at the cell's
    shapes (709 M parameters: 16 of 64 heads and 8 of 320 experts a layer,
    B=8, 1,568 tokens in 25 chunks of 64, the 4,096-slot ring): state, ring
    and temporaries leave over 0.5 GB of a v5e (under that the configuration's
    batch would have to be halved); no part of the ring is copied; the
    delta-rule scan is a loop over chunks in the executable that builds
    nothing of all 25 chunks' ``[64, 64]`` a head at once; the attention
    kernels compile once, at 8 query heads a key-value head."""
    _, frames, compiled = _cell_fused_program(topo, monkeypatch, "solar2_q_ep40", 708_979_043)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"solar2_q_ep40 fused program for v5e: arguments {mem.argument_size_in_bytes} B, "
          f"temporaries {mem.temp_size_in_bytes} B, code {mem.generated_code_size_in_bytes} B")
    hbm = 16_909_336_064                      # a v5e's, PERF.md Open question 11
    assert hbm - mem.argument_size_in_bytes - mem.temp_size_in_bytes > 0.5e9, mem
    # PR 39: 8,690,472,960 (10,155,860,480 with the scan's residuals kept through the expert
    # layer's backward pass: 86 MB over the chip)
    assert mem.temp_size_in_bytes <= 8_690_472_960, mem
    ring_bytes = frames * 56448 * 4
    assert_ring_stays_put(text, ring_bytes, 0)
    assert "mini-gather" not in text
    # one chunk's pair scores and (I + A)^-1 a head, never 25 chunks' at once
    per_chunk = [dims for _, dims, _ in _ARRAY.findall(text) if dims.endswith("64,64")]
    assert per_chunk and not any(
        int(np.prod([int(d) for d in dims.split(",")])) > 2 * 8 * 16 * 64 * 64 for dims in per_chunk), \
        sorted(set(per_chunk))
    kernels = re.findall(r"%(attn_\w+?)[.\d]* = ", text)
    assert "attn_fwd_lse" in kernels
    assert [kernels.count(k) for k in ("attn_dq", "attn_dkv")] == [1, 1], kernels


@pytest.mark.slow    # about 160 s alone: the suite's time limit has no room for a third such compile
def test_the_latent_torsos_fused_program_fits_the_chip(topo, no_compile_cache, monkeypatch):
    """``benchmark/configs/ling3_q_l7.json``'s fused program at the cell's
    shapes (763 M parameters at 16 held experts: 8 of 32 heads and 16 of 512
    experts a layer, B=8, 1,568 tokens, the 4,096-slot ring): state, ring and
    temporaries leave over 0.5 GB of a v5e (under that the issue's rule holds
    8 experts); no part of the ring is copied; the attention kernels compile
    once, with the shared key operand ``[8, 1, 1568, 64]`` never laid out a
    head at a time."""
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / "ling3_q_l7.json").read_text())
    parameters = {16: 763_253_219, 8: 480_138_723}[cfg["experts_held"][1]]
    _, frames, compiled = _cell_fused_program(topo, monkeypatch, "ling3_q_l7", parameters)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"ling3_q_l7 fused program for v5e: arguments {mem.argument_size_in_bytes} B, "
          f"temporaries {mem.temp_size_in_bytes} B, code {mem.generated_code_size_in_bytes} B")
    hbm = 16_909_336_064                      # a v5e's, PERF.md Open question 11
    assert hbm - mem.argument_size_in_bytes - mem.temp_size_in_bytes > 0.5e9, mem
    assert mem.temp_size_in_bytes <= 9_103_542_784, mem      # PR 42, 16 held experts
    assert_ring_stays_put(text, frames * 56448 * 4, 0)
    kernels = re.findall(r"%(attn_\w+?)[.\d]* = ", text)
    assert "attn_fwd_lse" in kernels
    assert [kernels.count(k) for k in ("attn_dq", "attn_dkv")] == [1, 1], kernels
    assert not [dims for dtype, dims, _ in _ARRAY.findall(text)
                if dtype == "bf16" and dims == "8,8,1568,256"]     # no head padded from 192


def test_the_latent_kernels_compile_for_the_chip(topo, no_compile_cache, monkeypatch):
    """One ``ling_hybrid.LatentAttention`` layer at ``ling3_q_l7``'s shapes
    (``u`` ``bf16[8, 1568, 2560]``, 8 heads of 128 + 64 against values of
    128), pulled back: the three kernels take the shared operand through the
    chip's compiler (blocks of 64 lanes, the second accumulators), the rope
    key crosses as ``[8, 1, 1568, 64]`` and no ``[8, 8, 1568, 192]`` or
    ``256`` exists; its gradient a head is summed outside the kernel."""
    from ape_x_dqn_tpu.models import ling_hybrid
    from ape_x_dqn_tpu.ops.pallas import blocked_attention

    monkeypatch.setattr(blocked_attention, "INTERPRET", False)   # this process sees the CPU
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / "ling3_q_l7.json").read_text())
    layer = ling_hybrid.LatentAttention(ling_hybrid.spec_from_config(cfg), "latent_attention",
                                        jnp.bfloat16, jnp.float32)
    dev = SingleDeviceSharding(topo.devices[0])
    params = _with(jax.eval_shape(
        lambda k: layer.init(k, jnp.zeros((1, 8, 2560), jnp.bfloat16)), jax.random.PRNGKey(0)), dev)
    u = jax.ShapeDtypeStruct((8, 1568, 2560), jnp.bfloat16, sharding=dev)
    text = _compile_text(jax.jit(lambda p, v, ct: jax.vjp(layer.apply, p, v)[1](ct)), (params, u, u))
    kernels = sorted(set(re.findall(r"%(attn_\w+?)[.\d]* = ", text)))
    assert kernels == ["attn_dkv", "attn_dq", "attn_fwd_lse"]
    shapes = {dims for dtype, dims, _ in _ARRAY.findall(text) if dtype == "bf16"}
    assert "8,1,1568,64" in shapes and "8,8,1568,64" in shapes      # the one key; dq's and dk's parts a head
    assert not shapes & {"8,8,1568,192", "8,8,1568,256"}, shapes


# ------------------------------- what a Mamba-2 mixer passes around its scan

RELAYOUTS = ("copy", "pad", "slice")


def outside_the_walk(hlo_text: str, under: str = ""):
    """(name, opcode, shape text) of the instructions that make a buffer of
    their own outside every ``while`` body: not inside a fused computation,
    not inside a loop's body or condition; with ``under``, those whose line
    (its ``op_name``) holds that scope."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo_text))
    loops = set(re.findall(r"(?:body|condition)=%?([\w.\-]+)", hlo_text))
    inside = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if (m and inside not in fused and inside not in loops and under in line
                and m.group("op") not in _PASS_THROUGH):
            yield m.group("name"), m.group("op"), m.group("shape")


def relayouts(hlo_text: str, least_bytes: int, under: str = "") -> list:
    """The ``copy``, ``pad`` and ``slice`` instructions outside the walk whose
    result is at least ``least_bytes``: an activation written again as it was."""
    return [name for name, op, shape in outside_the_walk(hlo_text, under)
            if op in RELAYOUTS and any(b >= least_bytes for b, _ in _arrays(shape))]


def wide_float32(hlo_text: str, least_elements: int) -> list:
    """The instructions outside the walk with a float32 result of at least
    ``least_elements`` elements."""
    return [name for name, _, shape in outside_the_walk(hlo_text)
            if any(dtype == "f32" and int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
                   >= least_elements for dtype, dims, _ in _ARRAY.findall(shape))]


@pytest.mark.parametrize("differentiated", [False, True])
def test_the_mixer_passes_its_activations_around_the_scan_once(
        topo, no_compile_cache, monkeypatch, differentiated):
    """One ``granite_hybrid.Mamba2`` layer at ``granite4h_q_l10``'s shapes (``u``
    ``bf16[8, 1568, 2048]``, float32 parameters), forward and under
    ``jax.grad``: outside the chunk walk no ``copy``, ``pad`` or ``slice``
    writes an activation of 100 MB again (left to the compiler ``x`` was
    written seven times on its way into the scan: 7 such instructions a
    forward, 13 a differentiated pass), and nothing is a float32 array of
    ``[8, 1568, 4096]`` (``y`` was widened in HBM and copied once more
    before the gate and norm read it)."""
    from ape_x_dqn_tpu.models import granite_hybrid
    from ape_x_dqn_tpu.ops.pallas import blocked_attention

    monkeypatch.setattr(blocked_attention, "INTERPRET", False)   # this process sees the CPU
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / "granite4h_q_l10.json").read_text())
    layer = granite_hybrid.Mamba2(spec=granite_hybrid.spec_from_config(cfg), op="mamba",
                                  compute_dtype=jnp.bfloat16, param_dtype=jnp.float32)
    dev = SingleDeviceSharding(topo.devices[0])
    rows, tokens, hidden = cfg["batch_size"], 32 * 49, cfg["hidden_size"]
    params = _with(jax.eval_shape(
        lambda k: layer.init(k, jnp.zeros((1, 8, hidden), jnp.bfloat16)), jax.random.PRNGKey(0)), dev)
    u = jax.ShapeDtypeStruct((rows, tokens, hidden), jnp.bfloat16, sharding=dev)
    fn = (jax.grad(lambda p, v: jnp.sum(jnp.square(layer.apply(p, v).astype(jnp.float32))), (0, 1))
          if differentiated else layer.apply)
    text = _compile_text(jax.jit(fn), (params, u))
    assert " while(" in text                                   # the walk is a loop
    again = relayouts(text, 100_000_000)
    assert not again, again
    wide = wide_float32(text, rows * tokens * 4096)
    assert not wide, wide


def test_reader_finds_the_copies_around_the_scan():
    """The readers on the entry computation the parent of PR 35 compiled one
    layer's forward to (operands shortened): ``x`` sliced, split, turned,
    padded and copied twice into the walk, ``y`` widened and copied out."""
    text = """HloModule jit_fwd

%fused_computation.5 (p: bf16[8,1792,64,64]) -> f32[8,1568,64,64] {
  %p = bf16[8,1792,64,64]{1,3,2,0:T(8,128)(2,1)} parameter(0)
  %s = bf16[8,1568,64,64]{1,3,2,0:T(8,128)(2,1)} slice(%p), slice={[0:8], [0:1568], [0:64], [0:64]}
  ROOT %c = f32[8,1568,64,64]{1,3,2,0:T(8,128)} convert(%s)
}

%body.1 (t: (s32[], bf16[7,8,256,64,64])) -> (s32[], bf16[7,8,256,64,64]) {
  %t = (s32[], bf16[7,8,256,64,64]{2,4,3,1,0:T(8,128)(2,1)}) parameter(0)
  %copy.99 = bf16[7,8,256,64,64]{2,4,3,1,0:T(8,128)(2,1)} copy(%gte.1)
  ROOT %r = (s32[], bf16[7,8,256,64,64]{2,4,3,1,0:T(8,128)(2,1)}) tuple(%gte.0, %copy.99)
}

ENTRY %main (u: bf16[8,1568,2048]) -> bf16[8,1568,2048] {
  %fusion.60 = bf16[8,1568,8512]{2,1,0:T(8,128)(2,1)} fusion(%u, %w), kind=kOutput, calls=%fused_computation.60
  %slice.21 = bf16[8,1568,4352]{2,1,0:T(8,128)(2,1)} slice(%fusion.60), slice={[0:8], [0:1568], [4096:8448]}
  %divide_multiply_fusion = bf16[8,1568,4352]{2,1,0:T(8,128)(2,1)S(1)} fusion(%slice.21), kind=kLoop, calls=%fused_computation.61
  %split.0 = bf16[8,1568,4096]{2,1,0:T(8,128)(2,1)} slice(%divide_multiply_fusion), slice={[0:8], [0:1568], [0:4096]}
  %copy.22 = bf16[8,1568,4096]{1,2,0:T(8,128)(2,1)S(1)} copy(%split.0)
  %bitcast.9 = bf16[8,1568,64,64]{1,3,2,0:T(8,128)(2,1)} bitcast(%copy.22)
  %pad.2 = bf16[8,1792,64,64]{1,3,2,0:T(8,128)(2,1)} pad(%bitcast.9, %constant.1), padding=0_0x0_224x0_0x0_0
  %reshape.8 = bf16[8,7,256,64,64]{2,1,4,3,0:T(8,128)(2,1)} reshape(%pad.2)
  %copy.33 = bf16[7,8,64,8,8,256]{5,4,0,3,2,1:T(8,128)(2,1)S(1)} copy(%bitcast.11)
  %copy.26 = bf16[7,8,256,64,64]{2,4,3,1,0:T(8,128)(2,1)} copy(%bitcast.12)
  %while.1 = (s32[], bf16[7,8,256,64,64]{2,4,3,1,0:T(8,128)(2,1)}) while(%tuple.1), condition=%cond.1, body=%body.1
  %slice_convert_fusion = f32[8,1568,64,64]{1,3,2,0:T(8,128)} fusion(%gte.5), kind=kLoop, calls=%fused_computation.5
  %copy.28 = f32[8,1568,4096]{2,1,0:T(8,128)} copy(%bitcast.10)
  %copy.24 = bf16[2048,8512]{1,0:T(8,128)(2,1)S(1)} copy(%w)
  %multiply_reduce_fusion = f32[8,1568]{1,0:T(8,128)S(1)} fusion(%copy.28, %z), kind=kInput, calls=%fused_computation.62
  ROOT %fusion.55 = bf16[8,1568,2048]{2,1,0:T(8,128)(2,1)} fusion(%copy.28, %w2), kind=kOutput, calls=%fused_computation.63
}
"""
    assert relayouts(text, 100_000_000) == [
        "slice.21", "split.0", "copy.22", "pad.2", "copy.33", "copy.26", "copy.28"]
    assert wide_float32(text, 8 * 1568 * 4096) == ["slice_convert_fusion", "copy.28"]
    # the weights' copy (35 MB), a chunk's copy in the walk and a reshape are none of them
    assert "copy.24" not in relayouts(text, 100_000_000) and "copy.99" not in relayouts(text, 1)


# ------------------------------- what an attention layer hands its kernels

@pytest.mark.parametrize("differentiated", [False, True])
def test_an_attention_layer_hands_its_kernels_what_the_projections_wrote(
        topo, no_compile_cache, monkeypatch, differentiated):
    """One sliding ``laguna_moe.GatedAttention`` layer at ``laguna_q_ep32``'s
    shapes (``u`` ``bf16[8, 1568, 3072]``, 72 query heads over 8 key-value
    heads of 128), forward and pulled back: under ``torso:attn_*`` no ``pad``,
    ``slice`` or ``copy`` of a whole ``q``, ``k``, ``v`` or output (the
    length was padded to 2,048 in HBM: four pads a differentiated pass), and
    nowhere a float32 array of ``[B, H, T, 128]`` (the log-sum, a lane of
    which was kept, and a float32 copy of the output for ``di``): the
    log-sum and ``di`` are ``f32[8, 8, 9, 1568]``."""
    from ape_x_dqn_tpu.models import laguna_moe
    from ape_x_dqn_tpu.ops.pallas import blocked_attention

    monkeypatch.setattr(blocked_attention, "INTERPRET", False)   # this process sees the CPU
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / "laguna_q_ep32.json").read_text())
    layer = laguna_moe.GatedAttention(laguna_moe.spec_from_config(cfg), "sliding_attention",
                                      jnp.bfloat16, jnp.float32)
    dev = SingleDeviceSharding(topo.devices[0])
    rows, tokens, hidden, kv, hd = (cfg["batch_size"], 32 * 49, cfg["hidden_size"],
                                    cfg["num_key_value_heads"], cfg["head_dim"])
    params = _with(jax.eval_shape(
        lambda k: layer.init(k, jnp.zeros((1, 8, hidden), jnp.bfloat16)), jax.random.PRNGKey(0)), dev)
    u = jax.ShapeDtypeStruct((rows, tokens, hidden), jnp.bfloat16, sharding=dev)
    fn = ((lambda p, v, ct: jax.vjp(layer.apply, p, v)[1](ct)) if differentiated
          else (lambda p, v, ct: layer.apply(p, v)))
    text = _compile_text(jax.jit(fn), (params, u, u))
    kernels = sorted(set(re.findall(r"%(attn_\w+?)[.\d]* = ", text)))
    assert kernels == (["attn_dkv", "attn_dq", "attn_fwd_lse"] if differentiated else ["attn_fwd"])
    again = relayouts(text, rows * kv * tokens * hd * 2, under="torso:attn_")
    assert not again, again
    wide = wide_float32(text, rows * 72 * tokens * 128)
    assert not wide, wide
    sums = {dims for dtype, dims, _ in _ARRAY.findall(text) if dtype == "f32" and "1568" in dims
            and dims.startswith(f"{rows},{kv},9,")}
    assert sums == ({f"{rows},{kv},9,{tokens}"} if differentiated else set()), sums


def test_reader_finds_the_pads_around_the_kernels():
    """The readers on the entry computation the parent of PR 38 compiled the
    same layer's differentiated pass to (operands shortened): ``q``, ``k``
    and ``v`` padded to 2,048, the cotangent padded, the log-sum written over
    128 lanes and the float32 copy of the output that ``di`` was summed from."""
    text = """HloModule jit_pull

ENTRY %main (u: bf16[8,1568,3072]) -> bf16[8,1568,3072] {
  %pad.4 = bf16[8,8,2048,128]{3,2,1,0:T(8,128)(2,1)} pad(%fusion.3, %c), padding=0_0x0_0x0_480x0_0, metadata={op_name="jit(pull)/jvp(GatedAttention)/torso:attn_window/jit(_pad)/pad"}
  %pad.2 = bf16[8,8,2048,128]{3,2,1,0:T(8,128)(2,1)} pad(%fusion.2, %c), padding=0_0x0_0x0_480x0_0, metadata={op_name="jit(pull)/jvp(GatedAttention)/torso:attn_window/jit(_pad)/pad"}
  %pad.0 = bf16[8,72,2048,128]{3,2,1,0:T(8,128)(2,1)} pad(%fusion.1, %c), padding=0_0x0_0x0_480x0_0, metadata={op_name="jit(pull)/jvp(GatedAttention)/torso:attn_window/jit(_pad)/pad"}
  %splash_mha_fwd_residuals.1 = (f32[8,512,128]{2,1,0:T(8,128)}, bf16[8,72,2048,128]{3,2,1,0:T(8,128)(2,1)}, f32[8,72,2048,128]{3,2,1,0:T(8,128)}) custom-call(%pad.0, %pad.2, %pad.4), custom_call_target="tpu_custom_call"
  %copy.23 = f32[8,72,2048,128]{2,3,1,0:T(8,128)} copy(%gte.2)
  %broadcast_in_dim.7 = f32[8,72,8,2048]{3,2,1,0:T(8,128)} broadcast(%fusion.9), dimensions={0,1,3}, metadata={op_name="jit(pull)/transpose(jvp(GatedAttention))/torso:attn_window/vmap(jit(_splash_attention))/broadcast_in_dim"}
  %pad.9 = bf16[8,72,2048,128]{3,2,1,0:T(8,128)(2,1)} pad(%fusion.8, %c), padding=0_0x0_0x0_480x0_0, metadata={op_name="jit(pull)/transpose(jvp(GatedAttention))/torso:attn_window/pad"}
  %copy.11 = bf16[8,72,1568,128]{3,2,1,0:T(8,128)(2,1)} copy(%fusion.0), metadata={op_name="jit(pull)/jvp(GatedAttention)/convert_element_type"}
  ROOT %fusion.55 = bf16[8,1568,3072]{2,1,0:T(8,128)(2,1)} fusion(%pad.9, %w), kind=kOutput, calls=%fused_computation.63
}
"""
    assert relayouts(text, 8 * 8 * 1568 * 128 * 2, under="torso:attn_") == [
        "pad.4", "pad.2", "pad.0", "pad.9"]
    assert wide_float32(text, 8 * 72 * 1568 * 128) == ["splash_mha_fwd_residuals.1", "copy.23"]
    # RoPE's output written in the kernels' layout is the mixer's, not the kernels'
    assert "copy.11" in relayouts(text, 1) and "copy.11" not in relayouts(text, 1, "torso:attn_")


# ------------------------------- the readers of the dedup programs' texts

def test_reader_finds_k_batches_gathered_ahead():
    """The reader on the entry computation the parent of PR 33 compiled to:
    a side's rows gathered for all K batches, copied twice, taken apart."""
    text = """HloModule jit_fused

%fused_computation.7 (p: u32[153600,7168], i: s32[32768]) -> u32[32768,7168] {
  %p = u32[153600,7168]{1,0:T(8,128)} parameter(0)
  %i = s32[32768]{0:T(1024)} parameter(1)
  ROOT %g = u32[32768,7168]{1,0:T(8,128)} gather(%p, %i), offset_dims={1}
}

ENTRY %main (replay_state_rows.1: u32[153600,7168]) -> u8[64,512,84,84,4] {
  %replay_state_rows.1 = u32[153600,7168]{1,0:T(8,128)} parameter(0)
  %fusion.7 = u32[32768,7168]{1,0:T(8,128)} fusion(%replay_state_rows.1, %broadcast_clamp_fusion.1), kind=kCustom, calls=%fused_computation.7, metadata={op_name="jit(fused)/stage:gather/gather"}
  %bitcast.133 = u32[64,512,7056]{2,1,0:T(8,128)} bitcast(%fusion.7)
  %copy.36 = u32[64,512,7056]{1,0,2:T(8,128)} copy(%bitcast.133)
  %bitcast.5 = u32[64,512,84,84]{1,0,3,2:T(8,128)} bitcast(%copy.36)
  %copy.30 = u32[64,512,84,84]{1,3,2,0:T(8,128)} copy(%bitcast.5)
  ROOT %fusion.234 = u8[64,512,84,84,4]{1,4,3,2,0:T(4,128)(4,1)} fusion(%copy.30), kind=kLoop, calls=%fused_computation.324, metadata={op_name="jit(fused)/stage:gather/bitcast_convert_type"}
}
"""
    k_batches = 64 * 512 * 84 * 84 * 4
    assert [n for n, _, _ in ring_sized_instructions(text, k_batches)] == [
        "fusion.7", "copy.36", "copy.30", "fusion.234"]
    # a batch's rows inside the loop are far under the threshold
    assert not ring_sized_instructions(
        text.replace("64,512,", "1,512,").replace("32768", "512"), k_batches)


def test_reader_finds_a_joined_forward():
    """The reader on a forward over ``[obs; next_obs]`` and its weight
    gradient, as the optimized text had them until PR 29."""
    text = """HloModule jit_fused

%fused_computation.1 (p0: bf16[1024,20,20,32], p1: bf16[4,4,32,64]) -> bf16[1024,9,9,64] {
  %p0 = bf16[1024,20,20,32]{3,0,2,1:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[4,4,32,64]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  ROOT %conv_general_dilated.1 = bf16[1024,9,9,64]{3,0,2,1:T(8,128)(2,1)} convolution(%p0, %p1), window={size=4x4 stride=2x2}, dim_labels=b01f_01io->b01f
}

%fused_computation.2 (p2: bf16[1024,20,20,32], p3: bf16[1024,9,9,64]) -> bf16[4,4,32,64] {
  %p2 = bf16[1024,20,20,32]{3,0,2,1:T(8,128)(2,1)} parameter(0)
  %p3 = bf16[1024,9,9,64]{3,0,2,1:T(8,128)(2,1)} parameter(1)
  ROOT %conv_general_dilated.2 = bf16[4,4,32,64]{3,2,1,0:T(8,128)(2,1)} convolution(%p2, %p3), window={size=9x9 rhs_dilate=2x2}, dim_labels=f01b_i01o->01bf
}
"""
    convs = convolution_dims(text)
    assert sorted(convs) == ["conv_general_dilated.1", "conv_general_dilated.2"]
    assert all(1024 in dims for dims in convs.values())
    assert convs["conv_general_dilated.2"] == {1024, 20, 32, 9, 64, 4}


def test_reader_finds_a_copied_ring():
    """The reader on the text the old storage gave: the ring index in the
    lanes, a copy in, the scatter, a copy out."""
    text = """HloModule jit_add, input_output_alias={ {0}: (0, {}, may-alias) }

%fused_computation (p: u8[5120,84,84,4]) -> u8[5120,84,84,4] {
  %p = u8[5120,84,84,4]{2,3,1,0:T(8,128)(4,1)} parameter(0)
  ROOT %s = u8[5120,84,84,4]{2,3,1,0:T(8,128)(4,1)} scatter(%p, %p, %p)
}

ENTRY %main (state_frames.1: u8[5120,84,84,4]) -> u8[5120,84,84,4] {
  %state_frames.1 = u8[5120,84,84,4]{0,3,2,1:T(4,128)(4,1)} parameter(0)
  %copy.3 = u8[5120,84,84,4]{2,3,1,0:T(8,128)(4,1)} copy(%state_frames.1)
  %fusion = u8[5120,84,84,4]{2,3,1,0:T(8,128)(4,1)} fusion(%copy.3, %x, %y), kind=kCustom, calls=%fused_computation, metadata={op_name="scatter"}
  ROOT %copy.5 = u8[5120,84,84,4]{0,3,2,1:T(4,128)(4,1)} copy(%fusion)
}
"""
    ring_bytes = 5120 * 84 * 84 * 4
    assert ring_parameters(text, ring_bytes) == {
        "state_frames.1": "{0,3,2,1:T(4,128)(4,1)}"}
    assert [n for n, _, _ in ring_sized_instructions(text, ring_bytes)] == [
        "copy.3", "fusion", "copy.5"]
    with pytest.raises(AssertionError, match="not major"):
        assert_ring_stays_put(text, ring_bytes, 1)

"""Compile-only: the dedup programs, compiled for a TPU v5e that is described
and not attached, keep the frame ring's index major and touch no array of the
ring's size but the ring itself.

At the benchmark check's ring (4,096 slots, 5,120 observations of 84x84x4) the
optimized text of the frame add, the transition add and the fused program, on
one chip and under ``shard_map`` over four, holds no instruction that makes an
array of the ring's size except the scatter that writes into the donated ring.
Stored as ``[Cf, 84, 84, 4]`` the chip put the ring index in the lanes and
every program copied the whole ring (``copy.3``/``copy.5`` around the
scatter, ``copy.25`` before the gather).

The fused program at the paper's shapes (``benchmark/configs/apex_b512.json``,
one chip and the four-chip shard) holds no convolution or product over two
batches of rows: the backward pass covers the rows that carry a gradient.
It makes no array of K batches of observations either: the scan is handed
the sampled slots and fetches a batch's rows in its body, a side one kernel
on a ring whose rows are whole tiles (``ops/pallas/row_fetch.py``), with
nothing but bitcasts between the kernel and the first convolutions.

One expert block of ``benchmark/configs/lfm2moe_q_ep8.json`` at its published
widths, forward and backward: no array of the worst case's ``tokens x k``
rows by an expert's width, every grouped product inside a loop over the
tiles the routing fills, and fewer temporaries than the layer that built the
worst case's buffers took.

Every test here shares one description of the topology, made in a fixture:
only one process may load the TPU's library (on-chip-measurement guide), and
one cache of the dedup programs compiled (``compiled``).  The cells' whole
fused programs, one layer of each torso and the readers' own tests on recorded
texts are ``tests/test_cell_programs_fit_the_chip.py``, a file of its own for
another worker of the test run.
"""

import json
import os
import pathlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ape_x_dqn_tpu.replay.device_dedup import (
    build_dedup_fused_learn_step,
    dedup_device_add_frames,
    dedup_device_add_transitions,
    init_dedup_device_replay,
)
from ape_x_dqn_tpu.replay.device_dedup_dp import (
    build_sharded_dedup_add_frames,
    build_sharded_dedup_add_transitions,
    build_sharded_dedup_fused_learn_step,
    dedup_replay_specs,
)

OBS = (84, 84, 4)
ROWS, BLOCK = 256, 320          # transitions and observations a call
# A chip's ring in the benchmark's check; global batch 32, 8 a chip over four.
CHECK = dict(channels=(8, 8, 8), hidden=32, actions=4, batch=32, k=2,
             slots=4096, frames=5120)
COMPILE_LIMIT_S = 240.0


def _paper() -> dict:
    """``apex_b512`` as its cells run it: network, batch, K, a chip's ring."""
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / "apex_b512.json").read_text())
    assert tuple(cfg["obs_shape"]) == OBS and cfg["replay_layout"] == "dedup"
    return dict(channels=tuple(cfg["channels"]), hidden=cfg["hidden"],
                actions=cfg["num_actions"], batch=cfg["batch_size"],
                k=cfg["steps_per_call"], slots=cfg["replay_capacity"],
                frames=int(cfg["replay_capacity"] * cfg["frame_ratio"]))

# ------------------------------------------------ reading the optimized text

_DTYPE_BYTES = {"pred": 1, "u8": 1, "s8": 1, "u16": 2, "s16": 2, "bf16": 2,
                "f16": 2, "u32": 4, "s32": 4, "f32": 4, "u64": 8, "s64": 8,
                "f64": 8}
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<shape>.*?) (?P<op>[\w\-]+)\((?P<args>.*)$")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\](\{[^}]*\})?")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
# Opcodes that hand an array on without making one.
_PASS_THROUGH = {"parameter", "tuple", "get-tuple-element", "bitcast", "while",
                 "optimization-barrier"}


def _arrays(shape_text: str):
    """(bytes, layout text) of every array in an instruction's shape."""
    for dtype, dims, layout in _ARRAY.findall(shape_text):
        if dtype in _DTYPE_BYTES:
            n = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            yield n * _DTYPE_BYTES[dtype], layout


def ring_sized_instructions(hlo_text: str, ring_bytes: int) -> list:
    """[(name, opcode, line)] of the instructions of the optimized module
    that produce an array of at least ``ring_bytes``, outside fused
    computations (whose instructions make no buffer of their own) and
    leaving out what only hands an array on."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo_text))
    out, inside = [], None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or inside in fused or m.group("op") in _PASS_THROUGH:
            continue
        if any(b >= ring_bytes for b, _ in _arrays(m.group("shape"))):
            out.append((m.group("name"), m.group("op"), line.strip()))
    return out


def ring_parameters(hlo_text: str, ring_bytes: int) -> dict:
    """{name: layout text} of the entry parameters of the ring's size."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    out = {}
    for line in entry.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group("op") == "parameter":
            for b, layout in _arrays(m.group("shape")):
                if b >= ring_bytes:
                    out[m.group("name")] = layout
    return out


def assert_ring_stays_put(hlo_text: str, ring_bytes: int, scatters: int):
    """Dimension 0 major in the ring parameter's layout; ``scatters``
    ring-sized instructions, each a scatter (alone or as a fusion) whose
    first operand is the ring parameter itself and whose output the module
    aliases to a parameter."""
    rings = ring_parameters(hlo_text, ring_bytes)
    assert rings, "no parameter of the ring's size"
    for layout in rings.values():
        order = re.match(r"\{([\d,]+)", layout).group(1).split(",")
        assert order[-1] == "0", f"ring held {layout}: its index is not major"
    big = ring_sized_instructions(hlo_text, ring_bytes)
    lines = "\n".join(line[:200] for _, _, line in big)
    assert len(big) == scatters, f"ring-sized instructions:\n{lines}"
    for name, op, line in big:
        assert op in ("fusion", "scatter") and "scatter" in line, lines
        first = _INSTRUCTION.match("  " + line).group("args").split(",")[0]
        assert first.strip().lstrip("%") in rings, (
            f"{name} does not take the ring parameter first:\n{lines}")
    if scatters:
        assert "input_output_alias" in hlo_text.split("\n", 1)[0]


# ------------------------------------------------------- what is compiled

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _learner(shapes: dict):
    from ape_x_dqn_tpu.learner.train_step import init_train_state, make_optimizer
    from ape_x_dqn_tpu.models.dueling import build_network

    net = build_network("nature", shapes["actions"], channels=shapes["channels"],
                        hidden=shapes["hidden"], compute_dtype=jnp.bfloat16)
    opt = make_optimizer("rmsprop", learning_rate=1e-4)
    state = jax.eval_shape(
        lambda k: init_train_state(net, opt, k, jnp.zeros((1, *OBS), jnp.uint8)),
        jax.random.PRNGKey(0))
    return net, opt, state


def _with(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _programs(topo, n: int, shapes: dict = CHECK) -> dict:
    """{name: (jitted, argument shapes)} of the three programs, on the first
    chip of the topology (``n`` = 1) or under ``shard_map`` over ``n``."""
    from ape_x_dqn_tpu.learner.train_step import build_train_step

    net, opt, tstate = _learner(shapes)
    step_fn = build_train_step(
        net, opt, loss_kind="squared", sync_in_step=False, jit=False,
        grad_reduce_axis="data" if n > 1 else None)
    ring = jax.eval_shape(
        lambda: init_dedup_device_replay(shapes["slots"] * n, OBS, frame_capacity=shapes["frames"] * n))
    kw = dict(steps_per_call=shapes["k"], target_sync_freq=shapes["k"], sample_ahead=True)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if n == 1:
        dev = SingleDeviceSharding(topo.devices[0])
        ring, tstate = _with(ring, dev), _with(tstate, dev)
        arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=dev)  # noqa: E731
        lead = ()
        add_f = jax.jit(dedup_device_add_frames, donate_argnums=(0,))
        add_t = jax.jit(
            lambda st, *a: dedup_device_add_transitions(st, *a, 0.6),
            donate_argnums=(0,))
        fused = build_dedup_fused_learn_step(step_fn, shapes["batch"], **kw)
    else:
        mesh = Mesh(np.array(topo.devices[:n]), ("data",))
        row = NamedSharding(mesh, P("data"))
        ring = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape if x.ndim else (n,), x.dtype, sharding=row), ring)
        tstate = _with(tstate, NamedSharding(mesh, P()))
        arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=row)  # noqa: E731
        lead = (n,)
        add_f = build_sharded_dedup_add_frames(mesh)
        add_t = build_sharded_dedup_add_transitions(mesh, 0.6)
        fused = build_sharded_dedup_fused_learn_step(step_fn, mesh, shapes["batch"], **kw)
        assert jax.tree_util.tree_structure(ring) == jax.tree_util.tree_structure(
            dedup_replay_specs())
    vec = lambda dt: arg((*lead, ROWS), dt)  # noqa: E731
    return {
        "add_frames": (add_f, (ring, arg((*lead, BLOCK, *OBS), jnp.uint8))),
        "add_transitions": (add_t, (
            ring, vec(jnp.int32), vec(jnp.int32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.float32), vec(jnp.float32))),
        "fused": (fused, (tstate, ring, 0.4, key)),
    }


def _compile(jitted, args, limit_s: float = COMPILE_LIMIT_S):
    """The executable, or a failure once ``limit_s`` have gone (the compile
    runs on a thread of this process, which holds the TPU's library; a stuck
    one is left behind as a daemon)."""
    from ape_x_dqn_tpu.ops.pallas import blocked_attention

    box = {}

    def work():
        # this process sees the CPU: a kernel traced here is for the chip
        before, blocked_attention.INTERPRET = blocked_attention.INTERPRET, False
        try:
            box["done"] = jitted.lower(*args).compile()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            box["error"] = e
        finally:
            blocked_attention.INTERPRET = before

    t = threading.Thread(target=work, daemon=True)
    t.start()
    t.join(limit_s)
    if t.is_alive():
        pytest.fail(f"compile for v5e not done in {limit_s:.0f} s")
    if "error" in box:
        raise box["error"]
    return box["done"]


def _compile_text(jitted, args) -> str:
    return _compile(jitted, args).as_text()


@pytest.fixture(scope="module")
def compiled(topo, no_compile_cache):
    """``compiled(chips, shapes, program)``: one of ``_programs``' three at the
    check's shapes (``"check"``) or the paper's (``"paper"``), compiled for
    v5e once for every test of the module that reads it."""
    programs, done = {}, {}

    def get(chips: int, shapes: str, program: str):
        if (chips, shapes, program) not in done:
            if (chips, shapes) not in programs:
                programs[chips, shapes] = _programs(topo, chips, {"check": CHECK, "paper": _paper()}[shapes])
            done[chips, shapes, program] = _compile(*programs[chips, shapes][program])
        return done[chips, shapes, program]

    return get


@pytest.mark.parametrize("program,scatters", [
    ("add_frames", 1), ("add_transitions", 0), ("fused", 0)])
@pytest.mark.parametrize("chips", [1, 4])
def test_no_program_copies_the_ring(compiled, chips, program, scatters):
    ring_bytes = CHECK["frames"] * int(np.prod(OBS))  # a chip's ring, unpadded
    assert_ring_stays_put(compiled(chips, "check", program).as_text(), ring_bytes, scatters)


def convolution_dims(hlo_text: str) -> dict:
    """{name: every dimension of its result and operands} of the optimized
    module's convolutions (on the TPU a matrix product is one too).  A
    weight gradient contracts over the rows, so they show in its operands."""
    shapes, convs = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            shapes[m.group("name")] = m.group("shape")
            if m.group("op") == "convolution":
                convs[m.group("name")] = re.findall(r"%([\w.\-]+)", m.group("args").split(")")[0])
    return {name: {int(d) for text in (shapes[name], *(shapes[o] for o in operands))
                   for _, dims, _ in _ARRAY.findall(text) for d in dims.split(",") if d}
            for name, operands in convs.items()}


@pytest.mark.parametrize("chips", [1, 4])
def test_no_convolution_at_paper_shapes_covers_two_batches(compiled, chips):
    """``apex_b512``'s fused program: the convolutions and products of three
    forwards and one backward pass, every one over a chip's 512 rows (128 of
    four).  Joined with ``obs``, the bootstrap's rows made the online forward
    and the whole backward pass 1,024 (256) rows long."""
    rows = _paper()["batch"] // chips
    convs = convolution_dims(compiled(chips, "paper", "fused").as_text())
    doubled = {name: sorted(dims) for name, dims in convs.items() if 2 * rows in dims}
    assert not doubled, doubled
    # 7 the differentiated forward, 13 the bootstrap's pair (the first
    # convolution is one for both nets) and 12 backward, less what the
    # compiler merges
    assert sum(rows in dims for dims in convs.values()) >= 20, sorted(convs)


def first_window_convolutions(hlo_text: str) -> list:
    """The result's dimensions of every convolution of the optimized module
    with the stem's first window (8 x 8, stride 4): the forward first
    convolutions, a weight gradient's window being its cotangent's 20 x 20."""
    found = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group("op") == "convolution" and "window={size=8x8 stride=4x4}" in line:
            found += [tuple(int(d) for d in dims.split(","))
                      for _, dims, _ in _ARRAY.findall(m.group("shape"))]
    return sorted(found)


@pytest.mark.parametrize("chips", [1, 4])
def test_the_bootstraps_two_nets_share_one_first_convolution(compiled, chips):
    """``apex_b512``'s fused program reads ``next_obs`` through one first
    convolution: a chip's rows, the online and the target filters side by
    side in 64 output features, and none of 32 beside the differentiated
    forward's on ``obs`` (there were three of 32: two held the two banks
    apart on the same bytes, each at a fifth of the array's rate)."""
    shapes = _paper()
    rows, features = shapes["batch"] // chips, shapes["channels"][0]
    firsts = first_window_convolutions(compiled(chips, "paper", "fused").as_text())
    assert firsts == [(rows, 20, 20, features), (rows, 20, 20, 2 * features)], firsts


@pytest.mark.parametrize("chips", [1, 4])
def test_no_array_of_k_batches_at_paper_shapes(compiled, chips):
    """``apex_b512``'s fused program makes nothing of K x B observations: no
    instruction outside a fusion makes an array of K batches of a chip's
    rows, and a side's rows are fetched inside the ``while`` (gathered ahead
    there were eight such arrays a chip, 0.92-0.94 GB each on one, and
    this program took 2,825,479,168 B of temporaries there)."""
    shapes = _paper()
    fused = compiled(chips, "paper", "fused")
    text = fused.as_text()
    rows = shapes["batch"] // chips
    k_batches = shapes["k"] * rows * int(np.prod(OBS))
    big = ring_sized_instructions(text, k_batches)
    assert not big, "\n".join(line[:200] for _, _, line in big)
    # the row fetch of both sides: a kernel each, in a loop body
    bodies = set(re.findall(r" while\(.*?body=%?([\w.\-]+)", text))
    fetches = [inside for _, _, inside, _ in side_fetches(text)]
    assert len(fetches) == 2 and set(fetches) <= bodies, fetches
    if chips == 1:
        temp = fused.memory_analysis().temp_size_in_bytes
        assert temp < 500_000_000, temp


def side_fetches(hlo_text: str) -> list:
    """[(name, operand names, computation, line)] of the optimized module's
    ``fetch_turned`` kernel calls (``ops/pallas/row_fetch.py``)."""
    inside, found = None, []
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if (m and m.group("op") == "custom-call" and "tpu_custom_call" in line
                and "stage:gather/fetch_turned" in line):
            operands = re.findall(r"%([\w.\-]+)", m.group("args").split(")")[0])
            found.append((m.group("name"), operands, inside, line))
    return found


@pytest.mark.parametrize("chips", [1, 4])
def test_a_side_is_one_kernel_on_a_ring_of_whole_tiles(compiled, chips):
    """``apex_b512``'s fused program: the ring is ``u32[Cf, 56, 128]``, index
    major, a row seven whole tiles; inside the ``while`` each side is one
    ``fetch_turned`` call whose operand is the ring as it lies (no copy, no
    slice of it), and between a call and the convolutions that read it stand
    bitcasts alone: no gather of rows, no copy of ``u32[B, 7056]`` that turns
    the batch to the lanes, no fusion that takes words apart into
    ``u8[B, 84, 84, 4]``, no pass that casts the bytes ahead of the
    convolution (the parent's stage was those three a side: 169 of its 171
    us a step at 512 rows)."""
    shapes = _paper()
    rows, frames = shapes["batch"] // chips, shapes["frames"]
    text = compiled(chips, "paper", "fused").as_text()
    rings = ring_parameters(text, frames * int(np.prod(OBS)))
    assert len(rings) == 1, rings
    entry = text[text.index("\nENTRY "):]
    assert re.search(rf" = u32\[{frames},56,128\]\{{2,1,0:T\(8,128\)\}} parameter\(", entry), rings

    made, consumers, called = {}, {}, {}     # name -> (op, shape); name -> [names]; fusion -> computation
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            made[m.group("name")] = (m.group("op"), m.group("shape"))
            for operand in re.findall(r"%([\w.\-]+)", m.group("args").split("), ")[0]):
                consumers.setdefault(operand, []).append(m.group("name"))
            if m.group("op") == "fusion":
                called[m.group("name")] = re.search(r"calls=%?([\w.\-]+)", line).group(1)
    bodies = set(re.findall(r" while\(.*?body=%?([\w.\-]+)", text))
    fetches = side_fetches(text)
    assert len(fetches) == 2 and {inside for _, _, inside, _ in fetches} <= bodies, fetches
    convolving = {name for name in re.findall(
        r"^%?([\w.\-]+) \([^\n]*\{\n(?:[^}\n][^\n]*\n)*?[^\n]* convolution\(", text, re.M)}
    for name, operands, _, line in fetches:
        assert f"u32[{frames},56,128]{{2,1,0}}" in line, line[:400]
        assert made[operands[1]][0] in _PASS_THROUGH, (operands[1], made[operands[1]])
        assert made[name][1].startswith(f"u8[{rows // 128 * 7056 * 4},128]"), made[name]
        readers = consumers[name]
        assert readers and all(made[r][0] == "bitcast" for r in readers), [(r, made[r]) for r in readers]
        for reader in readers:
            assert made[reader][1].startswith(f"u8[{rows},84,84,4]"), made[reader]
            fusions = consumers[reader]
            assert fusions and all(called.get(f) in convolving for f in fusions), [
                (f, made[f][0], called.get(f)) for f in fusions]
    gather_lines = [line for line in text.splitlines() if "stage:gather" in line]
    assert not [line[:200] for line in gather_lines
                if re.search(rf" = u32\[{rows},(7168|7056|56,128)\]\S* (gather|copy|fusion)\(", line)]
    assert not [line[:200] for line in text.splitlines()
                if re.search(rf" = u8\[{rows},84,84,4\]\S* (?!bitcast|parameter)[\w\-]+\(", line)]


def test_the_sharded_step_gathers_the_streams_rows_and_reduces_no_kernel(compiled):
    """``apex_b512_dp4``'s fused program, a chip of four: the streams'
    gradients are products over the 512 gathered rows (one gather of the
    shared input, ``bf16[512,3136]``, XLA joins the two; one of each stream's
    cotangent, ``bf16[512,512]``), and what is left to all-reduce is the
    convolutions, the biases and the head, under 0.2 MB.  The parent's
    ``all-reduce.132`` carried the two ``bf16[3136,512]`` kernels, 6.6 MB a
    step through the ring twice."""
    from ape_x_dqn_tpu.utils.profiling import hlo_collectives

    shapes = _paper()
    text = compiled(4, "paper", "fused").as_text()
    found = hlo_collectives(text)
    rows, hidden = shapes["batch"], shapes["hidden"]
    reduced = found["all-reduce"]
    assert reduced["sync"]["bytes"] + reduced["async"]["bytes"] < 200_000, found
    gathered = found["all-gather"]
    assert gathered["sync"]["bytes"] + gathered["async"]["bytes"] == \
        2 * rows * (3136 + 2 * hidden), found
    assert gathered["sync"]["count"] + gathered["async"]["count"] == 3, found
    assert not re.search(rf"bf16\[3136,{hidden}\]\S* all-reduce", text)


# The temporaries of one expert block's forward and backward at the published
# widths when the layer built the worst case's buffers (the parent of PR 31,
# compiled here for v5e by this test's own program).
WORST_CASE_BLOCK_TEMP_BYTES = 3_154_748_928


def test_the_expert_layer_builds_no_worst_case_buffer(topo, no_compile_cache):
    from flax import linen as nn

    from ape_x_dqn_tpu.models.lfm2_moe import Block, spec_from_config

    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / "lfm2moe_q_ep8.json").read_text())
    spec = spec_from_config(cfg)
    assert (spec.hidden_size, spec.moe_intermediate_size, spec.num_held,
            spec.router_outputs, spec.num_experts_per_tok) == (2048, 1536, 8, 64, 4)
    tokens, k = (cfg["batch_size"], 49), spec.num_experts_per_tok
    block = nn.remat(Block)(spec, "conv", "moe", jnp.bfloat16, jnp.float32)
    dev = SingleDeviceSharding(topo.devices[0])
    params = _with(jax.eval_shape(
        lambda key: block.init(key, jnp.zeros((1, 49, spec.hidden_size), jnp.bfloat16)),
        jax.random.PRNGKey(0)), dev)
    h = jax.ShapeDtypeStruct((*tokens, spec.hidden_size), jnp.bfloat16, sharding=dev)

    def loss(p, h):
        (out, _), sown = block.apply(p, h, mutable=["routing"])
        return jnp.sum(jnp.square(out.astype(jnp.float32))), sown

    compiled = _compile(jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)),
                        (params, h))
    text = compiled.as_text()
    rows = tokens[0] * tokens[1] * k
    assert rows == 100_352
    wide = [dims for _, dims, _ in _ARRAY.findall(text)
            if re.fullmatch(rf"{rows},\d+", dims)
            and int(dims.split(",")[1]) >= spec.moe_intermediate_size]
    assert not wide, sorted(set(wide))
    bodies = set(re.findall(r" while\(.*?body=%?([\w.\-]+)", text))
    inside, products = None, []
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
        elif 'custom_call_target="tpu_custom_call"' in line:
            products.append(inside)
    # two products a forward tile; those two again, two data and two weight
    # gradients a backward tile; the recomputed forward's loop is dead and gone
    assert len(bodies) == 2 and len(products) >= 8 and set(products) == bodies, products
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < WORST_CASE_BLOCK_TEMP_BYTES, temp

@pytest.fixture(scope="module", params=["ling3_q_l7", "lfm2moe_q_ep8"])
def expert_share(request, topo, no_compile_cache):
    """(the cell's name, spec, rows, tokens a row, one ``ExpertShare`` of
    ``benchmark/configs/<name>.json`` at the cell's shapes (12,544 tokens
    over 512 outputs in 8 groups; 25,088 over 64), forward and pulled back,
    compiled for v5e): one compile a cell for the tests that read it."""
    from ape_x_dqn_tpu.models import expert_torso, lfm2_moe, ling_hybrid

    name = request.param
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / f"{name}.json").read_text())
    family, rows, tokens = ((ling_hybrid, 8, 1568) if name == "ling3_q_l7" else (lfm2_moe, 512, 49))
    spec = family.spec_from_config(cfg)
    assert rows == cfg["batch_size"]
    layer = expert_torso.ExpertShare(spec, jnp.bfloat16, jnp.float32)
    dev = SingleDeviceSharding(topo.devices[0])
    params = _with(jax.eval_shape(
        lambda key: layer.init(key, jnp.zeros((1, 8, spec.hidden_size), jnp.bfloat16)),
        jax.random.PRNGKey(0)), dev)
    u = jax.ShapeDtypeStruct((rows, tokens, spec.hidden_size), jnp.bfloat16, sharding=dev)

    def loss(p, u):
        out, sown = layer.apply(p, u, mutable=["routing"])
        return jnp.sum(jnp.square(out.astype(jnp.float32))), sown

    return name, spec, rows, tokens, _compile(
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)), (params, u))


def _named(text: str) -> list:
    """[(an instruction's name, its opcode, its ``op_name``)] of a program's text."""
    named = [(m.group("name"), m.group("op"), re.search(r'op_name="([^"]*)"', line))
             for line in text.splitlines() for m in [_INSTRUCTION.match(line)] if m]
    return [(n, op, scope.group(1) if scope else "") for n, op, scope in named]


def test_the_router_chooses_without_a_sort_or_a_gather(expert_share):
    """In one ``ExpertShare`` at the cell's shapes the choice is fusions of
    ``router_choice``'s reductions whose innermost ``torso:`` scope is
    ``torso:router`` (the parts' readers count them there), no ``sort`` comes
    from a ``top_k`` and no ``gather`` from a ``take_along_axis``, nothing of
    the choice is a sort, a gather or a scatter at all; the pairs' ``argsort``
    is still a sort of ``tokens x k`` keys."""
    _, spec, rows, tokens, compiled = expert_share
    text = compiled.as_text()
    named = _named(text)
    assert not [(n, scope) for n, op, scope in named if op == "sort" and scope.endswith("top_k")]
    assert not [(n, scope) for n, op, scope in named if op == "gather" and "take_along_axis" in scope]
    assert not [(n, scope) for n, op, scope in named if "top_k" in scope or "take_along_axis" in scope]
    choice = [(op, scope) for n, op, scope in named if "router_choice" in scope]
    assert choice and all(re.findall(r"torso:\w+", scope)[-1] == "torso:router" for _, scope in choice)
    assert {op for op, _ in choice}.isdisjoint({"sort", "gather", "scatter", "custom-call"}), choice
    assert any("transpose(" in scope for _, scope in choice)       # the pull-back's one-hot select
    pairs = rows * tokens * spec.num_experts_per_tok
    assert [n for n, op, scope in named if op == "sort" and scope.endswith("argsort)/sort")
            and f"s32[{pairs}]" in text.split(f"%{n} = ", 1)[1][:80]], "the pairs' argsort went"


# One ``ExpertShare``'s temporaries, forward and pulled back, compiled for v5e
# by the fixture above at the parent of PR 45, whose combine was one
# scatter-add of whole rows into ``f32[tokens, d]``.
WHOLE_ROW_COMBINE_TEMP_BYTES = {"ling3_q_l7": 993_377_792, "lfm2moe_q_ep8": 1_284_998_656}


def test_the_combine_adds_a_column_block_at_a_time(expert_share):
    """The walk's combine in one ``ExpertShare`` at the cell's shapes: no
    scatter-add takes a token sum of whole rows (``f32[12544, 2560]``,
    ``f32[25088, 2048]``); the forward's walk and the backward's each add
    into blocks of 512 columns, five at Ling's width and four at LFM2's.
    Everything of the combine sits under ``torso:router`` (the parts' readers
    count it there), and the program takes no more temporaries than with one
    scatter-add of whole rows."""
    name, _, rows, tokens, compiled = expert_share
    text = compiled.as_text()
    blocks = ["512"] * {"ling3_q_l7": 5, "lfm2moe_q_ep8": 4}[name]
    sums = re.findall(rf"= f32\[{rows * tokens},(\d+)\]\S* scatter\(", text)
    assert sums == 2 * blocks, sums
    combine = [(op, scope) for _, op, scope in _named(text) if "/combine/" in scope]
    assert "scatter" in {op for op, _ in combine}
    assert all(re.findall(r"torso:\w+", scope)[-1] == "torso:router" for _, scope in combine), combine
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= WHOLE_ROW_COMBINE_TEMP_BYTES[name], temp

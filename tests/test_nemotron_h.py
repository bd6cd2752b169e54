"""The network kind ``nemotron_h`` in the program: the scan in groups against
the recurrence a token at a time (forward and gradients, a padded last chunk),
one group against the scan as it was, the gated norm a group, the
``relu2`` experts' walk against a dense loop, the pairing of one-sublayer
layers into blocks, the spec's refusals, and the shares (Mamba-2 by groups,
attention by heads, the shared expert by columns, the experts by range) adding
up to the uncut reference layer, at small widths on the CPU (the kernels in
Pallas' interpreter); what every torso is held to (structure,
``benchmark/reference/nemotron3s_q.py`` on seeded weights, the float32 leaves,
scopes, counters, the configuration path, the trainer's loop) is the
contract's, ``tests/torso_contract.py``, on this torso's row."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_torso, granite_hybrid, nemotron_h, solar_open2
from ape_x_dqn_tpu.ops import chunked_scan
from ape_x_dqn_tpu.ops.pallas import scan_layout
from tests import torso_contract as contract
from tests.torso_contract import built  # noqa: F401 - the module's fixture

TORSO = contract.NEMOTRON


class TestContract(contract.of("nemotron_h")):
    """The contract's cases on this torso (``tests/torso_contract.py``)."""


@pytest.fixture(scope="module")
def ref():
    return importlib.import_module("reference.nemotron3s_q")


def _u(seed=0, rows=2, tokens=40, width=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, tokens, width))


# ------------------------------------------------------- the scan, in groups

def _scan_inputs(groups: int, rows=2, tokens=40, heads=4, p=8, n=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (rows, tokens, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, tokens, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.0))
    b, c = (jax.random.normal(k, (rows, tokens, groups, n)) for k in ks[3:5])
    return x, dt, a, b, c, 1.0 + 0.1 * jax.random.normal(ks[5], (heads,))


def _chunked(x, dt, a, b, c, d, chunk=16, grouped=True):
    """``scan_chunks`` on [B, T, ...] operands: cut, walked, joined."""
    cut, join = chunked_scan.cut, chunked_scan.join
    bc = [jnp.moveaxis(cut(v, chunk), 3, 2) if grouped else cut(v[:, :, 0], chunk) for v in (b, c)]
    y = chunked_scan.scan_chunks(cut(x, chunk, True), cut(dt, chunk, True), a, *bc, d)
    return join(y, x.shape[1], True)


def test_the_scan_in_groups_is_the_recurrence_a_token_at_a_time(ref):
    """Four heads in two groups, 40 tokens in chunks of 16 (a last chunk of 8
    and 8 of padding): the forward and every gradient against the reference's
    literal recurrence."""
    args = _scan_inputs(groups=2)
    with jax.default_matmul_precision("highest"):
        want = ref.recurrence(*args)
        got = _chunked(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        weigh = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        grads = [jax.grad(lambda *a: jnp.sum(f(*a) * weigh), argnums=range(6))(*args)
                 for f in (_chunked, ref.recurrence)]
    for g, w in zip(*grads):
        assert float(jnp.linalg.norm(g - w)) <= 1e-4 * float(jnp.linalg.norm(w)) + 1e-6
    # the groups are read: every head on group 0's B and C is another function
    shared = _chunked(*args[:3], *(jnp.broadcast_to(v[:, :, :1], v.shape) for v in args[3:5]), args[5])
    assert float(jnp.max(jnp.abs(shared - want))) > 0.1


def test_one_group_is_the_scan_as_it_was():
    """A call without a group axis (granite's) traces the chunk as it stood,
    unmapped: the program of one group is the program there was, operation for
    operation.  With a group axis of one the mapped chunk gives the same
    numbers to rounding, forward and gradients."""
    args = _scan_inputs(groups=1)
    assert chunked_scan._chunk_of(jnp.zeros((3, 2, 16, 8))) is chunked_scan._chunk
    assert chunked_scan._chunk_of(jnp.zeros((3, 2, 1, 16, 8))) is not chunked_scan._chunk
    text = str(jax.make_jaxpr(lambda *a: _chunked(*a, grouped=False))(*args))
    assert "vmap" not in text and ",1,16,8]" not in text
    for f in (lambda g: _chunked(*args, grouped=g),
              lambda g: jax.grad(lambda x, b: jnp.sum(_chunked(x, *args[1:3], b, *args[4:], grouped=g) ** 2),
                                 argnums=(0, 1))(args[0], args[3])):
        for one, was in zip(jax.tree_util.tree_leaves(f(True)), jax.tree_util.tree_leaves(f(False))):
            np.testing.assert_allclose(np.asarray(one), np.asarray(was), rtol=1e-5, atol=1e-5)


def test_the_gated_norm_takes_its_mean_square_a_group():
    """``gated_norm`` told two groups against the norm written out, forward
    and gradients, rows past T among the chunks; told one group it is the
    norm over all channels."""
    rows, tokens, chunk, c = 2, 40, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    y, z = (jax.random.normal(k, (rows, tokens, c)) for k in ks[:2])
    w = 1.0 + 0.1 * jax.random.normal(ks[2], (c,))

    def plain(y, z, w, groups):
        g = (y * jax.nn.silu(z)).reshape(rows, tokens, groups, c // groups)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + 1e-5)
        return g.reshape(rows, tokens, c) * w

    def kernel(y, z, w, groups):
        return scan_layout.gated_norm(chunked_scan.cut(y, chunk, True), z, w, 1e-5, groups)

    for groups in (2, 1):
        np.testing.assert_allclose(np.asarray(kernel(y, z, w, groups)),
                                   np.asarray(plain(y, z, w, groups)), atol=1e-5)
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a, groups) ** 2), argnums=(0, 1, 2))(y, z, w)
                     for f in (kernel, plain))
        for g, v in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(v), atol=2e-4)
    assert float(jnp.max(jnp.abs(plain(y, z, w, 2) - plain(y, z, w, 1)))) > 0.1


# ------------------------------------------------ the experts' rule, the walk

@pytest.mark.parametrize("rule", expert_torso.RULES)
def test_the_walk_is_the_dense_loop_under_either_rule(monkeypatch, rule):
    """``held_experts`` over tiles of 8 rows (``tile_rows`` patched small, so
    the pairs of three held experts fill several tiles) against a loop over the
    experts on every token with masks, forward and gradients."""
    monkeypatch.setattr(expert_torso, "KERNEL_ROWS", 8)
    tokens, d, f, n, k, outputs = 24, 16, 12, 3, 2, 6
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    u = jax.random.normal(ks[0], (tokens, d))
    w_in = jax.random.normal(ks[1], (n, d, f if rule == "relu2" else 2 * f)) / 4
    w2 = jax.random.normal(ks[2], (n, f, d)) / 4
    chosen = jnp.stack([jax.random.permutation(k_, outputs)[:k]
                        for k_ in jax.random.split(ks[3], tokens)])
    held = chosen < n
    gates = jnp.where(held, jax.random.uniform(ks[4], (tokens, k)), 0.0)
    order = jnp.argsort(jnp.where(held, chosen, n).reshape(-1), stable=True)
    sizes = jnp.sum(jax.nn.one_hot(chosen.reshape(-1), outputs, dtype=jnp.int32), 0)[:n]
    tile = expert_torso.tile_rows(tokens * k, n, outputs)
    assert tile == 32 and int(jnp.sum(sizes)) > 0

    def walked(u, w_in, w2, gates):
        return expert_torso.held_experts(u, w_in, w2, gates, order, sizes, 8, rule)

    def dense(u, w_in, w2, gates):
        y = jnp.zeros_like(u)
        for e in range(n):
            h = u @ w_in[e]
            a = jnp.square(jax.nn.relu(h)) if rule == "relu2" else jax.nn.silu(h[:, :f]) * h[:, f:]
            y = y + jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)[:, None] * (a @ w2[e])
        return y

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(walked(u, w_in, w2, gates)),
                                   np.asarray(dense(u, w_in, w2, gates)), atol=1e-5)
        got, want = (jax.grad(lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2, 3))(u, w_in, w2, gates)
                     for fn in (walked, dense))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(jnp.where(held, w, 0.0) if g.shape == gates.shape else w),
                                   atol=2e-5)


# ------------------------------------------------------------------ the spec

def test_the_published_layers_pair_into_blocks():
    types = nemotron_h.layer_types(TORSO)
    assert types == [nemotron_h.KINDS[c] for c in "ME*EMEMEM*EM"]
    assert nemotron_h.paired(types, [4, 5, 6, 7, 8, 9, 10]) == (
        ("mamba", "moe"), ("mamba", "moe"), ("mamba", "none"), ("attention", "moe"))
    assert nemotron_h.paired(types, [0, 2, 3, 11]) == (      # an E not held: the mixer stands alone
        ("mamba", "none"), ("attention", "moe"), ("mamba", "none"))
    for held in ([1, 2, 3], [4, 5, 7]):                       # an E that follows no held mixer
        with pytest.raises(ValueError, match="cannot be paired"):
            nemotron_h.paired(types, held)
    assert expert_torso.FFNS == ("dense", "moe", "none")


@pytest.mark.parametrize("bad", [
    dict(n_group=2), dict(topk_group=2), dict(moe_shared_expert_overlap=True), dict(mlp_bias=True),
    dict(attention_bias=True), dict(mamba_proj_bias=True), dict(use_bias=True),
    dict(use_conv_bias=False), dict(mlp_hidden_act="silu"), dict(mamba_hidden_act="gelu"),
    dict(sliding_window=128), dict(norm_topk_prob=False), dict(hybrid_override_pattern="ME-EMEMEM*EM"),
    dict(layer_types=["mamba"] * 12), dict(layers_held=[5, 6]), dict(layers_held=[12]),
    dict(mamba_heads_held=[0, 3]), dict(mamba_heads_held=None), dict(heads_held=[0, 1]),
    dict(expand=3), dict(shared_expert_held=[0, 96]), dict(experts_held=[12, 20]),
])
def test_the_spec_refuses_what_is_not_built(bad):
    with pytest.raises(ValueError):
        nemotron_h.spec_from_config(dict(TORSO, **bad))


@pytest.mark.parametrize("key", sorted(nemotron_h.BUILT))
def test_the_spec_takes_the_published_value_and_its_absence(key):
    spec = nemotron_h.spec_from_config(TORSO)
    assert nemotron_h.spec_from_config(dict(TORSO, **{key: nemotron_h.BUILT[key]})) == spec
    assert nemotron_h.spec_from_config({k: v for k, v in TORSO.items() if k != key}) == spec


def test_the_uncut_spec_holds_every_head_column_and_expert():
    whole = {k: v for k, v in TORSO.items() if k not in (
        "published", "heads_held", "mamba_heads_held", "shared_expert_held", "experts_held",
        "router_outputs")}
    whole.update(mamba_num_heads=8, num_attention_heads=4, num_key_value_heads=2, n_routed_experts=16)
    spec = nemotron_h.spec_from_config(whole)
    assert (spec.heads_held, spec.shared_expert_held, spec.experts_held, spec.arg("mamba").share) == (
        None, None, (0, 16), (8, 4))
    # a share of the query heads inside one key-value head's group, or whole groups
    sp = nemotron_h.spec_from_config(TORSO)
    held = solar_open2.GatedNopeAttention.held
    assert held(sp) == (2, 1) and held(dataclasses.replace(sp, heads_held=(0, 1))) == (1, 1)
    assert held(dataclasses.replace(sp, heads_held=(0, 4))) == (4, 2)
    with pytest.raises(ValueError, match="cuts a group"):
        held(dataclasses.replace(sp, heads_held=(1, 3)))


# ------------------------------------------------- the shares against the whole

def _uncut(ref):
    """(the uncut configuration at the toy widths, its seeded weights): every
    head, column and expert held."""
    cfg = dict(TORSO, mamba_num_heads=8, num_attention_heads=4, num_key_value_heads=2,
               n_routed_experts=16, experts_held=[0, 16], obs_shape=[44, 60, 5], num_actions=6)
    for key in ("heads_held", "mamba_heads_held", "shared_expert_held"):
        cfg.pop(key)
    return cfg, ref.make_weights(jax.random.PRNGKey(7), cfg)


def test_the_shares_add_up_to_the_uncut_reference_layers(ref):
    """The 4 head shares x the expert shares against the uncut reference's
    layers: a Mamba-2 layer by groups (each chip of the TP group one group of
    two heads), the attention layer by query heads (two chips a key-value
    head), the shared expert by columns, the routed experts by range; router,
    latent projections and norms counted once.  The program's modules compute
    the shares, on slices of the uncut weights."""
    cfg, weights = _uncut(ref)
    u = _u(5)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        # Mamba-2, layer_0: heads [2i, 2i + 2) = group i; W_in's columns z | x | B | C | dt
        p = weights["layer_0"]
        want = ref.mamba(u, p, cfg, f32, lambda x: x)
        inner, gn = 8 * 16, 4 * 16
        total = 0.0
        for i in range(4):
            x_cols = np.r_[2 * i * 16:(2 * i + 2) * 16]
            cols = np.concatenate([x_cols, inner + x_cols, 2 * inner + np.r_[i * 16:(i + 1) * 16],
                                   2 * inner + gn + np.r_[i * 16:(i + 1) * 16],
                                   2 * inner + 2 * gn + np.r_[2 * i:2 * i + 2]])
            mixed = cols[32:96] - inner
            part = {"w_in": p["w_in"][:, cols], "conv_kernel": p["conv_kernel"][mixed],
                    "conv_bias": p["conv_bias"][mixed], "A_log": p["A_log"][2 * i:2 * i + 2],
                    "dt_bias": p["dt_bias"][2 * i:2 * i + 2], "D": p["D"][2 * i:2 * i + 2],
                    "norm": p["norm"][x_cols], "w_out": p["w_out"][x_cols]}
            spec = nemotron_h.spec_from_config(dict(
                TORSO, mamba_num_heads=2, mamba_heads_held=[2 * i, 2 * i + 2], num_attention_heads=1,
                heads_held=[i, i + 1]))
            assert spec.arg("mamba").share == (2, 1)
            share = granite_hybrid.Mamba2(spec, "mamba", f32, f32).apply({"params": part}, u)
            np.testing.assert_allclose(                       # the reference on the same slice
                np.asarray(share), np.asarray(ref.mamba(u, part, cfg, f32, lambda x: x)), atol=2e-5)
            total = total + share
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)

        # attention, layer_5 of the held seven: query head i on key-value head i // 2
        p = weights["layer_5"]
        want = ref.attention(u, p, cfg, f32, lambda x: x)
        total = 0.0
        for i in range(4):
            q, kv = np.r_[i * 16:(i + 1) * 16], np.r_[i // 2 * 16:(i // 2 + 1) * 16]
            part = {"w_q": p["w_q"][:, q], "w_k": p["w_k"][:, kv], "w_v": p["w_v"][:, kv],
                    "w_o": p["w_o"][q]}
            spec = nemotron_h.spec_from_config(dict(
                TORSO, num_attention_heads=1, heads_held=[i, i + 1], mamba_num_heads=2,
                mamba_heads_held=[2 * i, 2 * i + 2]))
            total = total + solar_open2.GatedNopeAttention(spec, "attention", f32, f32).apply(
                {"params": part}, u)
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)

        # the expert layer, layer_1: four column shares of the shared expert, four expert ranges
        p = weights["layer_1"]
        want, _ = ref.moe(u, p, cfg, f32, lambda x: x)
        shared = sum(ref.shared_expert(u, dict(p, shared_w1=p["shared_w1"][:, lo:lo + 16],
                                               shared_w2=p["shared_w2"][lo:lo + 16]), cfg, f32, lambda x: x)
                     for lo in range(0, 64, 16))
        total = shared
        for lo in range(0, 16, 4):
            spec = nemotron_h.spec_from_config(dict(TORSO, experts_held=[lo, lo + 4]))
            part = {"router": p["router"], "expert_bias": p["expert_bias"], "w_down": p["w_down"],
                    "w_up": p["w_up"], "w1": p["w1"][lo:lo + 4], "w2": p["w2"][lo:lo + 4]}
            total = total + expert_torso.ExpertShare(spec, f32, f32).apply({"params": part}, u)
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-4)
        # and the held columns are the program's shared expert: a Relu2 of that width
        spec = nemotron_h.spec_from_config(TORSO)
        assert spec.shared_expert_held == (0, 32)
        mine = expert_torso.Relu2(32, f32, f32).apply(
            {"params": {"w1": p["shared_w1"][:, :32], "w2": p["shared_w2"][:32]}}, u)
        both = sum(ref.shared_expert(u, dict(p, shared_w1=p["shared_w1"][:, lo:lo + 16],
                                             shared_w2=p["shared_w2"][lo:lo + 16]), cfg, f32, lambda x: x)
                   for lo in (0, 16))
        np.testing.assert_allclose(np.asarray(mine), np.asarray(both), atol=2e-5)


def test_the_like_blocks_are_one_scanned_body(built):  # noqa: F811
    """The two ``M E`` blocks of the toy (the cell's four) are one scanned run
    under ``layers_0_1``; the mixer alone and the attention block stand apart."""
    params = built.params["params"]
    assert params["layers_0_1"]["mamba"]["w_in"].shape[0] == 2
    assert params["layers_0_1"]["moe"]["w1"].shape == (2, 4, 32, 48)
    assert expert_torso.layer_runs(built.net().spec.layers) == [
        (0, 2, ("mamba", "moe")), (2, 1, ("mamba", "none")), (3, 1, ("attention", "moe"))]


@pytest.mark.parametrize("lost", [None, "groups", "relu2", "gate_factor"])
def test_the_chips_numeric_check_passes_here_and_fails_on_a_lost_mechanism(monkeypatch, lost):
    """``chip_smoke.py``'s leg ``nemotron_kernels`` at the toy widths: it passes
    on the program as it is; a scan that gives every head group 0's ``B`` and
    ``C``, experts of ``silu`` in place of ``relu^2`` and gates that
    lost their factor each fail it."""
    import chip_smoke

    spec = nemotron_h.spec_from_config(TORSO)
    if lost == "groups":
        whole = chunked_scan.scan_chunks
        monkeypatch.setattr(granite_hybrid, "scan_chunks", lambda x, dt, a, b, c, d: whole(
            x, dt, a, *(jnp.broadcast_to(v[:, :, :1], v.shape) for v in (b, c)), d))
        with pytest.raises(AssertionError, match="from the Mamba-2 mixer written out"):
            chip_smoke.grouped_mixer_against_plain(rows=1, tokens=40, spec=spec)
        return
    if lost == "relu2":
        def silu_experts(xs, w1, w2, sizes, rule):
            h = jax.lax.ragged_dot(xs, w1, sizes)
            return h, jax.nn.silu(h), jax.lax.ragged_dot(jax.nn.silu(h), w2, sizes)

        monkeypatch.setattr(expert_torso, "_products", silu_experts)
        with pytest.raises(AssertionError, match="from the LatentMoE layer written out"):
            chip_smoke.latent_experts_against_plain(rows=1, tokens=40, spec=spec)
        return
    if lost == "gate_factor":
        whole = expert_torso.route
        monkeypatch.setattr(expert_torso, "route", lambda s, b, spec, kept=None: whole(
            s, b, dataclasses.replace(spec, routed_scaling_factor=1.0)))
        with pytest.raises(AssertionError, match="from the host's"):
            chip_smoke.gates_against_sorting(tokens=512, spec=spec)
        return
    mixer = chip_smoke.grouped_mixer_against_plain(rows=1, tokens=40, spec=spec)
    layer = chip_smoke.latent_experts_against_plain(rows=1, tokens=40, spec=spec)
    for readings in (mixer, layer):
        assert readings.pop("near") <= chip_smoke.NEMOTRON_REL
        assert len(readings) == 2 and all(v >= chip_smoke.NEMOTRON_REL_LOST for v in readings.values())
    routed = chip_smoke.gates_against_sorting(tokens=512, spec=spec)
    assert routed["differing"] == 0 and routed["gates_max_abs"] <= chip_smoke.GATE_ABS

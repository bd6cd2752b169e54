"""What every torso's Q-network is held to, stated once.

``config.TORSO_NETWORKS`` names the network kinds whose torso is a stack of
blocks.  ``ROWS`` has a row a name: the toy block the kind is built at here,
the observation it reads, and what its structure, its scopes, its counters,
its float32 leaves, its configuration files and its reference are.  ``of(name)``
makes the class whose test methods are the contract's cases on that row; a
torso's own file, ``tests/test_<name>.py``, subclasses it beside the tests of
the torso's own mechanism, so a file stays what one worker of the test run
takes.  ``built`` is the module-scoped fixture those files import: one network
a compute type, one jitted ``init`` and its parameters, one jitted ``apply``,
one train step run once, for every case and every mechanism test that reads
them.  A case that changes parameters copies the tree.

A new torso: a row here, ``class TestContract(contract.of("<name>"))`` in its
file, and its own mechanism's tests (``tests/test_config.py`` fails a name
without both).  Not collected by itself: no ``test_`` prefix.
"""
import dataclasses
import functools
import importlib
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "benchmark"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from ape_x_dqn_tpu.config import HISTORY_NETWORKS, TORSO_NETWORKS, ApexConfig, load_config, network_kwargs
from ape_x_dqn_tpu.learner.train_step import (
    StepMetrics, build_train_step, init_train_state, make_optimizer,
)
from ape_x_dqn_tpu.models import (
    dueling, expert_torso, granite_hybrid, kanana_moe, lfm2_moe, ling_hybrid, nemotron_h, olmo_hybrid,
    solar_open2,
)
from ape_x_dqn_tpu.models.dueling import build_greedy_apply, build_network
from ape_x_dqn_tpu.ops.pallas import blocked_attention as blocked
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch, TrainState
from ape_x_dqn_tpu.utils import profiling

# ------------------------------------------------------------- the toy blocks

LFM2 = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, conv_L_cache=3, norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6}, layer_types=["conv", "conv", "full_attention", "conv"],
    num_dense_layers=1, num_experts=2, router_outputs=4, experts_held=[0, 2],
    num_experts_per_tok=2, layers_held=[0, 2, 3], channels=[8, 8, 8], hidden=32,
)
# the two-layer block the other torsos' cases build beside theirs
LFM2_TWO_LAYERS = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, conv_L_cache=3, norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
    layer_types=["conv", "full_attention"], num_dense_layers=1, num_experts=2,
    router_outputs=4, num_experts_per_tok=2)

LAGUNA_ROPE = {"full_attention": dict(rope_theta=500000, rope_type="yarn", factor=128,
                                      original_max_position_embeddings=8192, beta_slow=1, beta_fast=32,
                                      attention_factor=1.4852030263919618, partial_rotary_factor=0.5),
               "sliding_attention": dict(rope_type="default", rope_theta=10000, partial_rotary_factor=1)}
LAGUNA = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
    sliding_window=8, num_experts=4, router_outputs=16, experts_held=[0, 4],
    num_experts_per_tok=3, norm_topk_prob=True, moe_routed_scaling_factor=2.5,
    layer_types=["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    rope_parameters=LAGUNA_ROPE, layers_held=[0, 1, 2, 3, 4], channels=[8, 8, 8], hidden=32,
)

GRANITE = dict(
    hidden_size=64, shared_intermediate_size=128, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, rms_norm_eps=1e-5, attention_multiplier=0.0625, embedding_multiplier=12,
    residual_multiplier=0.22, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=8, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=16, num_local_experts=0,
    num_experts_per_tok=0, position_embedding_type="nope", logits_scaling=8, vocab_size=100352,
    layer_types=["mamba"] * 2 + ["attention"] + ["mamba"] * 2 + ["mamba"] * 5, num_hidden_layers=5,
    layers_held=[0, 1, 2, 3, 4], channels=[8, 8, 8], hidden=32,
)

SOLAR = dict(
    model_type="solar_open2", hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16, num_heads=8, num_kv_heads=None),
    rms_norm_eps=1e-5, num_hidden_layers=4, gqa_layers=[0, 4, 8], gqa_interval=3,
    first_k_dense_replace=0, use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, n_routed_experts=4, router_outputs=8, experts_held=[2, 6],
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1, num_experts_per_tok=2,
    kda_chunk_size=16, channels=[8, 8, 8], hidden=32, expert_bias_update_rate=0.05,
)

LING = dict(
    model_type="bailing_hybrid", hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_shared_experts=1, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, q_lora_rank=None, rope_theta=6000000, rope_interleave=True,
    short_conv_kernel_size=4, rms_norm_eps=1e-6, layer_group_size=3, num_hidden_layers=4,
    first_k_dense_replace=1, published=dict(num_hidden_layers=12, first_k_dense_replace=2),
    layers_held=[1, 2, 3, 4], no_kda_lora=True, kda_safe_gate=True, kda_lower_bound=-5,
    num_kv_heads_for_linear_attn=0, num_experts=4, router_outputs=16, experts_held=[4, 8],
    n_group=4, topk_group=2, norm_topk_prob=True, routed_scaling_factor=2.5,
    num_experts_per_tok=2, score_function="sigmoid", moe_router_enable_expert_bias=True,
    expert_swiglu_limit_list=[0] * 10 + [4, 4], share_expert_swiglu_limit_list=[0] * 11 + [5],
    kda_chunk_size=16, channels=[8, 8, 8], hidden=32, expert_bias_update_rate=0.05,
)

# four of the pattern's layers paired into like blocks, a mixer alone, the attention block; half
# of the published Mamba-2 heads (two groups of four), query heads and shared columns, four experts
NEMOTRON = dict(
    model_type="nemotron_h", hidden_size=64, intermediate_size=48, moe_intermediate_size=48,
    moe_latent_size=32, moe_shared_expert_intermediate_size=64, n_shared_experts=1,
    hybrid_override_pattern="ME*EMEMEM*EM", num_hidden_layers=7, layers_held=[4, 5, 6, 7, 8, 9, 10],
    published=dict(num_hidden_layers=12, n_routed_experts=16, mamba_num_heads=8,
                   num_attention_heads=4, num_key_value_heads=2),
    mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, conv_kernel=4, chunk_size=16,
    n_groups=4, expand=2, num_attention_heads=2, num_key_value_heads=1, head_dim=16,
    heads_held=[0, 2], mamba_heads_held=[0, 4], shared_expert_held=[0, 32],
    n_routed_experts=4, router_outputs=16, experts_held=[4, 8], num_experts_per_tok=3,
    n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=5.0,
    mlp_hidden_act="relu2", layer_norm_epsilon=1e-5, channels=[8, 8, 8], hidden=32,
    expert_bias_update_rate=0.05,
)

# three heads (no power of two) of 16 in the full layer; keys of 12 and values of 24 in the linear ones
OLMO = dict(
    model_type="olmo_hybrid", hidden_size=48, intermediate_size=96, num_attention_heads=3,
    num_key_value_heads=3, rms_norm_eps=1e-6, attention_bias=False, hidden_act="silu",
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2, num_hidden_layers=4,
    published=dict(num_hidden_layers=8), layers_held=[0, 1, 2, 3], linear_num_key_heads=3,
    linear_num_value_heads=3, linear_key_head_dim=12, linear_value_head_dim=24,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
    linear_chunk_size=16, channels=[8, 8, 8], hidden=32,
)

# four heads of 16 + 8 / 16 on a latent of 24, every layer; eight outputs, two held, three a token
KANANA = dict(
    model_type="deepseek_v3", hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    n_shared_experts=2, num_attention_heads=4, num_key_value_heads=4, head_dim=8, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None, rope_theta=1000000,
    rope_interleave=True, rope_scaling=None, rms_norm_eps=1e-6, num_hidden_layers=4,
    first_k_dense_replace=1, moe_layer_freq=1, published=dict(num_hidden_layers=12, n_routed_experts=8),
    layers_held=[0, 1, 2, 3], n_routed_experts=2, router_outputs=8, experts_held=[2, 4], n_group=1,
    topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.448, num_experts_per_tok=3,
    scoring_func="sigmoid", topk_method="noaux_tc", attention_bias=False, channels=[8, 8, 8],
    hidden=32, expert_bias_update_rate=0.05,
)


def obs(key, rows=2, shape=(44, 60, 5)):   # 5 frames of 2 x 4 positions: 40 tokens
    return jax.random.randint(key, (rows, *shape), 0, 256).astype(jnp.uint8)


def batch_of(x):
    n = x.shape[0]
    return PrioritizedBatch(
        transition=NStepTransition(obs=x, action=jnp.arange(n) % 6, reward=jnp.ones(n),
                                   discount=jnp.full((n,), 0.9), next_obs=x[::-1]),
        indices=jnp.arange(n), is_weights=jnp.linspace(0.4, 1.0, n))


def network(name, compute=jnp.float32, **over):
    """The kind ``name`` at its row's toy block (``over`` changes keys of it)."""
    return build_network(name, 6, torso=dict(ROWS[name].torso, **over), channels=(8, 8, 8),
                         hidden=32, compute_dtype=compute)


def pulled(fn):
    """jitted ``(cot, *args) -> (fn(*args), the cotangents of args from cot)``:
    a comparison's two sides are one program each, not a program a primitive."""
    def run(cot, *args):
        out, pull = jax.vjp(fn, *args)
        return out, pull(cot)
    return jax.jit(run)


def init_of(net, key, x):
    """``net.init`` as one program: eager, an init is a hundred one-primitive
    programs built anew by every test that calls it."""
    return jax.jit(net.init)(key, x)


# -------------------------------------------------------------------- the rows

@dataclasses.dataclass(frozen=True)
class Row:
    """What a torso's file stated for itself until PR 46."""
    name: str
    torso: dict
    class_name: str                  # what the trainer builds
    committed: str                   # the file under configs/ that is the cell's
    config: object                   # (row, spec at the toy block, committed config, its spec)
    counters: object                 # (what ``Built.stepped`` holds): the train step's counters
    stepped: object = None           # Built -> the step run once, for a torso with no reference
    kept_float32: tuple = ()         # the leaves a lower target keeps float32, by their last key
    float32_leaves: tuple = ("router", "expert_bias")
    obs_shape: tuple = (44, 60, 5)
    rows: int = 2
    structure: object = None         # Built -> None, the structure's assertions
    reference: str = None            # the module under benchmark/reference/
    flags: tuple = ()                # the reference's mechanism flags, each moves Q
    loads: object = None             # what the reference's forward counts beside Q: its assertions
    bf16_tolerance: float = None
    grad_tolerance: float = 1e-3     # of a leaf's norm, the gradients against the reference's
    bias_moved: tuple = ()           # the reference's layers whose expert bias the rule moves
    others: object = None            # () -> None: the other torsos are as they were
    parts_at: slice = None           # where profiling.PARTS names this torso's parts
    parts: tuple = ()
    scopes: tuple = ()               # torso:<part> in the differentiated program's text
    scope_paths: tuple = ()          # whole strings there
    scopes_absent: tuple = ()
    walked_back: str = None          # the part the backward walk names more than ten times
    compiled_part: str = None        # a part the compiled forward's readers find
    scoped: object = None            # Built -> None, where the check is the torso's own

    @property
    def history(self) -> bool:
        return self.name in HISTORY_NETWORKS

    @property
    def cfg(self) -> dict:
        """What the benchmark's driver adds to the torso's keys for its reference."""
        return dict(self.torso, obs_shape=list(self.obs_shape), num_actions=6, batch_size=4,
                    optimizer="rmsprop", learning_rate=6.25e-5, rmsprop_decay=0.95,
                    rmsprop_eps=1.5e-7, max_grad_norm=40.0, loss="squared")


class Built:
    """A row's network, parameters and programs, each made when first asked for."""

    def __init__(self, row: Row):
        self.row = row
        self._nets, self._applies = {}, {}

    def net(self, compute=jnp.float32):
        if compute not in self._nets:
            self._nets[compute] = network(self.row.name, compute)
        return self._nets[compute]

    def apply(self, compute=jnp.float32):
        """jitted ``(params, x) -> net.apply(params, x)`` at ``compute``."""
        if compute not in self._applies:
            self._applies[compute] = jax.jit(self.net(compute).apply)
        return self._applies[compute]

    @functools.cached_property
    def x(self):
        return obs(jax.random.PRNGKey(2), self.row.rows, self.row.obs_shape)

    @functools.cached_property
    def params(self):
        """``{"params": ...}`` of the float32 network: read, never written."""
        return init_of(self.net(), jax.random.PRNGKey(3), self.x)

    @functools.cached_property
    def apply_sown(self):
        return jax.jit(lambda p, x: self.net().apply(p, x, mutable=["routing"]))

    @functools.cached_property
    def applied(self):
        """(the network's output, what it sowed) on ``params`` and ``x``."""
        return self.apply_sown(self.params, self.x)

    @functools.cached_property
    def ref(self):
        return importlib.import_module(f"reference.{self.row.reference}")

    @functools.cached_property
    def _make_weights(self):
        cfg = self.row.cfg
        return jax.jit(lambda k: self.ref.make_weights(k, cfg))

    def weights(self, seed: int):
        """The reference's seeded weights (one program for every seed)."""
        return self._make_weights(jax.random.PRNGKey(seed))

    @functools.cached_property
    def stepped(self):
        """One train step, run once: (net, x, state, batch, new_state, metrics)
        and, against a reference, what ``learner_step`` gives."""
        return (_stepped_beside_the_reference if self.row.reference else self.row.stepped)(self)


@pytest.fixture(scope="module")
def built(request):
    """The ``Built`` of the row the module's ``TestContract`` names."""
    return Built(request.module.TestContract.row)


def _stepped_beside_the_reference(b: Built):
    ref, cfg = b.ref, b.row.cfg
    weights = b.weights(12)
    noise = np.random.default_rng(21)      # a target apart from the online weights: any will do
    target = jax.tree_util.tree_map(
        lambda w: jnp.asarray(np.asarray(w) + 0.05 * np.std(w) * noise.standard_normal(w.shape, np.float32)),
        weights)
    x = obs(jax.random.fold_in(jax.random.PRNGKey(21), 1), rows=4)
    batch = batch_of(x)
    net = b.net()
    opt = make_optimizer("rmsprop", learning_rate=cfg["learning_rate"], rmsprop_decay=0.95,
                         rmsprop_eps=1.5e-7, max_grad_norm=40.0, second_moment_dtype=jnp.float32)
    own = lambda t: jax.tree_util.tree_map(lambda v: jnp.array(v, copy=True), t)  # noqa: E731
    params = own(ref.to_program_params(weights, cfg))
    nu0 = 1e-4
    opt_state = jax.tree_util.tree_map_with_path(
        lambda path, v: jnp.full_like(v, nu0) if any("nu" in str(p) for p in path) else v,
        opt.init(params))
    state = TrainState(params=params, target_params=own(ref.to_program_params(target, cfg)),
                       opt_state=opt_state, step=jnp.zeros((), jnp.int32), rng=jax.random.PRNGKey(0))
    step = build_train_step(net, opt, loss_kind="squared", sync_in_step=False, jit=True)
    t = batch.transition
    with jax.default_matmul_precision("highest"):
        new_state, metrics = step(state, batch)
        want_w, _, _, want_prio, want_loss = jax.jit(
            lambda w, tw, nu, rows: ref.learner_step(w, tw, nu, rows, cfg))(
            weights, target, jax.tree_util.tree_map(lambda w: jnp.full(w.shape, nu0), weights),
            dict(obs=t.obs, next_obs=t.next_obs, action=t.action, reward=t.reward,
                 discount=t.discount, is_weights=batch.is_weights))
    return types.SimpleNamespace(
        net=net, x=x, state=state, batch=batch, new_state=new_state, metrics=metrics,
        weights=weights, want_w=want_w, want_prio=want_prio, want_loss=want_loss,
        got_w=ref.from_program_params(new_state.params, cfg))


# ------------------------------------------------------------------- the cases

class _Every:
    """The cases every row has."""
    row: Row = None

    def test_the_trainers_loop_runs_the_network(self):
        """``runtime/single_process.py``'s loop, a few learner steps, through
        ``build_components``: the normal path builds and trains the network
        (a history torso on histories of ``env.frame_stack`` frames)."""
        from ape_x_dqn_tpu.runtime import SingleProcessDriver

        cfg = ApexConfig()
        cfg.env.name = "fake-atari"
        if self.row.history:
            cfg.env.frame_stack = 4
        cfg.network = self.row.name
        cfg.torso = dict(self.row.torso)
        cfg.actor.num_actors = 2
        cfg.actor.flush_every = 8
        cfg.learner.min_replay_mem_size = 32
        cfg.learner.replay_sample_size = 4
        cfg.replay.capacity = 256
        driver = SingleProcessDriver(cfg.validate())
        results = driver.run(learner_steps=3)
        assert driver.learner_step >= 3
        learned = [r.loss for r in results if r.learner_step > 0]
        assert len(learned) >= 3 and all(np.isfinite(v) for v in learned), learned
        assert type(driver.network).__name__ == self.row.class_name

    def test_a_lower_target_keeps_the_float32_leaves(self, built):
        """A bfloat16 target net: every leaf is bfloat16 but the row's (the
        decays, the router's scores and bias), found by their own key
        (``Dense_0``'s kernel is no ``D``).  The state's types alone are read,
        so it is traced and not run."""
        row, net = self.row, built.net(jnp.bfloat16)
        assert net.float32_leaves == row.float32_leaves
        state = jax.eval_shape(
            lambda k: init_train_state(net, make_optimizer("rmsprop", learning_rate=1e-4), k,
                                       obs(jax.random.PRNGKey(1), 1, row.obs_shape),
                                       target_dtype=jnp.bfloat16), jax.random.PRNGKey(0))
        kept = set()
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.target_params):
            name = path[-1].key
            if name in row.kept_float32:
                kept.add(name)
                assert leaf.dtype == jnp.float32, jax.tree_util.keystr(path)
            else:
                assert leaf.dtype == jnp.bfloat16, jax.tree_util.keystr(path)
        assert kept == set(row.kept_float32)

    def test_the_parts_are_scoped(self, built):
        (self.row.scoped or _scoped)(built)

    def test_config_carries_the_torso_and_the_committed_file_is_the_cells(self):
        row = self.row
        assert tuple(dueling.TORSO_KINDS) == TORSO_NETWORKS and row.name in TORSO_NETWORKS
        cfg = ApexConfig()
        cfg.network = row.name
        with pytest.raises(ValueError, match="lfm2_moe | laguna_moe" if row.history else "torso"):
            cfg.validate()                      # a torso's kind needs its block
        cfg.torso = dict(row.torso)
        if row.history:
            with pytest.raises(ValueError, match="frame_stack"):
                cfg.validate()                  # a history needs more than one frame
            cfg.env.frame_stack = 5
        kw = network_kwargs(cfg.validate())
        assert kw["channels"] == (8, 8, 8) and kw["hidden"] == 32
        committed = load_config(os.path.join(ROOT, "configs", row.committed))
        spec = build_network(committed.network, 18, **network_kwargs(committed)).spec
        if row.history:
            assert committed.env.frame_stack == 32 and spec.frame_history
        row.config(row, build_network(cfg.network, 6, **kw).spec, committed, spec)

    def test_the_train_step_carries_the_counters(self, built):
        self.row.counters(built.stepped)


class _Structure:
    def test_the_network_has_the_issues_structure(self, built):
        net = built.net()
        if self.row.history:
            assert net.tokens_of(built.x.shape) == 40
        self.row.structure(built)


class _Reference:
    def test_the_network_is_the_reference(self, built):
        """Forward in float32 (1e-4 of |Q|: sums in another order, the scan in
        chunks against a token a step) and at the stated precision; the
        gradients of sum(Q^2) leaf by leaf, 1e-3 of each leaf's norm (the
        row's ``grad_tolerance``); every mechanism flag of the reference
        moves Q."""
        row, ref, cfg = self.row, built.ref, self.row.cfg
        weights = built.weights(11)
        x = obs(jax.random.PRNGKey(5), rows=4)
        with jax.default_matmul_precision("highest"):
            want, loads = jax.jit(lambda w: ref.forward(w, x, cfg))(weights)
            if row.loads:                   # a torso without experts counts nothing
                row.loads(loads)
            scale = float(jnp.std(want)) + float(jnp.mean(jnp.abs(want)))
            program = ref.to_program_params(weights, cfg)
            for compute, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, row.bf16_tolerance)):
                got = built.apply(compute)(program, x)[2]
                assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, compute
            assert set(row.flags) >= set(getattr(ref, "FLAGS", ()))      # every flag the reference names
            for flag in row.flags:
                other, _ = jax.jit(lambda w: ref.forward(w, x, dict(cfg, **{flag: True})))(weights)
                assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * scale, flag
            net = built.net()
            wanted = jax.jit(jax.grad(lambda w: jnp.sum(ref.forward(w, x, cfg)[0] ** 2)))(weights)
            got = ref.from_program_params(
                jax.jit(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2)))(program), cfg)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree_util.tree_leaves(wanted)):
            name = jax.tree_util.keystr(path)
            assert float(jnp.linalg.norm(a - b)) <= row.grad_tolerance * float(jnp.linalg.norm(b)) + 1e-7, name
            assert float(jnp.linalg.norm(b)) > 0 or (row.bias_moved and "expert_bias" in name), name

    def test_one_learner_step_is_the_references(self, built):
        """Loss, priorities and the parameters after one RMSProp step of the
        program's train step, float32 compute, against ``learner_step`` (the
        balancing rule's move of the bias among them)."""
        s = built.stepped
        assert float(s.metrics.loss) == pytest.approx(float(s.want_loss), rel=1e-4)
        np.testing.assert_allclose(np.asarray(s.metrics.priorities), np.asarray(s.want_prio), rtol=2e-4)
        num = den = 0.0
        for a, b, old in zip(*(jax.tree_util.tree_leaves(tree)
                               for tree in (s.got_w, s.want_w, s.weights))):
            num += float(jnp.sum(jnp.square((a - old) - (b - old))))
            den += float(jnp.sum(jnp.square(b - old)))
        assert den > 0 and np.sqrt(num / den) < 2e-3
        for i in self.row.bias_moved:        # the balancing rule moved every routing layer's bias
            moved = s.got_w[f"layer_{i}"]["expert_bias"] - s.weights[f"layer_{i}"]["expert_bias"]
            assert float(jnp.max(jnp.abs(moved))) > 1e-3
            np.testing.assert_allclose(np.asarray(s.got_w[f"layer_{i}"]["expert_bias"]),
                                       np.asarray(s.want_w[f"layer_{i}"]["expert_bias"]), atol=1e-6)


class _Others:
    def test_the_other_torsos_are_as_they_were(self):
        self.row.others()


def of(name: str) -> type:
    """The contract's cases on ``ROWS[name]``: the base of a file's ``TestContract``."""
    row = ROWS[name]
    bases = tuple(cases for cases, has in (
        (_Structure, row.structure), (_Reference, row.reference), (_Others, row.others)) if has)
    return type(f"Contract_{name}", (*bases, _Every), {"row": row})


def _scoped(b: Built):
    """The text of the differentiated network names the row's parts under
    ``torso:``, the kernels and the scans inside their mixers, forward and
    in the backward walk, and none of the other torsos'."""
    row = b.row
    assert profiling.PARTS[row.parts_at] == row.parts
    net, x = b.net(), b.x
    text = jax.jit(jax.grad(lambda p: jnp.sum(net.apply(p, x)[2] ** 2))).lower(b.params).as_text(
        debug_info=True)
    for part in row.scopes:
        assert f"torso:{part}" in text, part
    for path in row.scope_paths:
        assert path in text, path
    if row.walked_back:
        assert text.count(f"torso:{row.walked_back}") > 10      # the backward walk too
    for part in row.scopes_absent:
        assert f"torso:{part}" not in text, part
    if row.compiled_part:
        parts = profiling.hlo_parts(b.apply().lower(b.params, x).compile().as_text())
        assert row.compiled_part in set(parts.values())


# ----------------------------------------------------------------- lfm2_moe

def train_pieces(net, batch=4, side=52):
    """(the jitted train step, a state with a bfloat16 target and second
    moment, a batch) on observations of ``side`` x ``side`` x 4."""
    draw = lambda key, rows: obs(key, rows, (side, side, 4))  # noqa: E731
    opt = make_optimizer("rmsprop", second_moment_dtype=jnp.bfloat16)
    state = jax.jit(lambda k: init_train_state(net, opt, k, draw(jax.random.PRNGKey(1), 1),
                                               target_dtype=jnp.bfloat16))(jax.random.PRNGKey(0))
    k = jax.random.PRNGKey(7)
    t = NStepTransition(
        obs=draw(k, batch), action=jnp.arange(batch, dtype=jnp.int32) % 6,
        reward=jnp.ones((batch,)), discount=jnp.full((batch,), 0.97),
        next_obs=draw(jax.random.fold_in(k, 1), batch))
    b = PrioritizedBatch(transition=t, indices=jnp.arange(batch, dtype=jnp.int32),
                         is_weights=jnp.ones((batch,)))
    return jax.jit(build_train_step(net, opt, loss_kind="squared", jit=False)), state, b


def _lfm2_stepped(b: Built):
    net = b.net()
    step, state, batch = train_pieces(net)
    new_state, metrics = step(state, batch)
    return types.SimpleNamespace(net=net, x=batch.transition.obs, step=step, state=state,
                                 batch=batch, new_state=new_state, metrics=metrics)


def _lfm2_counters(s):
    """The step reports routing, and the bias moved by the rule on the two
    online forwards' loads and by nothing else (no gradient reaches it,
    RMSProp leaves it)."""
    net, state, new, metrics = s.net, s.state, s.new_state, s.metrics
    moe = state.params["params"]["layer_1"]["moe"]
    assert np.isfinite(float(metrics.loss))
    # three forwards of 4 rows x 9 tokens x 2 a token x 2 layers
    assert 0 < float(metrics.routing["held_pairs"]) <= 3 * 144
    t = s.batch.transition
    sown = jax.jit(lambda p, o: net.apply(p, o, mutable=["routing"])[1])
    loads = sum(sown(state.params, o)["routing"]["layer_1"]["moe"]["load"][0]
                for o in (t.obs, t.next_obs)).astype(jnp.float32)
    want = moe["expert_bias"] - lfm2_moe.BIAS_UPDATE_RATE * jnp.clip(loads / jnp.mean(loads) - 1, -1, 1)
    np.testing.assert_allclose(
        np.asarray(new.params["params"]["layer_1"]["moe"]["expert_bias"]), np.asarray(want),
        atol=1e-7)
    assert float(jnp.max(jnp.abs(want - moe["expert_bias"]))) > 1e-3
    assert not np.array_equal(np.asarray(new.params["params"]["w_tok"]),
                              np.asarray(state.params["params"]["w_tok"]))


def _lfm2_scoped(b: Built):
    """The train step's text names every part under ``torso:``, forward and
    backward, and the ``stage:`` readers still see ``forward``."""
    lfm2_parts = ("stem", "mixer", "router", "experts", "dense_ffn", "head")
    assert profiling.PARTS[:6] == lfm2_parts  # the rest are another torso's
    with pytest.raises(ValueError):
        profiling.part("torso")
    s = b.stepped
    lowered = s.step.lower(s.state, s.batch)
    text = lowered.as_text(debug_info=True)
    for part in lfm2_parts:
        assert f"torso:{part}" in text, part
    assert "transpose(jvp(stage:forward))" in text and "torso:experts" in text
    compiled = lowered.compile().as_text()
    stages = profiling.hlo_stages(compiled)
    assert {"forward", "backward"} <= set(stages.values())
    parts = profiling.hlo_parts(compiled)
    assert {"mixer", "router", "experts", "dense_ffn"} <= set(parts.values())
    # a part is read beside its stage: the experts' instructions are the
    # forward's and the backward's
    assert {stages[name] for name, p in parts.items() if p == "experts"} >= {"forward", "backward"}
    # the expert layers' hand-written backward pass: one loop a layer, and every
    # instruction of it that is named at all is the backward's and a part's
    loops = [m.groups() for m in re.finditer(
        r"%?([\w.\-]+) = [^\n]*? while\([^\n]*?condition=%?([\w.\-]+), body=%?([\w.\-]+)",
        compiled) if stages[m.group(1)] == "backward"]
    assert len(loops) == 2 and all(parts[loop] == "router" for loop, _, _ in loops)
    found = {name: set() for _, cond, body in loops for name in (cond, body)}
    computation = None
    for line in compiled.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            computation = head.group(1)
        elif computation in found and "op_name=" in line:
            name = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
            assert stages[name] == "backward" and parts.get(name) in ("router", "experts"), line
            found[computation].add(parts[name])
    assert all(found[body] == {"router", "experts"} for _, _, body in loops), found


def _lfm2_config(row, spec, committed, committed_spec):
    assert spec.experts_held == (0, 2)
    other = ApexConfig()
    other.torso = dict(row.torso)
    with pytest.raises(ValueError, match="torso"):
        other.validate()
    spec = committed_spec
    assert spec.hidden_size == 2048 and spec.router_outputs == 64 and spec.num_held == 8
    assert [op for op, _ in spec.layers] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert [f for _, f in spec.layers] == ["dense", "moe", "moe", "moe", "moe"]


# --------------------------------------------------------------- laguna_moe

def _laguna_structure(b: Built):
    net, x, params = b.net(), b.x, b.params["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layer_0", "layers_1_3", "layer_4", "w_tok", "final_norm"}
    assert "dense" in params["layer_0"] and "shared_expert" in params["layers_1_3"]
    assert set(params["layer_4"]["moe"]) == {"router", "w13", "w2"}      # no expert bias
    assert params["layers_1_3"]["sliding_attention"]["w_q"].shape == (3, 64, 96)
    (_, _, q), sown = b.applied
    assert q.shape == (2, 6) and bool(jnp.all(jnp.isfinite(q)))
    loads = np.concatenate([np.asarray(v).reshape(-1, 16)
                            for v in jax.tree_util.tree_leaves(sown["routing"])])
    assert loads.shape == (4, 16) and (loads.sum(-1) == 2 * 40 * 3).all()
    actions, served = build_greedy_apply(net)({"params": params}, x)
    np.testing.assert_array_equal(np.asarray(actions), np.argmax(np.asarray(served), -1))


def _laguna_stepped(b: Built):
    net = b.net()
    opt = make_optimizer("rmsprop", learning_rate=1e-4)
    x = obs(jax.random.PRNGKey(6), rows=4)
    state = jax.jit(lambda k: init_train_state(net, opt, k, x[:1]))(jax.random.PRNGKey(7))
    batch = PrioritizedBatch(
        transition=NStepTransition(obs=x, action=jnp.zeros(4, jnp.int32), reward=jnp.ones(4),
                                   discount=jnp.full((4,), 0.9), next_obs=x[::-1]),
        indices=jnp.arange(4), is_weights=jnp.ones(4))
    step = build_train_step(net, opt, loss_kind="squared", sync_in_step=False, jit=True)
    new_state, metrics = step(state, batch)
    return types.SimpleNamespace(net=net, x=x, state=state, batch=batch, new_state=new_state,
                                 metrics=metrics)


def _laguna_counters(s):
    net, x, metrics = s.net, s.x, s.metrics
    assert bool(jnp.isfinite(metrics.loss))
    full, window = 40 * 41 // 2, 8 * 9 // 2 + 32 * 8
    want = net.attention_metrics(x.shape)
    assert want["pairs_in_mask_full"] == 4 * 2 * full
    assert want["pairs_in_mask_window"] == 4 * 3 * window
    # 40 tokens lie in one block of either kind's plan, visited by 4 heads on
    # two layers and by 6 on three; the kernels compute the whole block
    assert want["blocks_visited_full"] == want["blocks_total_full"] == 4 * 2 * 4
    assert want["blocks_visited_window"] == want["blocks_total_window"] == 4 * 3 * 6
    for kind, layers, span, group in (("full", 2, None, 2), ("window", 3, 8, 3)):
        plan = blocked.plan(40, span, group)       # 4 and 6 query heads on 2 key-value heads
        assert want[f"pairs_computed_{kind}"] == 4 * layers * plan.block_q * plan.block_kv
        assert want[f"pairs_computed_{kind}"] > want[f"pairs_in_mask_{kind}"]
    assert {k: float(v) for k, v in metrics.attention.items()} == {
        k: 3.0 * v for k, v in want.items()}
    assert float(metrics.routing["held_pairs"]) > 0
    assert float(metrics.routing["rows_walked"]) >= float(metrics.routing["held_pairs"])
    assert StepMetrics(loss=0, mean_abs_td=0, max_abs_td=0, priorities=0, mean_q=0).attention is None
    # a network with no blocked attention counts none
    lfm2 = build_network("lfm2_moe", 6, torso=LFM2_TWO_LAYERS, compute_dtype=jnp.float32)
    assert lfm2.attention_metrics((4, 52, 52, 4)) is None and lfm2.tokens_of((4, 52, 52, 4)) == 9


def _laguna_config(row, spec, committed, committed_spec):
    assert spec.experts_held == (0, 4)
    spec = committed_spec
    assert spec.hidden_size == 3072 and spec.router_outputs == 256 and spec.num_held == 8
    assert spec.shared_expert_intermediate_size == 1024 and spec.routed_scaling_factor == 2.5
    assert [op for op, _ in spec.layers] == (
        ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"])
    assert [f for _, f in spec.layers] == ["dense", "moe", "moe", "moe", "moe"]
    kinds = dict(spec.arg("attention"))
    assert (kinds["full_attention"].heads, kinds["sliding_attention"].heads) == (48, 72)
    assert kinds["sliding_attention"].window == 512


# ----------------------------------------------------------- granite_hybrid

def _granite_structure(b: Built):
    net, params = b.net(), b.params["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layers_0_1", "layer_2", "layers_3_4", "w_tok", "final_norm"}
    mamba = params["layers_0_1"]["mamba"]
    assert {k: v.shape[1:] for k, v in mamba.items()} == {
        "w_in": (64, 128 + 144 + 8), "conv_kernel": (144, 4), "conv_bias": (144,), "A_log": (8,),
        "dt_bias": (8,), "D": (8,), "norm": (128,), "w_out": (128, 64)}
    assert set(params["layer_2"]["attention"]) == {"w_q", "w_k", "w_v", "w_o"}   # no gate, no bias
    assert all("dense" in params[k] and "moe" not in params[k]
               for k in ("layers_0_1", "layer_2", "layers_3_4"))
    # Mamba-2's initialisation: -A in [1, 16], softplus(dt_bias) in [1e-3, 1e-1], D = 1
    a, dt = np.exp(np.asarray(mamba["A_log"])), np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (1 <= a).all() and (a <= 16).all() and (1e-3 <= dt).all() and (dt <= 1e-1 + 1e-6).all()
    assert (np.asarray(mamba["D"]) == 1).all()
    out, sown = b.applied
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2]))) and not sown
    spec = net.spec
    assert (spec.residual_multiplier, spec.token_multiplier) == (0.22, 12.0)
    assert spec.router_outputs == 0 and spec.num_held == 0 and spec.frame_history
    assert dict(spec.mixers) == {"attention": granite_hybrid.NopeAttention,
                                 "mamba": granite_hybrid.Mamba2}
    for bad in (dict(mamba_n_groups=2), dict(num_local_experts=4),
                dict(position_embedding_type="rope"), dict(mamba_expand=3)):
        with pytest.raises(ValueError):
            granite_hybrid.spec_from_config(dict(GRANITE, **bad))


def _granite_counters(s):
    """The scan's and the attention layer's from the shapes, no routing."""
    net, x, metrics = s.net, s.x, s.metrics
    # 40 tokens in chunks of 16: 3 chunks, 48 tokens walked, four layers, 4 rows, 3 forwards
    assert metrics.routing is None
    assert {k: float(v) for k, v in metrics.scan.items()} == {
        "chunks": 3 * 4 * 4 * 3.0, "tokens_padded": 3 * 4 * 4 * 48.0, "tokens": 3 * 4 * 4 * 40.0}
    assert float(metrics.attention["pairs_in_mask_full"]) == 3 * 4 * (40 * 41 // 2)
    assert net.scan_metrics(x.shape) == {"chunks": 48.0, "tokens_padded": 768.0, "tokens": 640.0}
    assert StepMetrics(loss=0, mean_abs_td=0, max_abs_td=0, priorities=0, mean_q=0).scan is None


def _granite_others():
    """The new spec fields default to what the two expert torsos had: no
    multiplier and no multiplication, the router's float32 leaves alone, the
    parameter trees by name, the counters of a step."""
    h, y = jnp.ones((2, 3), jnp.bfloat16), jnp.full((2, 3), 0.5, jnp.bfloat16)
    eqns = jax.make_jaxpr(lambda h, y: expert_torso._added(h, y, 1.0))(h, y).eqns
    assert [e.primitive.name for e in eqns] == ["add"]
    assert "mul" in [e.primitive.name for e in
                     jax.make_jaxpr(lambda h, y: expert_torso._added(h, y, 0.22))(h, y).eqns]
    lfm2 = build_network("lfm2_moe", 6, torso=LFM2_TWO_LAYERS, compute_dtype=jnp.float32)
    laguna = network("laguna_moe")
    for net in (lfm2, laguna):
        sp = net.spec
        assert (sp.residual_multiplier, sp.token_multiplier, sp.float32_leaves) == (1.0, 1.0, ())
        assert net.float32_leaves == ("router", "expert_bias") and sp.num_held > 0
        assert net.scan_metrics((2, 52, 52, 4)) is None
    x = obs(jax.random.PRNGKey(4))
    params = init_of(laguna, jax.random.PRNGKey(5), x)
    assert sorted(params["params"]) == sorted(
        ["Conv_0", "Conv_1", "Conv_2", "Dense_0", "Dense_1", "Dense_2", "Dense_3", "final_norm",
         "layer_0", "layers_1_3", "layer_4", "w_tok"])
    text = str(jax.make_jaxpr(lambda p: laguna.apply(p, x)[2])(params))
    assert "0.22" not in text and " 12.0" not in text
    _, sown = jax.jit(lambda p: laguna.apply(p, x, mutable=["routing"]))(params)
    assert float(laguna.routing_metrics(sown)["held_pairs"]) > 0
    with pytest.raises(KeyError):      # a config with experts still has to name them
        lfm2_moe.spec_from_config({k: v for k, v in LFM2_TWO_LAYERS.items() if k != "num_experts_per_tok"})


def _granite_config(row, spec, committed, committed_spec):
    assert TORSO_NETWORKS[2] == "granite_hybrid" and HISTORY_NETWORKS[:2] == ("laguna_moe", "granite_hybrid")
    assert spec.num_held == 0
    spec = committed_spec
    assert committed.learner.replay_sample_size == 8 and committed.learner.steps_per_call == 1
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "granite4h_q_l10.json")))
    assert spec == granite_hybrid.spec_from_config(cell)
    assert [op for op, _ in spec.layers] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    m = spec.arg("mamba")
    assert (spec.hidden_size, spec.intermediate_size, m.heads, m.head_dim, m.state, m.conv,
            m.chunk, m.inner) == (2048, 8192, 64, 64, 128, 4, 256, 4096)
    assert (spec.arg("num_attention_heads"), spec.arg("num_key_value_heads"), spec.arg("head_dim"),
            spec.arg("attention_multiplier")) == (32, 8, 64, 0.015625)


# -------------------------------------------------------------- solar_open2

def _solar_structure(b: Built):
    net, params = b.net(), b.params["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layer_0", "layers_1_3", "w_tok", "final_norm"}
    linear = params["layers_1_3"]["linear_attention"]
    assert {k: v.shape[1:] for k, v in linear.items()} == {
        "w_q": (64, 128), "w_k": (64, 128), "w_v": (64, 128), "conv_q": (128, 4),
        "conv_k": (128, 4), "conv_v": (128, 4), "w_f1": (64, 16), "w_f2": (16, 128),
        "A_log": (8,), "dt_bias": (128,), "w_b": (64, 8), "w_g1": (64, 16), "w_g2": (16, 128),
        "b_g": (128,), "norm": (16,), "w_o": (128, 64)}
    assert {k: v.shape for k, v in params["layer_0"]["full_attention"].items()} == {
        "w_q": (64, 128), "w_k": (64, 32), "w_v": (64, 32), "w_g": (64, 128), "w_o": (128, 64)}
    for run in ("layer_0", "layers_1_3"):                        # every layer routes
        assert set(params[run]) == {"operator_norm", "ffn_norm", "moe", "shared_expert",
                                    "full_attention" if run == "layer_0" else "linear_attention"}
        assert params[run]["moe"]["router"].shape[-2:] == (64, 8)
        assert params[run]["moe"]["w13"].shape[-3:] == (4, 64, 64)
        assert params[run]["shared_expert"]["w1"].shape[-2:] == (64, 32)
    a, dt = np.exp(np.asarray(linear["A_log"])), np.asarray(jax.nn.softplus(linear["dt_bias"]))
    assert (1 <= a).all() and (a <= 16).all() and (1e-3 <= dt).all() and (dt <= 1e-1 + 1e-6).all()
    out, sown = b.applied
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2])))
    assert float(net.routing_metrics(sown)["held_pairs"]) > 0
    spec = net.spec
    assert [op for op, _ in spec.layers] == ["full_attention"] + ["linear_attention"] * 3
    assert all(ffn == "moe" for _, ffn in spec.layers) and spec.frame_history
    assert (spec.router_outputs, spec.experts_held, spec.num_experts_per_tok, spec.score_function,
            spec.use_expert_bias, spec.shared_expert_intermediate_size, spec.heads_held) == (
                8, (2, 6), 2, "sigmoid", True, 32, None)
    m = spec.arg("linear")
    assert (m.heads, m.head_dim, m.conv, m.gate_rank, m.beta_scale, m.chunk) == (8, 16, 4, 16, 2.0, 16)
    assert dict(spec.mixers) == {"full_attention": solar_open2.GatedNopeAttention,
                                 "linear_attention": solar_open2.DeltaAttention}
    assert solar_open2.layer_types(SOLAR) == ["full_attention"] + ["linear_attention"] * 3
    assert solar_open2.spec_from_config(dict(SOLAR, kda_allow_neg_eigval=False)).arg(
        "linear").beta_scale == 1.0
    for bad in (dict(use_rope=True), dict(kda_use_full_proj=True), dict(first_k_dense_replace=1),
                dict(layer_types=["linear_attention"] * 4), dict(num_key_value_heads=3),
                dict(linear_attn_config=dict(SOLAR["linear_attn_config"], num_heads=4))):
        with pytest.raises(ValueError):
            solar_open2.spec_from_config(dict(SOLAR, **bad))


def _solar_counters(s):
    net, x, metrics = s.net, s.x, s.metrics
    # 40 tokens in chunks of 16: 3 chunks, 48 tokens walked, three layers, 4 rows, 3 forwards
    assert {k: float(v) for k, v in metrics.delta.items()} == {
        "chunks": 3 * 3 * 4 * 3.0, "tokens_padded": 3 * 3 * 4 * 48.0, "tokens": 3 * 3 * 4 * 40.0}
    assert float(metrics.attention["pairs_in_mask_full"]) == 3 * 4 * (40 * 41 // 2)
    assert float(metrics.routing["held_pairs"]) > 0 and metrics.scan is None
    assert net.delta_metrics(x.shape) == {"chunks": 36.0, "tokens_padded": 576.0, "tokens": 480.0}
    assert net.scan_metrics(x.shape) is None
    assert StepMetrics(loss=0, mean_abs_td=0, max_abs_td=0, priorities=0, mean_q=0).delta is None


def _solar_others():
    """``heads_held`` defaults to every head and only a family whose mixers
    divide may state one: the three older families' specs carry none, their
    trees and outputs are what they were (their own tests hold the numbers),
    and a share of heads on them is refused."""
    nets = {"lfm2_moe": build_network("lfm2_moe", 6, torso=LFM2_TWO_LAYERS, compute_dtype=jnp.float32),
            "laguna_moe": network("laguna_moe"), "granite_hybrid": network("granite_hybrid")}
    for kind, net in nets.items():
        assert net.spec.heads_held is None and net.delta_metrics((2, 52, 52, 4)) is None, kind
        with pytest.raises(ValueError, match="hold every head"):
            dataclasses.replace(net.spec, heads_held=(0, 2))
        assert "heads_held" not in str(jax.tree_util.tree_structure(
            jax.eval_shape(net.init, jax.random.PRNGKey(0), obs(jax.random.PRNGKey(1)))))
    assert not hasattr(nets["laguna_moe"].spec.mixers[0][1], "divides_heads")
    assert nets["granite_hybrid"].scan_metrics((2, 44, 60, 5)) is not None


def _solar_config(row, spec, committed, committed_spec):
    assert TORSO_NETWORKS[3] == "solar_open2" and HISTORY_NETWORKS[2] == "solar_open2"
    assert spec.num_held == 4
    spec = committed_spec
    assert committed.learner.replay_sample_size == 8 and committed.learner.steps_per_call == 1
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "solar2_q_ep40.json")))
    assert spec == solar_open2.spec_from_config(cell)
    assert [op for op, _ in spec.layers] == ["full_attention"] + ["linear_attention"] * 3
    m = spec.arg("linear")
    assert (spec.hidden_size, spec.moe_intermediate_size, spec.shared_expert_intermediate_size,
            m.heads, m.head_dim, m.conv, m.gate_rank, m.chunk) == (4096, 1280, 1280, 64, 128, 4, 128, 64)
    assert (spec.arg("num_attention_heads"), spec.arg("num_key_value_heads"), spec.arg("head_dim"),
            spec.router_outputs, spec.num_experts_per_tok, spec.experts_held, spec.heads_held) == (
                64, 8, 128, 320, 8, (0, 8), (0, 16))
    assert solar_open2.GatedNopeAttention.held(spec) == (16, 2)
    assert expert_torso.tile_rows(12544 * 8, 8, 320) == 3584      # the walk's tile at 320 outputs


# -------------------------------------------------------------- ling_hybrid

def _ling_structure(b: Built):
    net, params = b.net(), b.params["params"]
    assert set(params) >= {"layer_0", "layer_1", "layers_2_3", "w_tok", "final_norm"}
    assert set(params["layer_0"]) == {"operator_norm", "ffn_norm", "linear_attention", "dense"}
    assert params["layer_0"]["dense"]["w1"].shape == (64, 128)      # the leading dense layer
    assert set(params["layer_1"]) == {"operator_norm", "ffn_norm", "latent_attention", "moe",
                                      "shared_expert"}
    assert {k: v.shape for k, v in params["layer_1"]["latent_attention"].items()} == {
        "w_q": (64, 4 * 24), "w_dkv": (64, 24 + 8), "kv_norm": (24,), "w_ukv": (24, 4 * 32),
        "w_g": (64, 4), "w_o": (64, 64)}
    assert params["layers_2_3"]["moe"]["router"].shape == (2, 64, 16)
    assert params["layers_2_3"]["moe"]["w13"].shape == (2, 4, 64, 64)
    assert params["layers_2_3"]["shared_expert"]["w1"].shape == (2, 64, 32)
    spec = net.spec
    assert spec.layers == (("linear_attention", "dense"), ("latent_attention", "moe"),
                           ("linear_attention", "moe"), ("linear_attention", "moe"))
    assert (spec.router_outputs, spec.experts_held, spec.num_experts_per_tok, spec.score_function,
            spec.use_expert_bias, spec.shared_expert_intermediate_size, spec.routed_scaling_factor,
            spec.router_groups, spec.router_groups_kept, spec.norm_eps, spec.frame_history) == (
                16, (4, 8), 2, "sigmoid", True, 32, 2.5, 4, 2, 1e-6, True)
    m = spec.arg("latent")
    assert (m.heads, m.kv_rank, m.nope, m.rope, m.v, m.theta) == (4, 24, 16, 8, 16, 6e6)
    assert ling_hybrid.layer_types(LING) == (["linear_attention"] * 2 + ["latent_attention"]) * 4
    out, sown = b.applied
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2])))
    routing = net.routing_metrics(sown)
    assert float(routing["held_pairs"]) > 0 and 0.0 < float(routing["groups_kept_hold_share"]) < 1.0
    # a non-zero swiglu limit on a held layer raises; on a layer not held it does not
    for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        with pytest.raises(ValueError, match="no clamp"):
            ling_hybrid.spec_from_config(dict(LING, **{name: [0, 0, 0, 4] + [0] * 8}))
    ling_hybrid.spec_from_config(dict(LING, expert_swiglu_limit_list=[4] + [0] * 11))
    for bad in (dict(no_kda_lora=False), dict(kda_safe_gate=False), dict(q_lora_rank=128),
                dict(num_kv_heads_for_linear_attn=2), dict(score_function="softmax"),
                dict(layer_types=["linear_attention"] * 12),
                dict(heads_held=[0, 2], published=dict(LING["published"], num_attention_heads=8))):
        with pytest.raises(ValueError):
            ling_hybrid.spec_from_config(dict(LING, **bad))


def _ling_counters(s):
    metrics = s.metrics
    assert "expert_bias" not in s.got_w["layer_0"]
    # 40 tokens in chunks of 16: 3 chunks, 48 tokens walked, three linear layers, 4 rows, 3 forwards
    assert {k: float(v) for k, v in metrics.delta.items()} == {
        "chunks": 3 * 3 * 4 * 3.0, "tokens_padded": 3 * 3 * 4 * 48.0, "tokens": 3 * 3 * 4 * 40.0}
    assert {k: float(v) for k, v in metrics.attention.items()} == {
        # a head has keys of its own: a block of 256 x 512 a head and row
        "pairs_in_mask_latent": 3 * 4 * (40 * 41 // 2), "pairs_computed_latent": 3 * 4 * 256 * 512.0,
        "blocks_visited_latent": 3 * 4 * 4 * 1.0, "blocks_total_latent": 3 * 4 * 4 * 1.0}
    assert float(metrics.routing["held_pairs"]) > 0 and metrics.scan is None
    share = float(metrics.routing["groups_kept_hold_share"])       # a mean, not the forwards' sum
    assert 0.0 < share < 1.0
    assert set(metrics.routing) == {"held_pairs", "load_max", "load_mean", "rows_walked",
                                    "groups_kept_hold_share"}


def _ling_others():
    """One group is no group: the four older families' specs carry the
    default, sow ``load`` alone and count no ``groups_kept_hold_share``."""
    net = network("solar_open2")
    assert (net.spec.router_groups, net.spec.router_groups_kept) == (1, 1)
    x = obs(jax.random.PRNGKey(2))
    params = init_of(net, jax.random.PRNGKey(3), x)
    _, sown = jax.jit(lambda p: net.apply(p, x, mutable=["routing"]))(params)
    names = {p[-2].key for p, _ in jax.tree_util.tree_leaves_with_path(sown["routing"])}
    assert names == {"load"} and set(net.routing_metrics(sown)) == {
        "held_pairs", "load_max", "load_mean", "rows_walked"}
    assert net.attention_metrics(x.shape).keys() == {"pairs_in_mask_full", "pairs_computed_full",
                                                     "blocks_visited_full", "blocks_total_full"}


def _ling_config(row, spec, committed, committed_spec):
    assert TORSO_NETWORKS[4] == "ling_hybrid" and HISTORY_NETWORKS[3] == "ling_hybrid"
    assert spec.num_held == 4
    spec = committed_spec
    assert committed.learner.replay_sample_size == 8 and committed.learner.steps_per_call == 1
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "ling3_q_l7.json")))
    assert spec == ling_hybrid.spec_from_config(cell)
    assert [kinds for kinds in spec.layers] == [("linear_attention", "dense")] + [
        ("linear_attention", "moe")] * 3 + [("latent_attention", "moe")] + [("linear_attention", "moe")] * 2
    m, n = spec.arg("linear"), spec.arg("latent")
    assert (spec.hidden_size, spec.intermediate_size, spec.moe_intermediate_size,
            spec.shared_expert_intermediate_size, m.heads, m.head_dim, m.conv, m.gate_rank, m.chunk,
            m.gate, m.gate_bound) == (2560, 6144, 768, 768, 32, 128, 4, None, 64, "bounded", -5.0)
    assert (n.heads, n.kv_rank, n.nope, n.rope, n.v, n.theta) == (32, 512, 128, 64, 128, 6e6)
    assert (spec.router_outputs, spec.num_experts_per_tok, spec.router_groups, spec.router_groups_kept,
            spec.heads_held, spec.routed_scaling_factor) == (512, 8, 8, 4, (0, 8), 2.5)
    lo, hi = spec.experts_held
    assert lo == 0 and hi in (8, 16) and hi <= 64                  # all in router group 0
    assert expert_torso.tile_rows(12544 * 8, hi, 512) == {16: 4608, 8: 2560}[hi]


# -------------------------------------------------------------- olmo_hybrid

def _olmo_structure(b: Built):
    net, params = b.net(), b.params["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layers_0_2", "layer_3", "w_tok", "final_norm"}
    linear = params["layers_0_2"]["linear_attention"]
    assert {k: v.shape[1:] for k, v in linear.items()} == {        # keys of 12, values of 24
        "w_q": (48, 36), "w_k": (48, 36), "w_v": (48, 72), "conv_q": (36, 4), "conv_k": (36, 4),
        "conv_v": (72, 4), "w_a": (48, 3), "A_log": (3,), "dt_bias": (3,), "w_b": (48, 3),
        "w_g": (48, 72), "norm": (24,), "w_o": (72, 48)}
    assert {k: v.shape for k, v in params["layer_3"]["full_attention"].items()} == {
        "w_q": (48, 48), "w_k": (48, 48), "w_v": (48, 48), "q_norm": (48,), "k_norm": (48,),
        "w_o": (48, 48)}                                          # the norms over the whole width
    for run, op in (("layers_0_2", "linear_attention"), ("layer_3", "full_attention")):
        assert set(params[run]) == {"operator_norm", "ffn_norm", "dense", op}
        assert params[run]["dense"]["w1"].shape[-2:] == (48, 96)
    a, dt = np.exp(np.asarray(linear["A_log"])), np.asarray(jax.nn.softplus(linear["dt_bias"]))
    assert (1 <= a).all() and (a <= 16).all() and (1e-3 <= dt).all() and (dt <= 1e-1 + 1e-6).all()
    out, _ = b.applied
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2])))
    spec = net.spec
    assert spec.layers == (("linear_attention", "dense"),) * 3 + (("full_attention", "dense"),)
    assert (spec.post_norm, spec.router_outputs, spec.experts_held, spec.heads_held, spec.norm_eps,
            spec.frame_history, spec.use_expert_bias) == (True, 0, (0, 0), None, 1e-6, True, False)
    m = spec.arg("linear")
    assert (m.heads, m.key_dim, m.value_dim, m.conv, m.beta_scale, m.chunk) == (3, 12, 24, 4, 2.0, 16)
    assert (spec.arg("num_attention_heads"), spec.arg("head_dim")) == (3, 16)
    assert dict(spec.mixers) == {"full_attention": olmo_hybrid.QkNormAttention,
                                 "linear_attention": olmo_hybrid.GatedDeltaNet}
    assert net.routing_metrics({"routing": {}}) is None and net.scan_metrics(b.x.shape) is None
    assert olmo_hybrid.spec_from_config(dict(OLMO, linear_allow_neg_eigval=False)).arg(
        "linear").beta_scale == 1.0
    for bad in (dict(rope_parameters={"rope_theta": 10000.0}), dict(attention_bias=True),
                dict(num_key_value_heads=1), dict(linear_num_value_heads=6), dict(num_experts=4),
                dict(layer_types=["mamba"] * 8), dict(num_attention_heads=5)):
        with pytest.raises(ValueError):
            olmo_hybrid.spec_from_config(dict(OLMO, **bad))
    with pytest.raises(ValueError, match="dense layers"):         # no expert layer under a post-norm
        dataclasses.replace(network("lfm2_moe").spec, post_norm=True)


def _olmo_counters(s):
    metrics = s.metrics
    # 40 tokens in chunks of 16: 3 chunks, 48 tokens walked, three linear layers, 4 rows, 3 forwards
    assert {k: float(v) for k, v in metrics.delta.items()} == {
        "chunks": 3 * 3 * 4 * 3.0, "tokens_padded": 3 * 3 * 4 * 48.0, "tokens": 3 * 3 * 4 * 40.0}
    # one full layer of three heads, a key-value head each: a block of 256 x 512 a head and row
    assert {k: float(v) for k, v in metrics.attention.items()} == {
        "pairs_in_mask_full": 3 * 4 * (40 * 41 // 2), "pairs_computed_full": 3 * 4 * 256 * 512.0,
        "blocks_visited_full": 3 * 4 * 3 * 1.0, "blocks_total_full": 3 * 4 * 3 * 1.0}
    assert metrics.routing is None and metrics.scan is None


def _olmo_others():
    """``post_norm`` defaults to the pre-norm block: the five older families'
    specs carry the default and their blocks norm a sublayer's input (the
    text of a pre-norm block has its norm's rsqrt before the mixer's first
    product; their own tests hold the numbers).  The per-channel walk is
    what it was: ``g`` of a key channel's rank takes ``_chunk``."""
    from ape_x_dqn_tpu.ops import chunked_delta as cd

    for kind in ("granite_hybrid", "solar_open2", "ling_hybrid", "laguna_moe"):
        assert network(kind).spec.post_norm is False, kind
    assert build_network("lfm2_moe", 6, torso=LFM2_TWO_LAYERS).spec.post_norm is False
    q = jnp.zeros((1, 1, 4, 8))
    assert cd._chunk_of(q, jnp.zeros((1, 1, 4, 8))) is cd._chunk
    assert cd._chunk_of(q, jnp.zeros((1, 1, 4))) is cd._chunk_scalar


def _olmo_config(row, spec, committed, committed_spec):
    assert TORSO_NETWORKS[5] == "olmo_hybrid" and HISTORY_NETWORKS[4] == "olmo_hybrid"
    assert spec.post_norm and spec.num_held == 0
    spec = committed_spec
    assert committed.learner.replay_sample_size == 4 and committed.learner.steps_per_call == 1
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "olmoh_q_l4.json")))
    assert spec == olmo_hybrid.spec_from_config(cell)
    assert spec.layers == (("linear_attention", "dense"),) * 3 + (("full_attention", "dense"),)
    m = spec.arg("linear")
    assert (spec.hidden_size, spec.intermediate_size, m.heads, m.key_dim, m.value_dim, m.conv,
            m.beta_scale, m.chunk, spec.arg("num_attention_heads"), spec.arg("head_dim"),
            spec.norm_eps, spec.post_norm, spec.heads_held) == (
                3840, 11008, 30, 96, 192, 4, 2.0, 64, 30, 128, 1e-6, True, None)


# ---------------------------------------------------------------- kanana_moe

def _kanana_structure(b: Built):
    net, params = b.net(), b.params["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layer_0", "layers_1_3", "w_tok", "final_norm"}
    assert set(params["layer_0"]) == {"operator_norm", "ffn_norm", "latent_attention", "dense"}
    assert params["layer_0"]["dense"]["w1"].shape == (64, 128)      # the leading dense layer
    assert set(params["layers_1_3"]) == {"operator_norm", "ffn_norm", "latent_attention", "moe",
                                         "shared_expert"}
    assert {k: v.shape for k, v in params["layer_0"]["latent_attention"].items()} == {
        "w_q": (64, 4 * 24), "w_dkv": (64, 24 + 8), "kv_norm": (24,), "w_ukv": (24, 4 * 32),
        "w_o": (64, 64)}                                          # no head gate
    assert params["layers_1_3"]["moe"]["router"].shape == (3, 64, 8)
    assert params["layers_1_3"]["moe"]["w13"].shape == (3, 2, 64, 64)
    assert params["layers_1_3"]["shared_expert"]["w1"].shape == (3, 64, 64)   # two of 32 as one
    spec = net.spec
    assert spec.layers == (("latent_attention", "dense"),) + (("latent_attention", "moe"),) * 3
    assert dict(spec.mixers) == {"latent_attention": ling_hybrid.LatentAttention}
    assert (spec.router_outputs, spec.experts_held, spec.heads_held, spec.norm_eps,
            spec.frame_history, spec.post_norm) == (8, (2, 4), None, 1e-6, True, False)
    out, sown = b.applied
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2])))
    routing = net.routing_metrics(sown)
    assert float(routing["held_pairs"]) > 0 and "groups_kept_hold_share" not in routing
    assert net.scan_metrics(b.x.shape) is None and net.delta_metrics(b.x.shape) is None


def _kanana_counters(s):
    metrics = s.metrics
    assert "expert_bias" not in s.got_w["layer_0"]
    # four latent layers of four heads: a block of 256 x 512 a head and row, 4 rows, 3 forwards
    assert {k: float(v) for k, v in metrics.attention.items()} == {
        "pairs_in_mask_latent": 4 * 3 * 4 * (40 * 41 // 2), "pairs_computed_latent": 4 * 3 * 4 * 256 * 512.0,
        "blocks_visited_latent": 4 * 3 * 4 * 4 * 1.0, "blocks_total_latent": 4 * 3 * 4 * 4 * 1.0}
    assert float(metrics.routing["held_pairs"]) > 0
    assert metrics.scan is None and metrics.delta is None
    assert set(metrics.routing) == {"held_pairs", "load_max", "load_mean", "rows_walked"}


def _kanana_others():
    """``LatentSizes.gated`` defaults to Ling's gate: ``ling_hybrid``'s spec
    carries it, its latent layer keeps ``w_g`` in its place among the six
    parameters, and the other families' specs hold no latent sizes at all."""
    ling = network("ling_hybrid")
    assert ling.spec.arg("latent").gated is True
    x = obs(jax.random.PRNGKey(2))
    shapes = jax.eval_shape(ling.init, jax.random.PRNGKey(3), x)["params"]
    assert list(shapes["layer_1"]["latent_attention"]) == [
        "kv_norm", "w_dkv", "w_g", "w_o", "w_q", "w_ukv"]
    for kind in ("granite_hybrid", "solar_open2", "laguna_moe", "olmo_hybrid"):
        assert "latent" not in dict(network(kind).spec.mixer_args), kind


def _kanana_config(row, spec, committed, committed_spec):
    assert TORSO_NETWORKS[6] == "kanana_moe" and HISTORY_NETWORKS[5] == "kanana_moe"
    assert spec.num_held == 2
    spec = committed_spec
    assert committed.learner.replay_sample_size == 8 and committed.learner.steps_per_call == 1
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "kanana2_q_ep8.json")))
    assert spec == kanana_moe.spec_from_config(cell)
    assert spec.layers == (("latent_attention", "dense"),) + (("latent_attention", "moe"),) * 5
    n = spec.arg("latent")
    assert (spec.hidden_size, spec.intermediate_size, spec.moe_intermediate_size,
            spec.shared_expert_intermediate_size, n.heads, n.kv_rank, n.nope, n.rope, n.v, n.theta,
            n.gated) == (2048, 6144, 768, 1536, 32, 512, 128, 64, 128, 1e6, False)
    assert (spec.router_outputs, spec.num_experts_per_tok, spec.router_groups, spec.router_groups_kept,
            spec.heads_held, spec.routed_scaling_factor, spec.experts_held, spec.gate_norm_eps) == (
                128, 6, 1, 1, None, 2.448, (0, 16), 1e-20)
    assert expert_torso.tile_rows(12544 * 6, 16, 128) == 12800      # the walk's tile at 128 outputs


# ---------------------------------------------------------------- nemotron_h

def _nemotron_structure(b: Built):
    net, params = b.net(), b.params["params"]
    assert params["Conv_0"]["kernel"].shape == (8, 8, 1, 8)     # one frame at a time
    assert set(params) >= {"layers_0_1", "layer_2", "layer_3", "w_tok", "final_norm"}
    assert set(params["layers_0_1"]) == {"operator_norm", "ffn_norm", "mamba", "moe", "shared_expert"}
    assert set(params["layer_2"]) == {"operator_norm", "mamba"}           # a mixer alone: no FFN, no norm of one
    assert set(params["layer_3"]) == {"operator_norm", "ffn_norm", "attention", "moe", "shared_expert"}
    # two groups of two heads of 16 held: x | B | C = 64 + 2 x 2 x 16
    assert {k: v.shape for k, v in params["layer_2"]["mamba"].items()} == {
        "w_in": (64, 64 + 128 + 4), "conv_kernel": (128, 4), "conv_bias": (128,), "A_log": (4,),
        "dt_bias": (4,), "D": (4,), "norm": (64,), "w_out": (64, 64)}
    assert {k: v.shape for k, v in params["layer_3"]["attention"].items()} == {
        "w_q": (64, 32), "w_k": (64, 16), "w_v": (64, 16), "w_o": (32, 64)}   # no gate, one key-value head
    assert {k: v.shape for k, v in params["layer_3"]["moe"].items()} == {
        "router": (64, 16), "expert_bias": (16,), "w1": (4, 32, 48), "w2": (4, 48, 32),
        "w_down": (64, 32), "w_up": (32, 64)}                             # two matrices an expert, in the latent
    assert {k: v.shape for k, v in params["layer_3"]["shared_expert"].items()} == {
        "w1": (64, 32), "w2": (32, 64)}                                   # half its 64 columns
    spec = net.spec
    assert spec.layers == (("mamba", "moe"),) * 2 + (("mamba", "none"), ("attention", "moe"))
    assert dict(spec.mixers) == {"mamba": granite_hybrid.Mamba2,
                                 "attention": solar_open2.GatedNopeAttention}
    assert (spec.router_outputs, spec.experts_held, spec.heads_held, spec.norm_eps, spec.frame_history,
            spec.expert_rule, spec.moe_latent_size, spec.shared_expert_held, spec.gate_norm_eps,
            spec.routed_scaling_factor) == (16, (4, 8), (0, 2), 1e-5, True, "relu2", 32, (0, 32), 1e-20, 5.0)
    m = spec.arg("mamba")
    assert (m.heads, m.groups, m.held, m.share, m.chunk) == (8, 4, (0, 4), (4, 2), 16)
    out, sown = b.applied
    assert out[2].shape == (2, 6) and bool(jnp.all(jnp.isfinite(out[2])))
    assert float(net.routing_metrics(sown)["held_pairs"]) > 0
    assert net.delta_metrics(b.x.shape) is None


def _nemotron_counters(s):
    metrics = s.metrics
    # 40 tokens in chunks of 16: 3 chunks, 48 tokens walked, three Mamba-2 layers, 4 rows, 3 forwards
    assert {k: float(v) for k, v in metrics.scan.items()} == {
        "chunks": 3 * 3 * 4 * 3.0, "tokens_padded": 3 * 3 * 4 * 48.0, "tokens": 3 * 3 * 4 * 40.0}
    # one attention layer of two held query heads on one key-value head
    assert float(metrics.attention["pairs_in_mask_full"]) == 3 * 4 * (40 * 41 // 2)
    assert float(metrics.attention["blocks_total_full"]) == 3 * 4 * 2 * 1.0
    assert float(metrics.routing["held_pairs"]) > 0 and metrics.delta is None
    assert set(metrics.routing) == {"held_pairs", "load_max", "load_mean", "rows_walked"}


def _nemotron_others():
    """The new spec fields default to what every expert torso had (SwiGLU
    experts at the layer's width, the shared expert whole, an FFN in every
    block), granite's Mamba-2 has one group and every head, and their
    parameter trees hold the names they held."""
    for kind in ("laguna_moe", "solar_open2", "ling_hybrid", "kanana_moe", "granite_hybrid"):
        sp = network(kind).spec
        assert (sp.expert_rule, sp.moe_latent_size, sp.shared_expert_held) == ("swiglu", 0, None), kind
        assert all(ffn in ("dense", "moe") for _, ffn in sp.layers), kind
    m = network("granite_hybrid").spec.arg("mamba")
    assert (m.groups, m.held, m.share) == (1, None, (8, 1))
    kanana = network("kanana_moe")
    shapes = jax.eval_shape(kanana.init, jax.random.PRNGKey(3), obs(jax.random.PRNGKey(2)))["params"]
    assert sorted(shapes["layers_1_3"]["moe"]) == ["expert_bias", "router", "w13", "w2"]
    assert sorted(shapes["layers_1_3"]["shared_expert"]) == ["w1", "w2", "w3"]


def _nemotron_config(row, spec, committed, committed_spec):
    assert TORSO_NETWORKS[-1] == "nemotron_h" and HISTORY_NETWORKS[-1] == "nemotron_h"
    assert spec.num_held == 4
    spec = committed_spec
    assert committed.learner.replay_sample_size == 8 and committed.learner.steps_per_call == 1
    cell = json.load(open(os.path.join(ROOT, "benchmark", "configs", "nemotron3s_q_ep32.json")))
    assert spec == nemotron_h.spec_from_config(cell)
    assert spec.layers == (("mamba", "moe"),) * 4 + (("mamba", "none"), ("attention", "moe"))
    m = spec.arg("mamba")
    assert (spec.hidden_size, spec.moe_intermediate_size, spec.moe_latent_size,
            spec.shared_expert_intermediate_size, spec.shared_expert_held, m.heads, m.head_dim, m.state,
            m.conv, m.chunk, m.groups, m.held, m.share) == (
                4096, 2688, 1024, 5376, (0, 1344), 128, 64, 128, 4, 128, 8, (0, 32), (32, 2))
    assert (spec.router_outputs, spec.num_experts_per_tok, spec.router_groups, spec.heads_held,
            spec.routed_scaling_factor, spec.experts_held, spec.gate_norm_eps, spec.expert_rule) == (
                512, 22, 1, (0, 8), 5.0, (0, 16), 1e-20, "relu2")
    assert solar_open2.GatedNopeAttention.held(spec) == (8, 1)      # eight query heads on key-value head 0
    assert expert_torso.tile_rows(12544 * 22, 16, 512) == 11776     # the walk's tile at 22 of 512


def _nemotron_loads(loads):
    # the seven held layers as the reference holds them: M E M E M * E
    assert loads.shape == (7, 16)
    assert [float(v) for v in jnp.sum(loads, -1)] == [0.0, 480.0, 0.0, 480.0, 0.0, 0.0, 480.0]


def _kanana_loads(loads):
    assert loads.shape == (4, 8) and [float(v) for v in jnp.sum(loads, -1)] == [0.0] + [4 * 40 * 3.0] * 3


def _solar_loads(loads):
    assert loads.shape == (4, 8) and float(jnp.sum(loads)) == 4 * 4 * 40 * 2


def _ling_loads(loads):
    assert loads.shape == (4, 16) and [float(v) for v in jnp.sum(loads, -1)] == [0.0] + [4 * 40 * 2.0] * 3



ROWS = {row.name: row for row in (
    Row("lfm2_moe", LFM2, "Lfm2MoeQ", "config6_lfm2moe_q_ep8.json", _lfm2_config, _lfm2_counters,
        stepped=_lfm2_stepped, kept_float32=("router", "expert_bias"), obs_shape=(52, 52, 4), rows=4,
        scoped=_lfm2_scoped),
    Row("laguna_moe", LAGUNA, "LagunaMoeQ", "config7_laguna_q_ep32.json", _laguna_config,
        _laguna_counters, stepped=_laguna_stepped, kept_float32=("router",),
        structure=_laguna_structure, parts_at=slice(6, 9),
        parts=("attn_window", "attn_full", "shared_expert"),
        scopes=("attn_window", "attn_full", "mixer", "shared_expert", "router", "experts",
                "dense_ffn", "stem", "head"),
        scope_paths=("torso:mixer/sliding_attention/torso:attn_window",)),   # the kernels inside the mixer
    Row("granite_hybrid", GRANITE, "GraniteHybridQ", "config8_granite4h_q_l10.json", _granite_config,
        _granite_counters, kept_float32=("A_log", "dt_bias", "D"),
        float32_leaves=("router", "expert_bias", "A_log", "dt_bias", "['D']"),
        structure=_granite_structure, reference="granite_h_q", bf16_tolerance=0.15,
        others=_granite_others, parts_at=slice(9, 10), parts=("ssm_scan",),
        scopes=("ssm_scan", "attn_full", "mixer", "dense_ffn", "stem", "head"),
        scope_paths=("torso:mixer/mamba/torso:ssm_scan", "torso:mixer/attention/torso:attn_full",
                     "transpose("),
        scopes_absent=("router", "experts", "shared_expert", "attn_window"),
        walked_back="ssm_scan", compiled_part="ssm_scan"),
    Row("solar_open2", SOLAR, "SolarOpen2Q", "config9_solar2_q_ep40.json", _solar_config,
        _solar_counters, kept_float32=("A_log", "dt_bias", "router", "expert_bias"),
        float32_leaves=("router", "expert_bias", "A_log", "dt_bias"),
        structure=_solar_structure, reference="solar2_q", bf16_tolerance=0.5,
        flags=("reference_resets_state", "reference_drops_delta"), loads=_solar_loads,
        bias_moved=(0, 1, 2, 3),
        others=_solar_others, parts_at=slice(9, 11), parts=("ssm_scan", "delta_scan"),
        scopes=("delta_scan", "attn_full", "mixer", "router", "experts", "shared_expert", "stem",
                "head"),
        scope_paths=("torso:mixer/linear_attention/", "torso:mixer/full_attention/torso:attn_full",
                     "transpose("),
        scopes_absent=("ssm_scan", "dense_ffn", "attn_window"),
        walked_back="delta_scan", compiled_part="delta_scan"),
    Row("ling_hybrid", LING, "LingHybridQ", "config10_ling3_q_l7.json", _ling_config, _ling_counters,
        kept_float32=("A_log", "dt_bias", "router", "expert_bias"),
        float32_leaves=("router", "expert_bias", "A_log", "dt_bias"),
        structure=_ling_structure, reference="ling3_q", bf16_tolerance=0.5,
        flags=("reference_ungrouped_router", "reference_drops_shared_key", "reference_unbounded_gate",
               "reference_resets_state"), loads=_ling_loads, bias_moved=(1, 2, 3),
        others=_ling_others, parts_at=slice(-2, -1), parts=("attn_latent",),
        scopes=("delta_scan", "attn_latent", "mixer", "router", "experts", "shared_expert",
                "dense_ffn", "stem", "head"),
        scope_paths=("torso:mixer/latent_attention/torso:attn_latent",
                     "torso:mixer/linear_attention/", "transpose("),
        scopes_absent=("ssm_scan", "attn_full", "attn_window")),
    # grad_tolerance: every sublayer's output is normed and a head's output is normed again, so
    # what reaches q and k is what two projections leave; the reference's own gradient of w_q
    # moves 7e-4 of its norm between four rows at once and a row at a time (float32, this size)
    Row("olmo_hybrid", OLMO, "OlmoHybridQ", "config11_olmoh_q_l4.json", _olmo_config, _olmo_counters,
        kept_float32=("A_log", "dt_bias"),
        float32_leaves=("router", "expert_bias", "A_log", "dt_bias"),
        structure=_olmo_structure, reference="olmoh_q", bf16_tolerance=0.5, grad_tolerance=4e-3,
        flags=("reference_pre_norm", "reference_drops_decay", "reference_beta_to_one",
               "reference_norms_by_head"),
        others=_olmo_others, parts_at=slice(10, 11), parts=("delta_scan",),
        scopes=("delta_scan", "attn_full", "mixer", "dense_ffn", "stem", "head"),
        scope_paths=("torso:mixer/linear_attention/", "/scalar_gate/",
                     "torso:mixer/full_attention/torso:attn_full", "transpose("),
        scopes_absent=("ssm_scan", "router", "experts", "shared_expert", "attn_window", "attn_latent"),
        walked_back="delta_scan", compiled_part="delta_scan"),
    Row("kanana_moe", KANANA, "KananaMoeQ", "config12_kanana2_q_ep8.json", _kanana_config,
        _kanana_counters, kept_float32=("router", "expert_bias"),
        structure=_kanana_structure, reference="kanana2_q", bf16_tolerance=0.5,
        flags=("reference_drops_shared_key", "reference_skips_latent_norm",
               "reference_unscaled_gates"), loads=_kanana_loads, bias_moved=(1, 2, 3),
        others=_kanana_others, parts_at=slice(-2, -1), parts=("attn_latent",),
        scopes=("attn_latent", "mixer", "router", "experts", "shared_expert", "dense_ffn", "stem",
                "head"),
        scope_paths=("torso:mixer/latent_attention/torso:attn_latent", "transpose("),
        scopes_absent=("ssm_scan", "delta_scan", "attn_full", "attn_window"),
        compiled_part="attn_latent"),
    Row("nemotron_h", NEMOTRON, "NemotronHQ", "config13_nemotron3s_q_ep32.json", _nemotron_config,
        _nemotron_counters, kept_float32=("router", "expert_bias", "A_log", "dt_bias", "D"),
        float32_leaves=("router", "expert_bias", "A_log", "dt_bias", "['D']"),
        structure=_nemotron_structure, reference="nemotron3s_q", bf16_tolerance=0.5,
        flags=("reference_shares_group0", "reference_norms_all_channels", "reference_silu_experts",
               "reference_router_reads_latent", "reference_unscaled_gates"),
        loads=_nemotron_loads, bias_moved=(1, 3, 6), others=_nemotron_others,
        parts_at=slice(-1, None), parts=("latent_proj",),
        scopes=("ssm_scan", "attn_full", "latent_proj", "mixer", "router", "experts", "shared_expert",
                "stem", "head"),
        scope_paths=("torso:mixer/mamba/torso:ssm_scan", "torso:mixer/attention/torso:attn_full",
                     "moe/torso:latent_proj", "transpose("),
        scopes_absent=("delta_scan", "attn_latent", "attn_window", "dense_ffn"),
        walked_back="ssm_scan", compiled_part="latent_proj"),
)}

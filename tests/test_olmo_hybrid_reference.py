"""``benchmark/reference/olmoh_q.py`` on seeded weights against the program's
``olmo_hybrid`` network, at small widths on the CPU: Q-values, the loss, the
priorities, the gradients and one update, each tolerance with its reason, and
the reference's controls (held in bfloat16, the pre-norm order, ``g = 0``,
``beta`` up to 1, a norm a head), each of which fails at least one of them.
The contract's cases (``tests/test_olmo_hybrid.py``) hold the program to the
reference; here the tolerances are held to what they must keep out."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import torso_contract as contract
from tests.torso_contract import Built, obs

ROW = contract.ROWS["olmo_hybrid"]
# Q in float32 compute: sums in another order, the walk in chunks against a
# token a step (the contract's); the loss is a mean of four squares of it.
Q_TOL, LOSS_TOL, PRIORITY_TOL = 1e-4, 1e-4, 2e-4
# the update: RMSProp divides every gradient by sqrt(nu0 + g^2 / 20), so a
# leaf's float32 rounding (``grad_tolerance``) reaches its step about as it is
UPDATE_TOL = 2e-3
CONTROLS = ("bf16_held",) + ROW.flags


@pytest.fixture(scope="module")
def sides():
    """The program's step beside the reference's, on one batch (the
    contract's), and each control's ``learner_step`` on the same."""
    b = Built(ROW)
    s = b.stepped
    ref, cfg, t = b.ref, ROW.cfg, b.stepped.batch.transition
    rows = dict(obs=t.obs, next_obs=t.next_obs, action=t.action, reward=t.reward,
                discount=t.discount, is_weights=s.batch.is_weights)
    noise = np.random.default_rng(21)      # the contract's target, made again: the step donated its state
    target = jax.tree_util.tree_map(
        lambda w: jnp.asarray(np.asarray(w) + 0.05 * np.std(w) * noise.standard_normal(w.shape, np.float32)),
        s.weights)
    nu = jax.tree_util.tree_map(lambda w: jnp.full(w.shape, 1e-4), s.weights)

    def control(name):
        over, precision = ({}, name) if name == "bf16_held" else ({name: True}, "stated")
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda w, tw, v, r: ref.learner_step(
                w, tw, v, r, dict(cfg, **over), precision))(s.weights, target, nu, rows)

    return s, control


def _update_distance(got, want, old) -> float:
    num = den = 0.0
    for a, b, o in zip(*(jax.tree_util.tree_leaves(t) for t in (got, want, old))):
        num += float(jnp.sum(jnp.square((a - o) - (b - o))))
        den += float(jnp.sum(jnp.square(b - o)))
    return float(np.sqrt(num / den))


def test_q_values_loss_priorities_and_the_update_are_the_references(sides):
    s, _ = sides
    ref, cfg = importlib.import_module("reference.olmoh_q"), ROW.cfg
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda w: ref.forward(w, s.x, cfg))(s.weights)
        got = jax.jit(s.net.apply)(ref.to_program_params(s.weights, cfg), s.x)[2]
    scale = float(jnp.std(want)) + float(jnp.mean(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= Q_TOL * scale
    assert float(s.metrics.loss) == pytest.approx(float(s.want_loss), rel=LOSS_TOL)
    np.testing.assert_allclose(np.asarray(s.metrics.priorities), np.asarray(s.want_prio),
                               rtol=PRIORITY_TOL)
    assert _update_distance(s.got_w, s.want_w, s.weights) < UPDATE_TOL


@pytest.mark.parametrize("name", CONTROLS)
def test_a_control_fails_a_tolerance(sides, name):
    """The reference with one thing changed reads past the loss's, the
    priorities' or the update's tolerance against the stated reference: the
    tolerances keep every one of them out."""
    s, control = sides
    new_w, _, _, prio, loss = control(name)
    failed = {
        "loss": abs(float(loss) - float(s.want_loss)) > LOSS_TOL * abs(float(s.want_loss)),
        "priorities": bool(np.any(np.abs(np.asarray(prio) - np.asarray(s.want_prio))
                                  > PRIORITY_TOL * np.abs(np.asarray(s.want_prio)))),
        "update": _update_distance(new_w, s.want_w, s.weights) >= UPDATE_TOL,
    }
    assert any(failed.values()), failed
    if name != "reference_norms_by_head":      # the mechanisms fail every one
        assert all(failed.values()), failed


@pytest.mark.parametrize("name", ROW.flags)
def test_a_flag_moves_the_references_q(name):
    ref, cfg = importlib.import_module("reference.olmoh_q"), ROW.cfg
    assert name in ref.FLAGS
    w = jax.jit(lambda k: ref.make_weights(k, cfg))(jax.random.PRNGKey(3))
    x = obs(jax.random.PRNGKey(4), rows=2)
    with jax.default_matmul_precision("highest"):
        q, _ = jax.jit(lambda w: ref.forward(w, x, cfg))(w)
        other, _ = jax.jit(lambda w: ref.forward(w, x, dict(cfg, **{name: True})))(w)
    assert float(jnp.max(jnp.abs(other - q))) > 1e-2 * float(jnp.std(q))


def test_the_reference_imports_nothing_from_the_program_and_steps_a_token_at_a_time():
    import inspect

    ref = importlib.import_module("reference.olmoh_q")
    source = inspect.getsource(ref)
    assert "ape_x_dqn_tpu" not in source.split('"""', 2)[2]
    assert "jax.lax.scan(step, state, tokens)" in source and "cumsum" not in source
    assert ref.param_count(dict(ROW.cfg)) == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda k: ref.make_weights(k, ROW.cfg), jax.random.PRNGKey(0))))


def test_the_cells_reference_counts_the_cells_parameters():
    """``benchmark/configs/olmoh_q_l4.json``: 836,784,807 parameters, a linear
    layer 215,570,172 and the full layer 185,809,920 (ISSUE 51's arithmetic)."""
    import json
    import os

    ref = importlib.import_module("reference.olmoh_q")
    cfg = json.load(open(os.path.join(contract.ROOT, "benchmark", "configs", "olmoh_q_l4.json")))
    shapes = ref.weight_shapes(cfg)
    count = lambda layer: sum(int(np.prod(s)) for s in layer.values())  # noqa: E731
    assert ref.param_count(cfg) == 836_784_807
    assert [count(shapes[f"layer_{i}"]) for i in range(4)] == [215_570_172] * 3 + [185_809_920]
    assert f"{ref.param_count(cfg):,}" in cfg["reduced_why"]["num_hidden_layers"]

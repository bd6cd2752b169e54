"""``benchmark/reference/olmoh_q.py`` by itself: the token-by-token recurrence
against numpy, the whole-width norm, the post-norm layer, the four mechanism
flags, the seeded weights' laws and the map to the program's tree and back."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import manifest as mf
import reference.olmoh_q as ref

SMALL = dict(
    hidden_size=24, intermediate_size=40, num_attention_heads=3, num_key_value_heads=3,
    rms_norm_eps=1e-6, layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    num_hidden_layers=4, layers_held=[0, 1, 2, 3], linear_num_key_heads=3,
    linear_num_value_heads=3, linear_key_head_dim=4, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, channels=[4, 4, 4], hidden=8,
    obs_shape=[44, 44, 3], num_actions=5)


def _weights(seed=0, cfg=SMALL):
    return jax.jit(lambda k: ref.make_weights(k, cfg))(jax.random.PRNGKey(seed))


def test_the_recurrence_is_the_equation_in_numpy():
    """``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_(t-1) + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, a head at a time in float64."""
    rng = np.random.default_rng(0)
    b, t, h, kd, vd = 2, 9, 2, 3, 5
    q, k = rng.standard_normal((2, b, t, h, kd))
    v = rng.standard_normal((b, t, h, vd))
    g, beta = -rng.uniform(0, 1, (b, t, h)), 2 * rng.uniform(0, 1, (b, t, h))
    want = np.zeros((b, t, h, vd))
    for i in range(b):
        for j in range(h):
            s = np.zeros((kd, vd))
            for n in range(t):
                kn = k[i, n, j]
                s = np.exp(g[i, n, j]) * (np.eye(kd) - beta[i, n, j] * np.outer(kn, kn)) @ s \
                    + beta[i, n, j] * np.outer(kn, v[i, n, j])
                want[i, n, j] = s.T @ q[i, n, j]
    got = ref.recurrence(*(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_the_layer_norms_its_sublayers_outputs():
    w = _weights()
    p = w["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 24))
    f32, same = jnp.float32, lambda a: a
    rms = lambda y, g: y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6) * g  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = ref.layer(x, p, "linear_attention", SMALL, f32, same)
        h = x + rms(ref.linear_attention(x, p, SMALL, f32, same), p["operator_norm"])
        y = (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(h + rms(y, p["ffn_norm"])), atol=2e-5)
        pre = ref.layer(x, p, "linear_attention", dict(SMALL, reference_pre_norm=True), f32, same)
    assert float(jnp.max(jnp.abs(pre - got))) > 0.1


def test_the_full_layer_norms_the_whole_width_and_attends_causally():
    w = _weights(1)
    p = w["layer_3"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 24))
    rms = lambda y, g: y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6) * g  # noqa: E731
    heads = lambda y: y.reshape(2, 12, 3, 8)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = ref.full_attention(x, p, SMALL, jnp.float32, lambda a: a)
        q, k, v = heads(rms(x @ p["w_q"], p["q_norm"])), heads(rms(x @ p["w_k"], p["k_norm"])), heads(x @ p["w_v"])
        s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(8)
        s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -jnp.inf)
        want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v).reshape(2, 12, 24) @ p["w_o"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        later = ref.full_attention(x.at[:, 7:].add(1.0), p, SMALL, jnp.float32, lambda a: a)
    np.testing.assert_allclose(np.asarray(later[:, :7]), np.asarray(got[:, :7]), atol=1e-6)


@pytest.mark.parametrize("flag", ref.FLAGS)
def test_each_flag_changes_the_forward(flag):
    w = _weights(2)
    x = jax.random.randint(jax.random.PRNGKey(3), (2, 44, 44, 3), 0, 256).astype(jnp.uint8)
    with jax.default_matmul_precision("highest"):
        q, none = ref.forward(w, x, SMALL)
        other, _ = ref.forward(w, x, dict(SMALL, **{flag: True}))
    assert none is None and q.shape == (2, 5)
    assert float(jnp.max(jnp.abs(other - q))) > 1e-3 * float(jnp.std(q))


def test_rows_one_at_a_time_or_all_at_once():
    w = _weights(3)
    x = jax.random.randint(jax.random.PRNGKey(4), (4, 44, 44, 3), 0, 256).astype(jnp.uint8)
    with jax.default_matmul_precision("highest"):
        a, _ = ref.forward(w, x, SMALL, row_block=1)
        b, _ = ref.forward(w, x, SMALL, row_block=4)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_the_seeded_weights_follow_their_laws():
    cfg = dict(SMALL, hidden_size=48, intermediate_size=96, linear_key_head_dim=8,
               linear_value_head_dim=16)
    w = _weights(4, cfg)
    run = w["layer_1"]
    assert run["w_q"].shape == (48, 24) and run["w_v"].shape == (48, 48)
    assert run["conv_v"].shape == (48, 4) and run["A_log"].shape == (3,)
    a, dt = np.exp(np.asarray(run["A_log"])), np.asarray(jax.nn.softplus(run["dt_bias"]))
    assert (1 <= a).all() and (a <= 16).all() and (1e-3 <= dt).all() and (dt <= 0.1 + 1e-6).all()
    assert abs(float(jnp.std(run["w1"])) - 48 ** -0.5) < 0.1 * 48 ** -0.5     # fan-in
    assert abs(float(jnp.std(run["conv_q"])) - 0.5) < 0.1                      # four taps
    assert abs(float(jnp.mean(w["layer_3"]["q_norm"])) - 1.0) < 0.05
    assert ref.param_count(cfg) == sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(w))


def test_the_map_to_the_programs_tree_and_back():
    w = _weights(5)
    p = ref.to_program_params(w, SMALL, jnp.bfloat16)["params"]
    assert set(p) >= {"layers_0_2", "layer_3", "w_tok", "final_norm", "Conv_0", "Dense_3"}
    linear = p["layers_0_2"]["linear_attention"]
    assert linear["A_log"].dtype == jnp.float32 and linear["dt_bias"].dtype == jnp.float32
    assert linear["w_q"].dtype == jnp.bfloat16 and p["layers_0_2"]["dense"]["w1"].shape == (3, 24, 40)
    assert set(p["layer_3"]["full_attention"]) == {"w_q", "w_k", "w_v", "q_norm", "k_norm", "w_o"}
    back = ref.from_program_params(ref.to_program_params(w, SMALL), SMALL)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(w)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_one_learner_step_moves_every_leaf_and_a_lower_precision_moves_it_elsewhere():
    w, target = _weights(6), _weights(7)
    x = jax.random.randint(jax.random.PRNGKey(8), (2, 44, 44, 3), 0, 256).astype(jnp.uint8)
    batch = dict(obs=x, next_obs=x[::-1], action=jnp.array([0, 3]), reward=jnp.ones(2),
                 discount=jnp.full((2,), 0.9), is_weights=jnp.ones(2))
    cfg = dict(SMALL, optimizer="rmsprop", learning_rate=6.25e-5, rmsprop_decay=0.95,
               rmsprop_eps=1.5e-7, max_grad_norm=40.0, loss="squared")
    nu = jax.tree_util.tree_map(lambda v: jnp.full(v.shape, 1e-4), w)
    new, _, delta, prio, loss = ref.learner_step(w, target, nu, batch, cfg)
    assert np.isfinite(float(loss)) and prio.shape == (2,)
    np.testing.assert_allclose(np.asarray(prio), np.abs(np.asarray(delta)) + 1e-6, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new), jax.tree_util.tree_leaves(w)):
        assert float(jnp.max(jnp.abs(a - b))) > 0, jax.tree_util.keystr(path)
    low = ref.learner_step(w, target, nu, batch, cfg, "bf16_held")[0]
    assert float(jnp.max(jnp.abs(low["w_tok"] - new["w_tok"]))) > 0


def test_the_cells_configuration_names_this_reference():
    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "olmoh_q_l4.json"))
    assert cfg["reference"] == "olmoh_q" and ref.param_count(cfg) == 836_784_807
    assert ref.layer_runs(cfg) == [(0, 3), (3, 1)]
    assert ref.layer_kinds(cfg) == ["linear_attention"] * 3 + ["full_attention"]

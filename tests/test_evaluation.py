"""Evaluation harness (evaluation.py): greedy eval fleet, human-normalized
scoring, runtime wiring (--eval-every) — the scoring path for the north-star
"Atari median human-normalized score" metric that the reference lacks
entirely (its only metric is the exploring actor's episode-reward print,
reference actor.py:177)."""

import numpy as np
import pytest

from ape_x_dqn_tpu.config import ApexConfig
from ape_x_dqn_tpu.envs.core import StepResult
from ape_x_dqn_tpu.evaluation import (
    GreedyEvaluator,
    canonical_game,
    human_normalized,
    median_human_normalized,
)


class TestScoreTable:
    def test_canonical_game_strips_suffixes(self):
        assert canonical_game("PongNoFrameskip-v4") == "Pong"
        assert canonical_game("Pong-v4") == "Pong"
        assert canonical_game("PongDeterministic-v4") == "Pong"
        assert canonical_game("pong") == "Pong"
        assert canonical_game("chain:6") == "chain"

    def test_canonical_game_strips_namespace_prefix(self):
        # gymnasium v5 spelling (round-4 advisor: eval/hns silently became
        # None for namespaced ids).
        assert canonical_game("ALE/Pong-v5") == "Pong"
        assert canonical_game("ALE/MsPacman-v5") == "MsPacman"
        assert canonical_game("gym:ALE/Pong-v5") == "Pong"
        assert canonical_game("gym:CartPole-v1") == "CartPole"

    def test_human_normalized_anchors(self):
        # By construction: random play = 0, human = 1.
        assert human_normalized("PongNoFrameskip-v4", -20.7) == pytest.approx(0.0)
        assert human_normalized("PongNoFrameskip-v4", 14.6) == pytest.approx(1.0)
        # Superhuman > 1 (Ape-X's regime on most games).
        assert human_normalized("BreakoutNoFrameskip-v4", 300.0) > 1.0

    def test_non_atari_returns_none(self):
        assert human_normalized("chain:6", 1.0) is None
        assert human_normalized("catch", 0.5) is None

    def test_median_over_suite(self):
        scores = {
            "PongNoFrameskip-v4": 14.6,       # hns 1.0
            "BreakoutNoFrameskip-v4": 1.7,    # hns 0.0
            "SeaquestNoFrameskip-v4": 21061.55,  # hns ~0.5
            "chain:6": 1.0,                   # excluded (no table entry)
        }
        assert median_human_normalized(scores) == pytest.approx(0.5, abs=1e-3)
        assert median_human_normalized({"chain:6": 1.0}) is None

    def test_table_covers_sweep_suite(self):
        import sys
        from pathlib import Path

        sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
        try:
            from sweep import ATARI_57
        finally:
            sys.path.pop(0)
        from ape_x_dqn_tpu.evaluation import ATARI_HUMAN_RANDOM

        missing = [g for g in ATARI_57 if g not in ATARI_HUMAN_RANDOM]
        assert not missing, f"no human/random entry for: {missing}"


class FixedEpisodeEnv:
    """Every episode: 4 steps of reward 2.5 then terminate — score 10.0
    regardless of policy.  Isolates the evaluator's episode accounting."""

    observation_shape = (3,)
    num_actions = 2

    def __init__(self):
        self._t = 0

    def reset(self, seed=None):
        self._t = 0
        return np.zeros(3, np.uint8)

    def step(self, action):
        self._t += 1
        return StepResult(np.zeros(3, np.uint8), 2.5, self._t >= 4, False)


class TestGreedyEvaluator:
    def test_counts_episodes_and_scores(self):
        import jax

        from ape_x_dqn_tpu.models.dueling import DuelingMLP

        net = DuelingMLP(num_actions=2, hidden_sizes=(8,))
        params = net.init(jax.random.PRNGKey(0), np.zeros((1, 3), np.uint8))
        ev = GreedyEvaluator(
            [FixedEpisodeEnv] * 3, net, env_name="fixed", seed=1
        )
        res = ev.evaluate(params, episodes=7)
        assert len(res.episodes) == 7
        assert res.mean_score == pytest.approx(10.0)
        assert res.median_score == pytest.approx(10.0)
        assert res.hns is None  # not an Atari game

    def test_repeated_evals_sample_independent_starts(self):
        """Successive evaluate() calls must NOT replay identical initial
        conditions (round-4 advisor: same reset seed + rng step 0 every call
        gave correlated score estimates over training)."""
        import jax

        from ape_x_dqn_tpu.models.dueling import DuelingMLP

        net = DuelingMLP(num_actions=2, hidden_sizes=(8,))
        params = net.init(jax.random.PRNGKey(0), np.zeros((1, 3), np.uint8))
        ev = GreedyEvaluator(
            [FixedEpisodeEnv] * 2, net, env_name="fixed", seed=1
        )
        seeds = []
        inner_reset = ev.envs.reset
        ev.envs.reset = lambda seed=None: (seeds.append(seed), inner_reset(seed=seed))[1]
        ev.evaluate(params, episodes=2)
        ev.evaluate(params, episodes=2)
        ev.evaluate(params, episodes=2)
        assert len(set(seeds)) == 3, f"reset seeds repeated: {seeds}"

    def test_trained_chain_policy_scores_optimal(self):
        """Greedy eval of a trained chain policy: every episode reaches the
        terminal (+1) — eval/score reports the POLICY's quality, not the
        ε-ladder's exploration returns (which hover near 0 on the chain)."""
        from ape_x_dqn_tpu.runtime import SingleProcessDriver

        cfg = ApexConfig()
        cfg.env.name = "chain:6"
        cfg.network = "mlp"
        cfg.actor.num_actors = 4
        cfg.actor.flush_every = 8
        cfg.actor.gamma = 0.8
        cfg.learner.min_replay_mem_size = 200
        cfg.learner.q_target_sync_freq = 25
        cfg.learner.learning_rate = 3e-3
        cfg.learner.optimizer = "adam"
        cfg.replay.capacity = 5000
        cfg.validate()
        driver = SingleProcessDriver(cfg, learner_steps_per_iter=4)
        driver.run(learner_steps=1500)
        ev = GreedyEvaluator(
            driver.comps.env_fns[:2], driver.network,
            env_name=cfg.env.name, seed=7,
        )
        res = ev.evaluate(driver.state.params, episodes=4)
        assert res.mean_score == pytest.approx(1.0), res
        assert res.hns is None


class TestHNSEndToEnd:
    """The median-HNS aggregation path exercised END TO
    END — real GreedyEvaluator rollouts over the full DQN wrapper stack on
    the ALE-faithful fake emulator, scores flowing through the human/random
    table into the suite-level median, with an unknown-game fallback."""

    GAMES = {
        # table id -> per-step reward of that fake "game" (clip off, so
        # magnitudes differ and each game lands a distinct raw score).
        "PongNoFrameskip-v4": 3.0,
        "ALE/Breakout-v5": 7.0,
        "SeaquestNoFrameskip-v4": 11.0,
    }

    @staticmethod
    def _env_fn(reward):
        from ape_x_dqn_tpu.envs.atari import wrap_dqn
        from ape_x_dqn_tpu.envs.fake_atari import FakeAtariEnv

        # clip_rewards=False: the raw reward magnitude IS the game's
        # signature, so the three games produce three distinct scores.
        return lambda: wrap_dqn(
            FakeAtariEnv(reward=reward), frame_skip=4, clip_rewards=False
        )

    def test_median_hns_over_fake_atari_suite(self):
        import jax

        from ape_x_dqn_tpu.models.dueling import DuelingMLP

        net = DuelingMLP(num_actions=4, hidden_sizes=(16,))
        params = net.init(
            jax.random.PRNGKey(0), np.zeros((1, 84, 84, 1), np.uint8)
        )
        suite_scores = {}
        per_game_hns = {}
        for name, reward in self.GAMES.items():
            ev = GreedyEvaluator(
                [self._env_fn(reward)] * 2, net, env_name=name, seed=3
            )
            res = ev.evaluate(params, episodes=2)
            assert len(res.episodes) == 2
            assert np.isfinite(res.mean_score)
            # The evaluator itself routed the score through the table.
            assert res.hns == pytest.approx(
                human_normalized(name, res.mean_score)
            )
            suite_scores[name] = res.mean_score
            per_game_hns[name] = res.hns
        # Distinct games produced distinct scores (the suite isn't
        # degenerately measuring one curve three times).
        assert len(set(suite_scores.values())) == 3
        # Unknown-game fallback: a fake game with no table entry is
        # EXCLUDED from the median, not scored as zero.
        ev = GreedyEvaluator(
            [self._env_fn(5.0)] * 2, net, env_name="fake-atari", seed=3
        )
        res_unknown = ev.evaluate(params, episodes=2)
        assert res_unknown.hns is None
        suite_scores["fake-atari"] = res_unknown.mean_score
        med = median_human_normalized(suite_scores)
        assert med == pytest.approx(
            float(np.median(sorted(per_game_hns.values())))
        )
        # All-unknown suite: no headline rather than a fabricated one.
        assert median_human_normalized(
            {"fake-atari": 1.0, "also-not-a-game": 2.0}
        ) is None


class TestRuntimeWiring:
    def test_async_pipeline_emits_eval_metrics(self):
        import io
        import json

        from ape_x_dqn_tpu.runtime import AsyncPipeline
        from ape_x_dqn_tpu.utils.metrics import MetricLogger

        cfg = ApexConfig()
        cfg.env.name = "chain:6"
        cfg.network = "mlp"
        cfg.actor.num_actors = 4
        cfg.actor.flush_every = 8
        cfg.learner.min_replay_mem_size = 256
        cfg.learner.optimizer = "adam"
        cfg.learner.learning_rate = 1e-3
        cfg.replay.capacity = 10_000
        cfg.validate()
        buf = io.StringIO()
        pipe = AsyncPipeline(
            cfg, logger=MetricLogger(stream=buf), log_every=50,
            eval_every=60, eval_episodes=2,
        )
        pipe.run(learner_steps=130, warmup_timeout=120.0)
        assert len(pipe.eval_scores) >= 2  # evals at ~60 and ~120
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert any("eval/score" in rec for rec in lines)

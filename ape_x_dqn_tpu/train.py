"""CLI entry point: ``python -m ape_x_dqn_tpu.train [--params-file F]``.

Mirrors the reference's orchestrator (``python main.py --params-file
PARAMSFILE`` — reference main.py:12-16, README.md:15-16) with the same
config vocabulary (the reference's parameters.json loads directly) plus:

  * ``--set section.field=value`` overrides (no editing JSON to try a knob);
  * ``--mode async|sync`` — the async actors∥replay∥learner pipeline
    (default, the Ape-X architecture) or the deterministic single-process
    round-robin (the race-free golden path, SURVEY §5);
  * ``--steps N`` learner-step cap (the reference hard-codes T=500000 in
    code, main.py:46);
  * JSONL metrics to stdout and optionally ``--metrics-file``.
"""

from __future__ import annotations

import argparse
import sys

from ape_x_dqn_tpu.config import load_config, to_dict
from ape_x_dqn_tpu.utils import profiling
from ape_x_dqn_tpu.utils.metrics import MetricLogger


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ape_x_dqn_tpu.train",
        description="TPU-native Ape-X DQN trainer",
    )
    p.add_argument(
        "--params-file",
        default=None,
        help="JSON config (native or reference parameters.json format)",
    )
    p.add_argument(
        "--set",
        dest="overrides",
        action="append",
        default=[],
        metavar="PATH=VALUE",
        help="config override, e.g. --set actor.num_actors=64",
    )
    p.add_argument("--mode", choices=("async", "sync"), default="async")
    p.add_argument(
        "--steps", type=int, default=None, help="learner steps (default: config)"
    )
    p.add_argument("--metrics-file", default=None, help="also write JSONL here")
    p.add_argument(
        "--eval-every", type=int, default=0, metavar="STEPS",
        help="greedy-evaluate (ε≈0.001, no emission) every N learner steps, "
        "logging eval/score and — for Atari games — eval/hns (human-"
        "normalized, evaluation.py); 0 disables",
    )
    p.add_argument(
        "--eval-episodes", type=int, default=10,
        help="episodes per evaluation pass",
    )
    p.add_argument(
        "--tensorboard-dir", default=None,
        help="also write scalar metrics as TensorBoard events here",
    )
    p.add_argument("--log-every", type=int, default=500)
    p.add_argument(
        "--profile-dir", default=None,
        help="capture a jax.profiler device trace of the run into this dir "
        "(TensorBoard-viewable); a profiler that cannot start fails the run",
    )
    p.add_argument(
        "--profile-port", type=int, default=None,
        help="start the live jax.profiler server on this port "
        "(attach with TensorBoard's profile tab)",
    )
    p.add_argument(
        "--coordinator", default=None, metavar="HOST:PORT",
        help="multi-host SPMD: jax.distributed coordinator address; run the "
        "SAME command on every host with its own --process-id "
        "(parallel/multihost.py)",
    )
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    return p


def main(argv=None, inspect=None) -> int:
    """Run the trainer.  ``inspect(pipeline, final_record)``, when given, is
    called after an async run completes — chip_smoke.py uses it to check
    where the train state and the replay ring live."""
    args = build_argparser().parse_args(argv)
    profiling.launch.begin()
    if args.coordinator:
        # Must run before anything touches the jax backend: after this,
        # jax.devices() is the GLOBAL device set across all participating
        # hosts and learner.data_parallel spans it.
        if args.num_processes is None or args.process_id is None:
            raise SystemExit(
                "--coordinator requires --num-processes and --process-id"
            )
        from ape_x_dqn_tpu.parallel.multihost import initialize_multihost

        initialize_multihost(
            args.coordinator, args.num_processes, args.process_id
        )
    cfg = load_config(args.params_file, overrides=args.overrides)
    from ape_x_dqn_tpu.parallel.mesh import device_info
    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Named on the run's first record (and the config line) so a CPU run
    # can never be read as a chip run.
    device = device_info()
    print("config:", {"device": device, **to_dict(cfg)}, file=sys.stderr)
    logger = MetricLogger(
        stream=sys.stdout,
        path=args.metrics_file,
        tensorboard_dir=args.tensorboard_dir,
    )
    logger.event("run_device", **device)
    import contextlib

    if args.profile_port is not None:
        import jax

        jax.profiler.start_server(args.profile_port)  # raises if it cannot
    profile_ctx = (
        profiling.trace(args.profile_dir) if args.profile_dir
        else contextlib.nullcontext()
    )
    with profile_ctx:
        return _run(args, cfg, logger, inspect)


def _run(args, cfg, logger, inspect=None) -> int:
    if args.mode == "async":
        from ape_x_dqn_tpu.runtime import AsyncPipeline

        with profiling.launch.span("pipeline"):
            pipe = AsyncPipeline(
                cfg, logger=logger, log_every=args.log_every,
                eval_every=args.eval_every, eval_episodes=args.eval_episodes,
            )
        final = pipe.run(learner_steps=args.steps)
        print("final:", final, file=sys.stderr)
        if inspect is not None:
            inspect(pipe, final)
    else:
        from ape_x_dqn_tpu.runtime import SingleProcessDriver

        driver = SingleProcessDriver(cfg)
        evaluator = None
        next_eval = args.eval_every
        target = args.steps if args.steps is not None else cfg.learner.total_steps
        while driver.learner_step < target:
            res = driver.run_iteration()
            for e in res.episodes:
                logger.log("episode/return", e.episode_return)
                logger.log("episode/length", e.episode_length)
            if res.loss == res.loss:  # not NaN
                logger.log("learner/loss", res.loss)
                logger.log("learner/mean_q", res.mean_q)
            if args.eval_every and driver.learner_step >= next_eval:
                from ape_x_dqn_tpu.evaluation import log_result, make_evaluator

                while next_eval <= driver.learner_step:
                    next_eval += args.eval_every
                if evaluator is None:
                    evaluator = make_evaluator(
                        driver.comps.env_fns, driver.network,
                        env_name=cfg.env.name, seed=cfg.seed,
                    )
                log_result(logger, evaluator.evaluate(
                    driver.state.params, episodes=args.eval_episodes
                ))
            if (
                driver.learner_step
                and driver.learner_step % args.log_every == 0
            ):
                logger.emit(
                    step=driver.learner_step,
                    actor_steps=res.actor_steps,
                    replay_size=res.replay_size,
                )
            if driver.fleet.step_count >= cfg.actor.T:
                break
        logger.emit(
            step=driver.learner_step,
            actor_steps=driver.total_actor_steps,
            replay_size=driver.replay.size(),
            final=True,
        )
    logger.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

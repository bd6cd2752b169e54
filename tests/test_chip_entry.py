"""Bring-up contracts that a CPU can check: where the compile cache goes,
that chip_smoke.py refuses to run without a TPU, that nothing on the main
path swallows a failed profiler or a dead attached trainer, and that every
child a chip owner starts is pinned to the CPU."""

import io
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE = (
    "import jax; "
    "from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache; "
    "print(enable_compile_cache()); "
    "print(jax.config.jax_compilation_cache_dir); "
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)"
)


def _run_cache_probe(cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env_over)
    return subprocess.run(
        [sys.executable, "-c", _PRINT_CACHE], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split()


class TestCompileCache:
    def test_default_is_checkout_jax_cache_from_any_process(
            self, tmp_path, monkeypatch):
        from ape_x_dqn_tpu.utils.compile_cache import cache_dir

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert cache_dir() == want            # this process
        returned, configured, min_s = _run_cache_probe(str(tmp_path))
        assert returned == configured == want  # another one, another cwd
        assert float(min_s) == 0.0

    def test_env_variable_wins_and_nothing_overrides_it(self, tmp_path):
        placed = str(tmp_path / "placed")
        returned, configured, _ = _run_cache_probe(
            str(tmp_path), JAX_COMPILATION_CACHE_DIR=placed
        )
        assert returned == configured == placed

    def test_no_other_module_sets_a_cache_directory(self):
        pat = re.compile(
            r"jax_compilation_cache_dir|set_cache_dir|initialize_cache"
        )
        allowed = {
            os.path.join("ape_x_dqn_tpu", "utils", "compile_cache.py"),
            os.path.join("tests", "test_chip_entry.py"),
        }
        hits = []
        for root, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d not in ("__pycache__", "chiprun_out")]
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                rel = os.path.relpath(path, REPO)
                if rel in allowed:
                    continue
                with open(path, encoding="utf-8") as f:
                    if pat.search(f.read()):
                        hits.append(rel)
        assert not hits, hits

    def test_cache_path_has_no_temp_pid_or_clock(self):
        src = open(os.path.join(
            REPO, "ape_x_dqn_tpu", "utils", "compile_cache.py"
        )).read()
        code = src.split('"""', 2)[2]  # past the module docstring
        for word in ("tempfile", "getpid", "time.", "uuid", "random"):
            assert word not in code, word


class TestChipSmokeRefusesWithoutAChip:
    def test_cpu_backend_exits_nonzero_before_running_anything(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert "needs a TPU" in proc.stderr, proc.stderr[-1000:]
        # No leg ran, no result line was printed.
        assert proc.stdout.strip() == "", proc.stdout[-500:]

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        import shutil

        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], env=env, cwd=str(tmp_path),
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


def test_profiling_trace_raises_when_the_profiler_cannot_start(
        tmp_path, monkeypatch):
    import jax

    from ape_x_dqn_tpu.utils.profiling import trace

    def boom(logdir, **_options):
        raise RuntimeError("profiler plugin missing")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="profiler plugin missing"):
        with trace(str(tmp_path)):
            pytest.fail("the body must not run without a trace")


def test_serve_attach_fails_when_the_trainer_dies(tmp_path):
    """The attached trainer's actors exhaust actor.T long before warmup can
    fill: pipe.run raises in its thread, and serve must not return 0."""
    from ape_x_dqn_tpu import serve

    with pytest.raises(RuntimeError, match="attached trainer died") as ei:
        serve.main([
            "--attach", "--duration", "0", "--steps", "10",
            "--metrics-file", str(tmp_path / "m.jsonl"),
            "--metrics-every", "0.2",
            "--set", "network=mlp", "--set", "env.name=chain:6",
            "--set", "actor.num_actors=2", "--set", "actor.T=8",
            "--set", "serving.max_batch=2",
        ])
    assert "actors exhausted" in str(ei.value.__cause__)


class _FakePopen:
    captured: dict = {}

    def __init__(self, cmd, env=None, **kw):
        type(self).captured = dict(env)
        self.stdout = io.StringIO("")
        self.pid = 0
        self.returncode = 0

    def poll(self):
        return 0


@pytest.mark.parametrize("explicit_env", [True, False])
def test_replica_spawn_assigns_the_cpu_whatever_the_parent_says(
        monkeypatch, explicit_env):
    from ape_x_dqn_tpu.serving import router

    monkeypatch.setattr(router.subprocess, "Popen", _FakePopen)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # what a chip host exports
    env = {"JAX_PLATFORMS": "tpu", "PATH": os.environ["PATH"]}
    rep = router.ReplicaProcess(
        0, hub_host="127.0.0.1", hub_port=1, hub_token=1,
        env=env if explicit_env else None,
    )
    rep.spawn()
    assert _FakePopen.captured["JAX_PLATFORMS"] == "cpu"


@pytest.mark.parametrize("lost", [None, "the_target_bank"])
def test_the_chips_timing_of_the_first_convolution_runs_here(monkeypatch, lost):
    """``chip_smoke.first_conv_of_two_on_the_chip`` at two rows: it passes on
    ``dueling.first_conv_of_two`` as it is and returns the four timings; a
    joined convolution that hands the online half to both nets fails it."""
    import chip_smoke
    from ape_x_dqn_tpu.models import dueling

    shapes = ((2, 1, 8),)
    if lost:
        whole = dueling.first_conv_of_two
        monkeypatch.setattr(dueling, "first_conv_of_two",
                            lambda *args: (whole(*args)[0],) * 2)
        with pytest.raises(AssertionError, match="one of 16 differs from two of 8"):
            chip_smoke.first_conv_of_two_on_the_chip(shapes=shapes, repeats=1)
        return
    (row,) = chip_smoke.first_conv_of_two_on_the_chip(shapes=shapes, repeats=1)
    assert row["obs"] == [2, 84, 84, 1] and row["outputs"] == 8 and row["equal_bits"]
    assert all(row[f"{name}_us"] > 0 for name in (
        "apart", "joined", "apart_then_second", "joined_then_second"))


@pytest.mark.parametrize("lost", [None, "_chunk_scalar", "_chunk"])
def test_the_chips_check_of_the_delta_walk_runs_here(monkeypatch, lost):
    """``chip_smoke.leg_olmo_kernels`` at a small size (3 heads, keys of 12,
    values of 24, 40 tokens in chunks of 16): each form of the walk is inside
    the limit of the token-by-token recurrence and the same walk with
    ``beta`` held to 1 is not; a form that lost its decay fails the leg."""
    import chip_smoke
    from ape_x_dqn_tpu.ops import chunked_delta

    small = chip_smoke.walk_against_the_recurrence
    monkeypatch.setattr(
        chip_smoke, "walk_against_the_recurrence", lambda per_channel=False, **cell: small(
            rows=2, heads=3, tokens=40, kw=12, vw=24, chunk=16, repeats=1, per_channel=per_channel))
    if lost:
        walk = getattr(chunked_delta, lost)
        monkeypatch.setattr(chunked_delta, lost,
                            lambda state, q, k, v, g, beta: walk(state, q, k, v, 0.0 * g, beta))
        with pytest.raises(AssertionError, match="from the recurrence"):
            chip_smoke.leg_olmo_kernels()
        return
    chip_smoke.leg_olmo_kernels()
    for per_channel in (False, True):
        near, times = chip_smoke.walk_against_the_recurrence(per_channel=per_channel)
        assert set(near) == {"o", "dq", "dk", "dv", "dg", "dbeta"}
        assert all(rel <= chip_smoke.OLMO_WALK_REL for rel, _ in near.values())
        assert times["forward_us"] > 0 and times["forward_and_backward_us"] > 0


@pytest.mark.parametrize("lost", [None, "the_byte_order"])
def test_the_chips_timing_of_a_gathered_side_runs_here(monkeypatch, lost):
    """``chip_smoke.fetch_against_three_ops_on_the_chip`` at a row of one
    tile: it passes on ``row_fetch.fetch_turned`` as it is, through
    ``dedup_fetch``, and returns the timings; a kernel whose packed word
    holds its first channel last fails it."""
    import chip_smoke
    from ape_x_dqn_tpu.ops.pallas import row_fetch

    run = lambda: chip_smoke.fetch_against_three_ops_on_the_chip(  # noqa: E731
        batches=(128,), obs_shape=(32, 32, 4), frames=16, repeats=1)
    if lost:
        whole = row_fetch.fetch_turned
        monkeypatch.setattr(row_fetch, "fetch_turned", lambda *args: whole(*args)[..., ::-1])
        with pytest.raises(AssertionError, match="bytes are not the gather's at 128 rows"):
            run()
        return
    (row,) = run()
    assert row["rows"] == 128 and row["ring"] == [16, 8, 128] and row["equal_bits"]
    assert all(row[f"{name}_us"] > 0 for name in (
        "kernel_then_conv", "three_ops_then_conv", "conv"))

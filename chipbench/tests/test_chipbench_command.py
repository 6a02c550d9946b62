"""The command refuses to report where there is no chip or no program."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "ridge-fig7.gd-mc16", "--seed", "3", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_command_without_a_tpu_exits_nonzero(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR":
           str(tmp_path / "cache")}
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_command_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert _no_result(p.stdout)

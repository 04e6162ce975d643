"""The command refuses, with no result line, where a number would not be
a chip's: no TPU, or a tree that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CMD = [sys.executable, "-m", "benchmark.run", "--workload",
       "gpt3-350m.pretrain_2k", "--seed", "1", "--seconds", "1",
       "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env.pop("PYTHONPATH", None)
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=240)


def test_no_tpu_no_result():
    p = run(ROOT)
    assert p.returncode not in (0, None)
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""

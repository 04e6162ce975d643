"""The chip-facing surface, as far as a CPU can check it: no path falls back
to the CPU silently, the compile cache lives where the operator says, and the
``chip_smoke.py`` command still runs end to end (as a labelled rehearsal).

What the smoke proves about the chip itself only a chip run can show:
``chiprun -- python chip_smoke.py`` (README, "On the chip through the tool").
"""
import json
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- no silent fallback


def test_chip_smoke_refuses_a_host_without_a_chip(capsys):
    """Default command, CPU-only host: non-zero, says what it found, prints
    no result line — and gets there before building any model."""
    import chip_smoke

    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and "cpu" in err
    assert '"ok"' not in out


def test_tpu_place_raises_without_an_accelerator():
    """A CPU is never handed back as an accelerator place."""
    assert paddle.device_count() == 0
    with pytest.raises(RuntimeError, match="no TPU/accelerator"):
        paddle.TPUPlace()
    with pytest.raises(RuntimeError, match="no TPU/accelerator"):
        paddle.set_device("tpu")
    assert paddle.set_device("cpu").is_cpu_place()


def test_cost_model_knows_v5e_and_refuses_unknown_tpus():
    from paddle_tpu.cost_model import (HOST_CPU, TPU_V4, TPU_V5E, CostModel,
                                       device_spec)

    class Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    assert device_spec(Dev("TPU v5 lite")) is TPU_V5E
    assert (TPU_V5E.peak_flops, TPU_V5E.hbm_bandwidth) == (197e12, 819e9)
    assert device_spec(Dev("TPU v4")) is TPU_V4
    with pytest.raises(ValueError, match="TPU v9"):
        device_spec(Dev("TPU v9"))
    assert CostModel().device is HOST_CPU          # planner on CPU: unchanged


# ------------------------------------------------------------ compile cache


def test_compile_cache_dir_env_wins_and_is_left_alone(monkeypatch, tmp_path):
    from paddle_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: pytest.fail(
        f"cache helper touched jax.config with the env set: {a}"))
    assert compile_cache.enable_compile_cache() == str(tmp_path)


def test_compile_cache_dir_default_is_fixed_and_in_checkout(monkeypatch):
    from paddle_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.compile_cache_dir()
    assert path == os.path.join(REPO, ".jax_cache")
    assert path == compile_cache.compile_cache_dir()      # no pid/timestamp
    assert not path.startswith("/tmp") and str(os.getpid()) not in path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------- rehearsal


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_cpu_rehearsal_runs_every_phase_and_labels_itself(chips, tmp_path):
    """``--rehearse-cpu``: trainer, kernel (Pallas interpreted) and server
    all pass at toy sizes, every line of the verdict says rehearsal-on-cpu,
    and the compile cache lands only where JAX_COMPILATION_CACHE_DIR says.
    (slow: ~15 s / ~30 s of subprocess)"""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu", "--chips", str(chips)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": True, "rehearsal": True,
                       "device": {"platform": "cpu", "kind": "cpu",
                                  "count": chips}}
    obs = json.loads(lines[-2])
    assert obs["rehearsal"] is True and obs["chips_driven"] == chips
    phases = obs["smoke_observations"]
    assert set(phases) >= {"trainer", "kernel", "server"}
    assert phases["trainer"]["num_compiles"] == 1
    assert phases["server"]["steady_state_recompiles"] == 0
    assert phases["server"]["prefix_hits"] >= 1
    assert "REHEARSAL on cpu" in out.stdout
    assert f"compile cache: {tmp_path}" in out.stdout
    assert os.listdir(tmp_path), "nothing was cached under the env dir"
    if chips == 4:
        assert phases["dryrun_body"] == "ok"
        assert len(phases["trainer"]["shards"]) == 3       # master + moments
        assert len(phases["server"]["shards"]) == 2        # K and V pools

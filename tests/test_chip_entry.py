"""Start-up and device selection, checked from outside the process:
where the compile cache lands, what chip_smoke.py does without an
accelerator, and what `agent -tpu` does when the backend cannot come
up. Every case is a child interpreter, because each property is about
what happens BEFORE a backend initializes."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRINT_CACHE_DIR = ("import jax, nomad_tpu.ops.binpack; "
                   "print(jax.config.jax_compilation_cache_dir)")


def run(argv, timeout=120, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base["PYTHONPATH"] = os.pathsep.join(
        p for p in [REPO, os.environ.get("PYTHONPATH", "")] if p)
    base["JAX_PLATFORMS"] = "cpu"
    base.update(env)
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, env=base, timeout=timeout,
        capture_output=True, text=True)


def test_compile_cache_dir_set_from_outside_is_left_alone(tmp_path):
    out = run(["-c", PRINT_CACHE_DIR],
              JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)


def test_compile_cache_dir_defaults_to_the_checkout():
    out = run(["-c", PRINT_CACHE_DIR])
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == os.path.join(REPO, ".jax_cache")


def test_chip_smoke_refuses_a_cpu_and_prints_no_result():
    out = run(["chip_smoke.py"], timeout=60)
    assert out.returncode not in (0, None)
    assert "platform=cpu" in out.stdout
    assert "'cpu'" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_chip_smoke_rehearsal_runs_every_phase_on_the_cpu():
    """The tiny-size rehearsal keeps the script itself from rotting
    between chip runs: same phases, same counters, same checks."""
    out = run(["chip_smoke.py", "--rehearse"], timeout=300)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    *_, summary_line, verdict_line = out.stdout.splitlines()
    # The last line is the verdict and nothing else, key for key.
    verdict = json.loads(verdict_line)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["count"], int)
    assert summary_line.startswith("summary: ")
    summary = json.loads(summary_line[len("summary: "):])
    assert summary["ok"] and summary["rehearsal"]
    assert summary["device"] == verdict["device"]
    assert summary["device"]["platform"] == "cpu"
    assert summary["differential"]["violations"] == 0
    assert summary["waves"][-1]["jit_cache_size"] > 0


def test_agent_tpu_does_not_start_without_a_backend():
    out = run(["-m", "nomad_tpu.cli", "agent", "-dev", "-tpu",
               "-port", "0"], timeout=60, JAX_PLATFORMS="no_such_backend")
    assert out.returncode == 1
    assert "error initializing the JAX backend" in out.stderr
    assert "agent started" not in out.stdout

"""CLI launcher + example smoke tests (subprocess, tiny configs)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")


def _run(args, timeout=420):
    env = dict(os.environ, PYTHONPATH=SRC)
    return subprocess.run([sys.executable] + args, env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_train_cli(tmp_path):
    out = _run(["-m", "repro.launch.train", "--arch", "xlstm-125m",
                "--steps", "3", "--batch", "2", "--seq", "32",
                "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "step    2" in out.stdout
    assert any(f.startswith("step_") for f in os.listdir(tmp_path))


@pytest.mark.slow
def test_serve_cli():
    out = _run(["-m", "repro.launch.serve", "--arch", "paligemma-3b",
                "--tokens", "4", "--batch", "2"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "generated 4 tokens" in out.stdout


@pytest.mark.slow
def test_quickstart_example():
    out = _run([os.path.join(ROOT, "examples", "quickstart.py")])
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("OK")


def test_benchmark_modules_import():
    sys.path.insert(0, ROOT)
    import benchmarks.run  # noqa: F401
    from benchmarks import (bench_kernels, bench_roofline, fig_avg_ms,
                            fig_cost_vs_dn, fig_cost_vs_nm, fig_ddpg_cost,
                            fig_hfl_convergence)  # noqa: F401


def test_dryrun_help():
    out = _run(["-m", "repro.launch.dryrun", "--help"], timeout=120)
    assert out.returncode == 0
    assert "--multi-pod" in out.stdout


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else a fixed directory at
    the repo root — never a per-run path."""
    from repro import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.cache_dir() == os.path.join(
        os.path.realpath(ROOT), ".jax_cache")


def test_chip_smoke_refuses_without_tpu():
    """No CPU fallback: on the CPU the script exits nonzero and never
    prints its passing line."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr

"""runtime.py and the entry points' refusal to pass for a chip run.

What PR 21 added: one platform predicate, one place for the compile
cache, ``--device tpu`` that means it, and ``chip_smoke.py`` — which on
this CPU-only box must fail, fast, by name, from a parent that never
imports jax.
"""

import os
import subprocess
import sys
import time

import jax
import pytest

from distributedtensorflow_tpu import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, *, cwd=REPO, timeout=120, **env):
    full = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    full.update(env)
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, capture_output=True, text=True,
        timeout=timeout, env=full,
    )


def test_predicate_and_summary_describe_this_backend():
    assert runtime.on_tpu() is False
    assert runtime.device_summary() == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    with pytest.raises(SystemExit, match="requires a tpu device"):
        runtime.require_tpu()


def test_compile_cache_env_is_left_alone(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert runtime.init_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # set nowhere


def test_compile_cache_default_is_one_path_inside_the_checkout(tmp_path):
    code = ("from distributedtensorflow_tpu import runtime; import jax; "
            "d = runtime.init_compile_cache(); "
            "assert jax.config.jax_compilation_cache_dir == d; print(d)")
    env = dict(JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR="")
    seen = {
        _run(["-c", code], cwd=cwd, **env).stdout.strip()
        for cwd in (REPO, str(tmp_path))
    }
    assert seen == {os.path.join(REPO, ".jax_cache")}, seen


def test_train_device_tpu_fails_on_cpu(tmp_path):
    res = _run(["train.py", "--workload", "mnist_lenet", "--device", "tpu",
                "--steps", "1", "--logdir", str(tmp_path)],
               JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert "requires a tpu device" in res.stderr, res.stderr[-1500:]
    assert not os.path.exists(tmp_path / "metrics.jsonl")


def test_chip_smoke_parent_imports_no_jax():
    res = _run(["-c", "import sys, chip_smoke; "
                      "assert 'jax' not in sys.modules, 'jax imported'; "
                      "assert 'numpy' not in sys.modules"])
    assert res.returncode == 0, res.stderr[-1500:]


def test_chip_smoke_fails_fast_without_a_chip():
    t0 = time.monotonic()
    res = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu", timeout=30)
    assert time.monotonic() - t0 < 10
    assert res.returncode != 0
    assert "no chip" in res.stderr and "JAX_PLATFORMS='cpu'" in res.stderr
    assert not res.stdout.strip()  # no result line of any kind


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    res = _run(["chip_smoke.py"], cwd=str(tmp_path), JAX_PLATFORMS="",
               timeout=30)
    assert res.returncode != 0
    assert "nothing to run" in res.stderr, res.stderr[-500:]
    assert not res.stdout.strip()
    assert os.listdir(tmp_path) == ["chip_smoke.py"]  # and left no litter

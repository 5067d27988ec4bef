"""CPU checks of the chip smoke and the compile-cache helper.

``chip_smoke.run`` is driven at a tiny size on the interpret tier (steered
here through ``REPRO_KERNEL_BACKEND``), so the script cannot rot between
chip runs; its ``main`` must refuse a CPU.  The compile-cache helper is
exercised in child processes so this process's JAX config stays as is.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_run_serves_every_turn_on_the_interpret_tier(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    rep = chip_smoke.run(n_docs=600, n_sessions=4, turns=2, k=5, k_c=16,
                         dim=32, docs_per_topic=100, seed=3)
    assert rep["tiers"] == {"engine": "interpret", "shared": "interpret",
                            "shards": ["interpret"]}
    assert rep["degraded"] == 0 and rep["quarantined"] == 0
    assert rep["router"]["failures"] == rep["router"]["timeouts"] == 0
    assert rep["cold_answers"] == 4 and rep["max_score_gap"] <= 1e-6
    # the tiered full-miss wave contract: L1 probe, L2 probe, one scan per
    # shard, one fused insert+query
    assert rep["launches"]["wave"] == 4
    assert rep["resident_bytes"]["corpus"] > 0


def test_compare_allows_only_tie_swaps():
    import numpy as np

    from repro.dist.retrieval import ShardTopK
    ref = ShardTopK(np.array([[0.9, 0.5, 0.5]], np.float32),
                    np.array([[1, 2, 3]]))
    swaps, gap = chip_smoke.compare(np.array([[1, 3, 2]]),
                                    np.array([[0.9, 0.5, 0.5]], np.float32),
                                    ref)
    assert swaps == 2 and gap == 0.0
    with pytest.raises(AssertionError, match="differs from the reference"):
        chip_smoke.compare(np.array([[2, 1, 3]]),
                           np.array([[0.5, 0.9, 0.5]], np.float32), ref)


def _child(code, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.path.join(ROOT, "src"))
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    return subprocess.run([sys.executable, *code], capture_output=True,
                          text=True, env=full, cwd=ROOT, timeout=300)


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_directory(tmp_path, env_dir):
    env = {} if env_dir is None else {
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / env_dir)}
    out = _child(["-c", "import jax; from repro import compile_cache; "
                  "print(compile_cache.enable()); "
                  "print(jax.config.jax_compilation_cache_dir)"], **env)
    assert out.returncode == 0, out.stderr
    want = env.get("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    assert out.stdout.split() == [want, want]
    if env_dir is not None:
        # a compile lands in the operator's directory
        out = _child(["-c", "import jax; from repro import compile_cache; "
                      "compile_cache.enable(); "
                      "jax.jit(lambda x: x * 3 + 1)(1.0).block_until_ready()"],
                     **env)
        assert out.returncode == 0, out.stderr
        assert os.listdir(want)


def test_main_refuses_a_cpu():
    out = _child(["chip_smoke.py"])
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "not a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())

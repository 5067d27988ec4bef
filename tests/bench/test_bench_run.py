"""The harness end to end on the CPU, at a tiny size.

``run_cell`` is driven past the harness's look for a chip: the tiny runs
must come out correct, the control must fail the configuration's limits,
and a run whose timed path is broken underneath must come out not
correct.  ``run.py`` itself must refuse to run without a TPU, and in a
directory that holds only the benchmark.

The program resets a session's L1 slot on the caller's thread while the
scheduler's worker writes waves back, each a read-modify-write of the
whole stacked state, with no lock between them.  At this size a wave
takes milliseconds and the two collide on several seeds in ten: a lost
reset leaves a fresh session its predecessor's rows, and ``foreign_ids``
reads the stale answer.  The tiny runs here hold the two under one lock,
so that they judge the harness and not the race (``PERF.md``, Open
questions).
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from bench import check, run
from repro.core.cache import BatchedMetricCache

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(autouse=True)
def _serialized_state_writes(monkeypatch):
    lock = threading.RLock()
    for name in ("reset", "scatter", "gather"):
        method = getattr(BatchedMetricCache, name)

        def held(self, *a, _method=method, **kw):
            with lock:
                return _method(self, *a, **kw)
        monkeypatch.setattr(BatchedMetricCache, name, held)


def _tiny(workload, raw_dim=64):
    bench, cell, cfg, mix = run.load_cell(workload)
    cfg = dict(cfg, n_docs=20000, raw_dim=raw_dim, k_c=50, l1_slots=8,
               l1_capacity=800, check_slots=4,
               world=dict(cfg["world"], docs_per_topic=2500))
    if mix["loop"] == "open":
        mix = dict(mix, sessions=8, rate_per_s=20.0)
    else:
        mix = dict(mix, clients=8, pool=2048)
    return bench, cell, cfg, mix


def _run(workload, seconds=2.0, **kw):
    bench, cell, cfg, mix = _tiny(workload, kw.pop("raw_dim", 64))
    return run.run_cell(cell, cfg, mix, seed=kw.pop("seed", 2 ** 31 + 9),
                        seconds=seconds, trace=False, bench=bench,
                        devices=jax.devices()[:1],
                        t_start=time.perf_counter(), say=lambda m: None,
                        **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_cell_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 8 and res["failed"] == 0
    names = {m["name"] for m in run.metrics_of(
        json.loads((ROOT / "BENCHMARK.json").read_text()), workload, False)}
    assert set(res["metrics"]) == names
    assert list(res)[-1] == "checks"
    assert list(res["checks"]) == list(check.NAMES)


def _alter_backend(engine, router):
    search = router.search

    def altered(q, k):
        ans, degraded = search(q, k)
        ans.ids[:, 0] = (ans.ids[:, 0] + 1) % router.n_docs
        return ans, degraded
    router.search = altered


def _alter_served(engine, router):
    fill = engine.fill_wave

    def altered(ws):
        out = fill(ws)
        for t in out:
            if not isinstance(t, BaseException) and len(t.ids):
                t.ids[0] = (t.ids[0] + 7) % router.n_docs
        return out
    engine.fill_wave = altered


def _corrupt_fill(engine, router):
    fill = engine.fill_wave

    def corrupted(ws):
        ws.new_emb[:, :, 0] += 0.5
        return fill(ws)
    engine.fill_wave = corrupted


def _skip_fill(engine, router):
    """The fill returns the cache unchanged: nothing is inserted and each
    turn is answered from what its slot held before."""
    fill = engine.fill_wave

    def unchanged(ws):
        ws.backend_ok[:] = False
        ws.reuse[:] = False
        ws.l2hit[:] = False
        return fill(ws)
    engine.fill_wave = unchanged


def _half_batch(engine, router):
    """The back end searches half of each wave's rows and hands the
    first half's answers to the rest."""
    search = router.search

    def halved(q, k):
        half = -(-len(q) // 2)
        ans, degraded = search(q[:half], k)
        pick = np.arange(len(q)) % half
        return type(ans)(ids=ans.ids[pick], scores=ans.scores[pick]), \
            degraded
    router.search = halved


def _skip_reset(engine, router):
    """Opening a session leaves its slot as the last session left it."""
    engine.cache.reset = lambda sessions=None: None


@pytest.mark.parametrize("fault", [_alter_backend, _alter_served,
                                   _corrupt_fill, _skip_fill, _half_batch,
                                   _skip_reset])
def test_a_broken_timed_path_is_not_correct(fault):
    res = _run("k2000-adhoc", fault=fault)
    assert not res["correct"]


def test_foreign_ids_counts_what_the_session_did_not_fetch():
    q = np.eye(3, dtype=np.float32)
    calls = [(q[:2], np.array([[1, 2, 3], [4, 5, -1]]), None)]

    def turn(key, i, tier, ids):
        return (key, 0, q[i], tier, np.array(ids), np.zeros(len(ids)))
    ok = check.Served(calls, [turn("a", 0, "backend", [1, 2]),
                              turn("b", 1, "backend", [4, 5])])
    assert check.foreign_ids(ok, [("a", [1, 2, 3, -1]), (None, [-1])]) == 0
    # a first turn answered from L1: none of its ids was fetched
    stale = check.Served(calls, ok.turns + [turn("c", 2, "l1", [1, 4])])
    assert check.foreign_ids(stale, []) == 2
    # a slot that kept its last session's rows, or holds rows unowned
    assert check.foreign_ids(ok, [("b", [4, 5, 1, 2])]) == 2
    assert check.foreign_ids(ok, [(None, [7])]) == 1
    # after an L2 answer the session's rows may come from the L2 tier
    l2 = check.Served(calls, [turn("d", 2, "l2", [8]),
                              turn("d", 2, "l1", [8, 9])])
    assert check.foreign_ids(l2, [("d", [8, 9])]) == 0


def test_the_control_fails_the_limits():
    bench, cell, cfg, mix = _tiny("k2000-adhoc", raw_dim=768)
    k, k_c = int(cfg["k"]), int(cfg["k_c"])
    res = run.run_cell(
        cell, cfg, mix, seed=12345, seconds=2.0, trace=False, bench=bench,
        devices=jax.devices()[:1], t_start=time.perf_counter(),
        say=lambda m: None,
        extra=lambda ref, served, exact: check.numbers(
            ref, check.control(ref, served, k, k_c), exact, k, k_c))
    assert res["correct"], res["checks"]
    control = res["extra"]
    assert any(control[n] > cfg["limits"][n]
               for n in check.NAMES if n in control), control


def _cli(args, cwd, env):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_refuses_without_a_tpu():
    p = _cli(["--workload", "k2000-adhoc", "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT, _env())
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_run_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(["--workload", "k2000-adhoc", "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path, _env())
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert np.isfinite(p.returncode)

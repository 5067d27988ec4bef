"""The conversational cell ``k1000-conv`` on the CPU, at a tiny size.

A tiny run answers turns from every tier (the session's L1, the shared
L2's shards or memo, and the back end) and comes out correct.  Two
planted faults come out not correct: L2 answers that warm L1 with the
rows of other ids, and the L1 query computed at bf16.  The control (the
reference at bf16 in the program's place) fails the served limits.  Each
of the cell's per-layer readers returns a number on a tiny traced run
(the roofline share needs a chip's peaks) and None where its input is
missing."""

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, run
from repro.serve import session

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "k1000-conv"
READERS = [m["name"] for m in run.metrics_of(BENCH, CELL, True)]
NEW = ("backend_turn_share.conv", "l2_turn_share.conv", "l2_ms_p50.conv")


def _run(seconds=6.0, trace=False, fault=None, seed=2 ** 31 + 21,
         extra=None):
    bench, cell, cfg, mix = run.load_cell(CELL)
    cfg = dict(cfg, n_docs=20000, raw_dim=64, k_c=50, l1_slots=8,
               l1_capacity=800, check_slots=4,
               world=dict(cfg["world"], docs_per_topic=2500))
    mix = dict(mix, sessions=8, rate_per_s=20.0)
    keep = {}

    def kept(engine, router):
        keep["engine"] = engine
        if fault is not None:
            fault(engine, router)
    res = run.run_cell(cell, cfg, mix, seed=seed, seconds=seconds,
                       trace=trace, bench=bench, devices=jax.devices()[:1],
                       t_start=time.perf_counter(), say=lambda m: None,
                       fault=kept, extra=extra)
    return res, keep["engine"]


@pytest.fixture(scope="module")
def traced():
    """A tiny traced run, and its engine."""
    return _run(trace=True)


def _data(turns=(), **kw) -> run.RunData:
    return run.RunData(cfg={}, mix={}, seconds=1.0, setup_s=0.0,
                       turns=list(turns), waves=[], calls=[], **kw)


def test_a_tiny_run_answers_from_every_tier(traced):
    res, engine = traced
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    got = {k: m["value"] for k, m in res["metrics"].items()}
    backend, l2 = got["backend_turn_share.conv"], got["l2_turn_share.conv"]
    assert backend > 0 and l2 > 0 and backend + l2 < 100, got
    tel = engine.telemetry
    assert min(tel.l1_hit_rows, tel.backend_rows,
               tel.l2_hit_rows + tel.l2_reuse_rows) > 0
    assert tel.l1_hit_rows + tel.l2_hit_rows + tel.l2_reuse_rows \
        + tel.backend_rows == tel.wave_rows


def test_every_reader_reads_the_tiny_run(traced):
    res, _engine = traced
    assert set(READERS) == set(NEW) | {
        "fill_ms_p50.adhoc", "backend_wave_ms.adhoc",
        "knn_fused_topk_roofline.adhoc", "device_idle_frac.adhoc"}
    # no peaks on the CPU, so no roofline share; the rest read a number
    got = {k: m["value"] for k, m in res["metrics"].items()}
    assert set(got) == set(READERS) - {"knn_fused_topk_roofline.adhoc"}
    assert all(np.isfinite(v) for v in got.values()), got
    assert min(got["l2_ms_p50.conv"], got["fill_ms_p50.adhoc"],
               got["backend_wave_ms.adhoc"]) > 0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_without_its_input_reads_none(name):
    assert run.reader(name)(_data()) is None


def _l2_rows_of_other_ids(engine, router):
    """Every L2 answer (shard hit or memo reuse) warms L1 with the corpus
    rows of the next ids in place of its own."""
    fill = engine.fill_wave
    n = len(engine.doc_embeddings)

    def altered(ws):
        for i in np.nonzero(np.logical_or(ws.l2hit, ws.reuse))[0]:
            ids = ws.new_ids[i, :engine.k_c]
            other = (np.maximum(ids, 0) + 1) % n
            ws.new_emb[i, :engine.k_c] = engine.doc_embeddings[other]
            engine.altered = getattr(engine, "altered", 0) + 1
        return fill(ws)
    engine.fill_wave = altered


def test_l2_answers_with_other_rows_are_not_correct():
    res, engine = _run(fault=_l2_rows_of_other_ids)
    assert getattr(engine, "altered", 0) > 0
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def _bf16_scores(out, sub, psi):
    """The query's scores recomputed with both operands rounded to bf16
    (f32 accumulation), for the slots the query picked."""
    scores, dists, ids, slots = out
    rows = jnp.take_along_axis(
        sub.doc_emb.astype(jnp.float32),
        jnp.maximum(slots, 0)[:, :, None], axis=1)
    q = jnp.zeros((psi.shape[0], rows.shape[-1]), jnp.float32)
    q = q.at[:, :psi.shape[1]].set(psi)
    low = jnp.einsum("bd,bkd->bk", q.astype(jnp.bfloat16),
                     rows.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return (jnp.where(ids >= 0, low, scores), dists, ids, slots)


def test_the_l1_query_at_bf16_is_not_correct(monkeypatch):
    """The fused insert+query and the plain query (every turn's answer
    over its session's L1 rows) score at bf16."""
    iq, q = session.insert_query_batched, session.query_batched
    calls = []

    def insert_query(sub, cfg, psi, *a, **kw):
        out, new, dropped = iq(sub, cfg, psi, *a, **kw)
        calls.append(1)
        return _bf16_scores(out, new, psi), new, dropped

    def query(sub, psi, k, **kw):
        out, new = q(sub, psi, k, **kw)
        calls.append(1)
        return _bf16_scores(out, new, psi), new
    monkeypatch.setattr(session, "insert_query_batched", insert_query)
    monkeypatch.setattr(session, "query_batched", query)
    res, _engine = _run()
    assert calls
    assert not res["correct"]
    checks = res["checks"]
    assert checks["served_self_gap"]["value"] > \
        checks["served_self_gap"]["limit"], checks


def test_the_control_fails_the_served_limits():
    _bench, _cell, cfg, _mix = run.load_cell(CELL)
    k, k_c = int(cfg["k"]), 50

    def control(ref, served, exact):
        return check.numbers(ref, check.control(ref, served, k, k_c), exact,
                             k, k_c)
    res, _engine = _run(extra=control)
    assert res["correct"], res["checks"]
    got = res["extra"]
    assert got["served_self_gap"] > cfg["limits"]["served_self_gap"], got
    assert got["backend_self_gap"] > cfg["limits"]["backend_self_gap"], got

"""The serving path's spans on the profiler's trace, and its counters.

A tiny ``SessionManager`` run under ``jax.profiler``, read back from the
``.xplane.pb``: each wave's three phases under one id, children inside
their parent on one thread, the scheduler worker always in one of its six
states.  The counters of ``ServeTelemetry`` equal the bytes of the arrays
a wave stages on and reads back from the device, worked out from their
shapes, and count with no profiler active, when the spans record
nothing."""

import dataclasses
import gc
import glob
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import layout
from repro.serve.router import ShardedRouter
from repro.serve.session import BatchedEngine, SessionManager
from repro.serve.telemetry import ServeTelemetry, span

DIM, N_DOCS, K, K_C, SLOTS = 65, 3000, 5, 16, 8
PHASES = ("engine.probe", "engine.backend", "engine.fill")
WORKER = ("sched.wait", "sched.select", "sched.join", "sched.resolve",
          "engine.probe", "engine.fill")


@pytest.fixture
def engine():
    rng = np.random.default_rng(7)
    docs = rng.standard_normal((N_DOCS, DIM)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    router = ShardedRouter.over_devices(docs, devices=jax.devices()[:1],
                                        dtype="fp32", deadline_s=30.0)
    eng = BatchedEngine(router, docs, dim=DIM, n_sessions=SLOTS, k=K,
                        k_c=K_C, capacity=8 * K_C)
    yield eng
    router.close()


def _queries(n, seed=0):
    q = np.random.default_rng(seed).standard_normal((n, DIM))
    return list((q / np.linalg.norm(q, axis=1, keepdims=True))
                .astype(np.float32))


def _miss_wave_bytes(wave: int) -> tuple:
    """(h2d, d2h) of one all-miss wave of ``wave`` fresh sessions."""
    bucket = 1 << (wave - 1).bit_length()
    f32 = i32 = 4
    h2d = (bucket * DIM * f32 + bucket * i32          # queries, slot row
           + bucket * f32                              # radii put
           + bucket * layout.phys_dim(DIM) * f32       # shard query put
           + bucket * f32                              # rad
           + bucket * K_C * DIM * f32                  # new_emb
           + bucket * K_C * i32                        # new_ids
           + 2 * bucket                                # do, record (bool)
           + bucket * i32)                             # write-back rows
    d2h = (bucket * i32 + bucket + bucket * DIM * f32  # n_queries, hit, psi
           + bucket * f32                              # radii read
           + 2 * bucket * K_C * 4                      # shard scores, ids
           + bucket * i32                              # dropped counts
           + wave * K * (i32 + f32))                   # answered rows
    return h2d, d2h


@pytest.mark.parametrize("wave", [1, 3, 8])
def test_counters_equal_the_staged_nbytes(engine, wave):
    tel = engine.telemetry
    assert engine.router.telemetry is tel
    for s in range(wave):
        engine.start_session(s)
    before = (tel.h2d_bytes, tel.d2h_bytes, tel.wave_rows, tel.bucket_rows)
    out = engine.answer_batch(list(range(wave)), _queries(wave))
    assert all(t.tier == "backend" for t in out)
    bucket = 1 << (wave - 1).bit_length()
    got = (tel.h2d_bytes - before[0], tel.d2h_bytes - before[1],
           tel.wave_rows - before[2], tel.bucket_rows - before[3])
    assert got == (*_miss_wave_bytes(wave), wave, bucket)
    assert {t.spans.wave for t in out} == {engine.wave_id}


def test_counters_count_and_nothing_records_without_a_profiler(
        engine, tmp_path):
    assert not TraceAnnotation.is_enabled()
    tel = engine.telemetry
    pauses = tel.gc_pauses
    for s in range(3):
        engine.start_session(s)
    engine.answer_batch([0, 1, 2], _queries(3, seed=1))
    gc.collect()
    assert tel.h2d_bytes > 0 and tel.d2h_bytes > 0
    assert tel.gc_pauses > pauses
    assert tel.summary()["counters"]["h2d_bytes"] == tel.h2d_bytes
    # spans opened while no profile was taken are not held for the next
    jax.profiler.start_trace(str(tmp_path))
    with span("test.marker"):
        pass
    jax.profiler.stop_trace()
    assert [sp.name for sp in _load(tmp_path)] == ["test.marker"]


def test_breaker_transitions_are_counted():
    tel = ServeTelemetry()
    tel.record_breaker("open")
    tel.record_breaker("half_open")
    tel.record_breaker("open")
    assert tel.faults == {"breaker_open": 2, "breaker_half_open": 1}
    assert tel.summary()["breaker_transitions"] == 3
    assert not hasattr(tel, "breaker_log")


# ------------------------------------------------------ the traced run

@dataclasses.dataclass
class Span:
    name: str              # without ``repro.``
    start: float           # seconds on the trace's clock
    end: float
    line: int              # host line: one per thread
    args: dict


def _load(path) -> list:
    """The ``repro.*`` spans of the one trace under ``path``, by start
    (parents before their children)."""
    pd = ProfileData.from_file(glob.glob(
        str(path / "**" / "*.xplane.pb"), recursive=True)[0])
    out, n = [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n += 1
            out += [Span(ev.name[len("repro."):], ev.start_ns * 1e-9,
                         ev.end_ns * 1e-9, n, dict(ev.stats))
                    for ev in line.events if ev.name.startswith("repro.")]
    return sorted(out, key=lambda sp: (sp.start, -sp.end))


def _named(spans, name) -> list:
    return [sp for sp in spans if sp.name == name]


def _moved(sp) -> int:
    return sp.args.get("h2d_bytes", 0) + sp.args.get("d2h_bytes", 0)


def _wave_bytes(spans) -> dict:
    """{wave id: bytes}: the byte args of the wave's phase spans and of
    the shard spans inside its back-end phase (one runs at a time)."""
    total = {}
    for sp in spans:
        if sp.name in PHASES:
            w = sp.args["wave"]
            total[w] = total.get(w, 0) + _moved(sp)
    backend = _named(spans, "engine.backend")
    for sp in spans:
        if sp.name.startswith("shard.") and _moved(sp):
            (owner,) = [b for b in backend if b.start <= sp.start <= b.end]
            total[owner.args["wave"]] += _moved(sp)
    return total


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(spans, answered turns, bytes the counters added) of 6 clients in
    a closed loop for 2 s, each turn on a fresh session."""
    rng = np.random.default_rng(3)
    docs = rng.standard_normal((4000, DIM)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    router = ShardedRouter.over_devices(docs, devices=jax.devices()[:1],
                                        dtype="fp32", deadline_s=30.0)
    engine = BatchedEngine(router, docs, dim=DIM, n_sessions=SLOTS, k=5,
                           k_c=K_C, capacity=8 * K_C)
    mgr = SessionManager(engine)
    tel = engine.telemetry
    turns, lock = [], threading.Lock()

    def client(c, stop):
        i = 0
        while time.perf_counter() < stop:
            key = (c, i)
            mgr.open(key)
            q = docs[(97 * c + 13 * i) % len(docs)]
            turn = mgr.submit(key, q).result(timeout=60)
            mgr.close(key)
            with lock:
                turns.append(turn)
            i += 1

    def drive(seconds):
        stop = time.perf_counter() + seconds
        threads = [threading.Thread(target=client, args=(c, stop))
                   for c in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mgr.flush()

    for b in (1, 2, 4, 8):     # compile each bucket's miss and hit waves
        engine.answer_batch(list(range(b)), list(docs[:b]))
        engine.answer_batch(list(range(b)), list(docs[:b]))
    drive(0.5)                       # ... and the opens and closes
    turns.clear()
    c0 = tel.h2d_bytes + tel.d2h_bytes
    path = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(path))
    drive(2.0)
    jax.profiler.stop_trace()
    moved = tel.h2d_bytes + tel.d2h_bytes - c0
    mgr.shutdown()
    router.close()
    return _load(path), turns, moved


def test_every_wave_has_its_three_phases_under_one_id(traced):
    tr, turns, _ = traced
    waves: dict = {}
    for sp in tr:
        if sp.name in PHASES:
            waves.setdefault(sp.args["wave"], []).append(sp)
    assert len(waves) >= 5
    for w, spans in waves.items():
        assert sorted(sp.name for sp in spans) == sorted(PHASES), w
        assert len({(sp.args["rows"], sp.args["bucket"])
                    for sp in spans}) == 1
    # each answered turn names its wave; a wave answered its rows
    got = {}
    for t in turns:
        got[t.spans.wave] = got.get(t.spans.wave, 0) + 1
    assert set(got) == set(waves)
    assert all(waves[w][0].args["rows"] == n for w, n in got.items())


def test_children_nest_inside_their_parent_on_one_line(traced):
    tr, _, _ = traced
    parents = {sp.name: [] for sp in tr}
    for sp in tr:
        parents[sp.name].append(sp)
    checked = 0
    for sp in tr:
        head = sp.name.rpartition(".")[0]
        if head not in PHASES:
            continue
        assert any(p.line == sp.line and p.start <= sp.start
                   and sp.end <= p.end for p in parents[head]), sp
        checked += 1
    assert checked >= 5 * 8
    for sp in _named(tr, "router.merge"):
        assert any(p.line == sp.line and p.start <= sp.start <= sp.end
                   <= p.end for p in parents["router.search"])


def test_worker_states_tile_the_worker_thread(traced):
    tr, _, _ = traced
    lines = {sp.line for sp in tr if sp.name == "sched.select"}
    assert len(lines) == 1
    line = lines.pop()
    states = [sp for sp in tr
              if sp.line == line and sp.name in WORKER]
    assert {sp.name for sp in states} >= set(WORKER) - {"sched.wait"}
    for a, b in zip(states, states[1:]):
        assert a.end <= b.start, (a, b)
    held = sum(sp.end - sp.start for sp in states)
    life = states[-1].end - states[0].start
    assert held >= 0.97 * life


def test_select_args_describe_the_wave(traced):
    tr, _, _ = traced
    took = [sp for sp in _named(tr, "sched.select")
            if sp.args.get("rows")]
    assert took
    for sp in took:
        a = sp.args
        assert 1 <= a["rows"] <= min(a["eligible"], a["limit"])
        assert a["eligible"] <= a["queue"] and a["max_wave"] == SLOTS
    probe = {sp.args["wave"]: sp.args["rows"]
             for sp in _named(tr, "engine.probe")}
    assert all(probe[sp.args["wave"]] == sp.args["rows"] for sp in took)


def test_wave_bytes_add_up_to_the_counters(traced):
    tr, _, moved = traced
    per = _wave_bytes(tr)
    assert per and min(per.values()) > K_C * DIM * 4
    assert sum(per.values()) == moved

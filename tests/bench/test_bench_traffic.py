"""The traffic generator: deterministic in its seed, true to its mix."""

import concurrent.futures as cf
import json
import pathlib
import threading
import time

import numpy as np
import pytest

from bench import traffic

MIXES = pathlib.Path(__file__).resolve().parents[2] / "bench" / "mixes"
# an open-loop mix of CAsT-shaped conversations (no cell runs one yet)
CONV = {"loop": "open", "sessions": 64, "rate_per_s": 16.0,
        "turns": [6, 12], "subtopic_share": 0.25}


def _mix(name):
    if name == "conv":
        return dict(CONV)
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["conv", "adhoc"])
def test_plan_is_deterministic_in_its_seed(name):
    a = traffic.plan(_mix(name), 2 ** 31 + 5, 30.0, 200, 16)
    b = traffic.plan(_mix(name), 2 ** 31 + 5, 30.0, 200, 16)
    c = traffic.plan(_mix(name), 2 ** 31 + 6, 30.0, 200, 16)
    for f in ("topic", "length", "draws", "jump", "due", "session", "conv",
              "turn"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.draws, c.draws)
    # every seed gets the same multiset of sizes and gaps
    if name == "adhoc":
        np.testing.assert_array_equal(np.sort(a.length), np.sort(c.length))
    else:
        np.testing.assert_array_equal(np.bincount(a.session),
                                      np.bincount(c.session))
        np.testing.assert_allclose(np.sort(np.diff(a.due, prepend=0)),
                                   np.sort(np.diff(c.due, prepend=0)),
                                   rtol=1e-9, atol=1e-9)


def test_conversation_plan_is_true_to_its_mix():
    mix = _mix("conv")
    p = traffic.plan(mix, 7, 40.0, 200, 16)
    lo, hi = mix["turns"]
    assert p.length.min() >= lo and p.length.max() <= hi
    assert abs(p.length.mean() - (lo + hi) / 2) < 0.5
    n = len(p.due)
    assert abs(n - mix["rate_per_s"] * 40.0) <= 2
    assert np.all(np.diff(p.due) > 0) and p.due[-1] < 40.0
    counts = np.bincount(p.session, minlength=mix["sessions"])
    assert counts.max() - counts.min() <= 1          # equal shares
    # each session walks its conversations turn by turn, one at a time
    for s in range(mix["sessions"]):
        seq = list(zip(p.conv[p.session == s], p.turn[p.session == s]))
        for (c0, t0), (c1, t1) in zip(seq, seq[1:]):
            assert (c1, t1) == (c0, t0 + 1) or \
                (t0 == p.length[c0] - 1 and t1 == 0 and c1 != c0)
        if seq:
            assert seq[0] == (p.first_conv[s], 0)
    later = int(sum(p.length - 1))
    assert p.jump[:, 0].sum() == 0
    assert p.jump.sum() == round(mix["subtopic_share"] * later)


def test_adhoc_plan_is_one_turn_conversations():
    p = traffic.plan(_mix("adhoc"), 3, 40.0, 200, 16)
    assert p.loop == "closed" and p.clients == 32
    assert np.all(p.length == 1) and not p.jump.any()
    assert len(p.length) == _mix("adhoc")["pool"]


class FakeManager:
    """A front door that answers each turn after ``delay_s``, in waves."""

    def __init__(self, delay_s=0.005):
        self.delay_s = delay_s
        self.open_keys, self.closed, self.submitted = set(), [], []
        self.lock = threading.Lock()
        self.inflight = 0
        self.max_inflight = 0
        self.pool = cf.ThreadPoolExecutor(4)

    def open(self, key):
        with self.lock:
            assert key not in self.open_keys and key not in self.closed
            self.open_keys.add(key)
            return len(self.closed) + len(self.open_keys)

    def close(self, key):
        with self.lock:
            self.open_keys.remove(key)
            self.closed.append(key)

    def submit(self, key, query):
        with self.lock:
            assert key in self.open_keys
            self.submitted.append(key)
            self.inflight += 1
            self.max_inflight = max(self.max_inflight, self.inflight)

        def answer():
            time.sleep(self.delay_s)
            with self.lock:
                self.inflight -= 1
            return key
        return self.pool.submit(answer)


def test_closed_loop_opens_a_fresh_session_for_every_turn():
    p = traffic.plan(_mix("adhoc"), 11, 0.3, 200, 16)
    mgr = FakeManager()
    loop = traffic.ClosedLoop(mgr, p, np.zeros((len(p.length), 1, 4)))
    loop.open_all()
    turns = loop.run(0.3)
    mgr.pool.shutdown()
    assert mgr.max_inflight == 32                    # a closed loop of 32
    assert len(turns) > 64
    assert len(set(mgr.submitted)) == len(mgr.submitted)   # one turn each
    assert all(t.result == t.key for t in turns)
    assert len({t.conv for t in turns}) == len(turns)      # fresh each time


def test_open_loop_times_turns_from_when_they_were_due():
    mix = dict(_mix("conv"), sessions=4, rate_per_s=200.0, turns=[2, 3])
    p = traffic.plan(mix, 5, 0.5, 10, 16)
    mgr = FakeManager(delay_s=0.002)
    loop = traffic.OpenLoop(mgr, p, np.zeros((len(p.length), 3, 4)))
    loop.open_all()
    turns = loop.run(0.5)
    deadline = time.time() + 5
    while any(t.result is None for t in turns) and time.time() < deadline:
        time.sleep(0.01)
    mgr.pool.shutdown()
    assert len(turns) == len(p.due)
    assert all(t.latency_s >= 0 for t in turns)
    # every conversation ran on its own session key, turns in order
    for t in turns:
        assert t.key == (int(p.session[turns.index(t)]), t.conv)
    assert len(mgr.closed) == len({t.conv for t in turns}) - \
        len({t.key[0] for t in turns})
    assert set(loop.live.values()) >= mgr.open_keys


def test_zipf_topics_keep_their_counts_across_seeds():
    mix = dict(_mix("adhoc"), topic_zipf=1.1, pool=4000)
    a = traffic.plan(mix, 2 ** 31 + 1, 30.0, 200, 16)
    b = traffic.plan(mix, 2 ** 31 + 2, 30.0, 200, 16)
    ca = np.sort(np.bincount(a.topic, minlength=200))[::-1]
    cb = np.sort(np.bincount(b.topic, minlength=200))[::-1]
    np.testing.assert_array_equal(ca, cb)
    assert ca.sum() == 4000
    w = 1.0 / np.arange(1, 201) ** 1.1
    np.testing.assert_allclose(ca, 4000 * w / w.sum(), atol=1.0)
    assert not np.array_equal(a.topic, b.topic)
    # uniform where the mix names no law: the plan of an existing mix
    # does not change
    u = traffic.plan(_mix("adhoc"), 2 ** 31 + 1, 30.0, 200, 16)
    rng = np.random.default_rng(2 ** 31 + 1)
    rng.shuffle(np.resize(np.arange(1, 2), _mix("adhoc")["pool"]))
    np.testing.assert_array_equal(
        u.topic, rng.integers(0, 200, _mix("adhoc")["pool"]))


def test_bursts_raise_the_rate_inside_them():
    mix = dict(_mix("conv"), rate_per_s=50.0,
               bursts={"factor": 4, "every_s": 10, "for_s": 2})
    p = traffic.plan(mix, 9, 30.0, 200, 16)
    inside = (p.due % 10) < 2
    # 3 bursts of 2 s at 200/s and 24 s at 50/s: 2,400 arrivals, with
    # the spread of a Poisson count between the two
    assert abs(len(p.due) - 2400) <= 2
    assert abs(inside.sum() - 3 * 2 * 200) <= 4 * np.sqrt(1200)
    assert abs(inside.sum() / 6 / ((~inside).sum() / 24) - 4) < 0.6
    assert np.all(np.diff(p.due) > 0) and p.due[-1] < 30.0
    q = traffic.plan(mix, 10, 30.0, 200, 16)
    assert len(q.due) == len(p.due)


def test_completion_rate_counts_whole_bursts():
    bursts = [1.0, 2.0, 3.0, 4.0]
    done = np.concatenate([b + np.arange(32) * 1e-5 for b in bursts])
    n, secs = traffic.completion_rate(done, end=3.5, gap_s=0.05)
    assert n == 64 and abs(secs - 2.0) < 1e-3
    assert traffic.completion_rate(done[:32], end=9, gap_s=0.05) is None

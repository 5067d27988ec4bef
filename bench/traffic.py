"""The one traffic generator: a mix file's parameters -> a plan -> turns.

A mix (``bench/mixes/<name>.json``) is data only.  ``plan`` turns its
parameters and the seed into a fixed amount of work: the conversations
(topic, length, the draws of every turn, which turns jump sub-topic) and,
for an open loop, the arrival schedule.  Every seed gets the same
multiset of sizes and gaps, in another order: inter-arrival gaps are the
stratified quantiles of the exponential distribution, sessions take
equal shares of the arrivals, conversation lengths cycle over their
range, and an exact share of later turns jump sub-topic.

Parameters (all loops): ``loop`` (``open`` or ``closed``), ``turns``
([lo, hi] per conversation), ``subtopic_share`` (share of later turns
that jump sub-topic), ``topic_zipf`` (optional exponent of a Zipf law
over topic popularity; absent, topics are drawn uniformly).  Open loop:
``sessions``, ``rate_per_s``, and optional ``bursts`` (``{"factor",
"every_s", "for_s"}``: the rate times ``factor`` for ``for_s`` seconds
at the start of every ``every_s``).  Closed loop: ``clients``, ``pool``
(conversations drawn for the run).

Two loops (``LOOPS``, chosen by ``loop``) drive a ``SessionManager``-like
front door (``open`` returning the session's slot, ``close``, ``submit``):

* ``open``: ``sessions`` sessions are held open; turns arrive on the
  schedule, each to its session, and are timed from when they were due.
  A session whose conversation ends is closed and reopened on the next
  one by a side thread, so the schedule never waits on a drain.
* ``closed``: ``clients`` clients, no think time; every turn is the first
  utterance of a fresh conversation on a freshly opened session.

Each loop keeps ``live``: the session key that holds each slot.
"""

from __future__ import annotations

import concurrent.futures as cf
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np


@dataclass
class Plan:
    loop: str
    topic: np.ndarray            # (C,) topic per conversation
    length: np.ndarray           # (C,) turns per conversation
    draws: np.ndarray            # (C, L, 3, s) standard-normal draws
    jump: np.ndarray             # (C, L) sub-topic jumps (turn 0 never)
    clients: int                 # sessions (open) or clients (closed)
    # open loop only: arrival i is due at ``due[i]`` s after the window
    # opens, for turn ``turn[i]`` of conversation ``conv[i]`` of ``session[i]``
    due: np.ndarray = field(default_factory=lambda: np.zeros(0))
    session: np.ndarray = field(default_factory=lambda: np.zeros(0, int))
    conv: np.ndarray = field(default_factory=lambda: np.zeros(0, int))
    turn: np.ndarray = field(default_factory=lambda: np.zeros(0, int))
    first_conv: np.ndarray = field(default_factory=lambda: np.zeros(0, int))


def _burst_clock(bursts: dict, seconds: float) -> tuple:
    """(grid, base): ``base[i]`` is the base-rate time that has passed by
    ``grid[i]`` seconds when the rate runs ``factor`` times higher for
    ``for_s`` seconds at the start of every ``every_s``."""
    grid = np.linspace(0.0, seconds, int(seconds * 1000) + 1)
    inside = (grid % float(bursts["every_s"])) < float(bursts["for_s"])
    speed = np.where(inside, float(bursts["factor"]), 1.0)
    base = np.concatenate([[0.0], np.cumsum(speed[:-1] * np.diff(grid))])
    return grid, base


def _topics(mix: dict, rng, n_conv: int, n_topics: int) -> np.ndarray:
    """A topic per conversation: uniform, or by a Zipf law over topic
    popularity, with the same count at each popularity rank for every
    seed (largest remainders) and the ranks dealt to topics by the seed."""
    if "topic_zipf" not in mix:
        return rng.integers(0, n_topics, n_conv)
    w = 1.0 / np.arange(1, n_topics + 1) ** float(mix["topic_zipf"])
    share = n_conv * w / w.sum()
    count = np.floor(share).astype(int)
    rest = np.argsort(-(share - count), kind="stable")[:n_conv - count.sum()]
    count[rest] += 1
    topic = np.repeat(rng.permutation(n_topics), count)
    rng.shuffle(topic)
    return topic


def plan(mix: dict, seed: int, seconds: float, n_topics: int,
         subspace_dim: int) -> Plan:
    """The work of one run, fixed by ``mix``, ``seed`` and ``seconds``."""
    rng = np.random.default_rng(seed)
    lo, hi = mix["turns"]
    lengths_cycle = np.arange(lo, hi + 1)
    if mix["loop"] == "open":
        sessions = int(mix["sessions"])
        rate = float(mix["rate_per_s"])
        span = seconds
        if "bursts" in mix:
            grid, base = _burst_clock(mix["bursts"], seconds)
            span = float(base[-1])
        n = max(1, int(round(rate * span)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        rng.shuffle(gaps)
        due = np.cumsum(gaps)
        if "bursts" in mix:
            due = np.interp(due, base, grid, right=np.inf)
        due = due[due < seconds]
        n = len(due)
        session = np.resize(np.arange(sessions), n)
        rng.shuffle(session)
        # each session walks its own conversations, one after another
        lens = np.resize(lengths_cycle,
                         n + sessions * len(lengths_cycle))
        rng.shuffle(lens)
        conv = np.empty(n, int)
        turn = np.empty(n, int)
        first_conv = np.empty(sessions, int)
        length, c_next = [], 0
        cur = {}
        for i, s in enumerate(session):
            c, t = cur.get(s, (None, 0))
            if c is None or t == length[c]:
                c, t = c_next, 0
                length.append(int(lens[c_next]))
                c_next += 1
                if s not in cur:
                    first_conv[s] = c
            conv[i], turn[i] = c, t
            cur[s] = (c, t + 1)
        for s in range(sessions):        # sessions without any arrival
            if s not in cur:
                first_conv[s] = c_next
                length.append(int(lens[c_next]))
                c_next += 1
        length = np.asarray(length)
        extra = dict(due=due, session=session, conv=conv, turn=turn,
                     first_conv=first_conv)
        clients = sessions
    elif mix["loop"] == "closed":
        clients = int(mix["clients"])
        n_conv = int(mix["pool"])
        length = np.resize(lengths_cycle, n_conv)
        rng.shuffle(length)
        extra = {}
    else:
        raise ValueError(f"mix loop {mix['loop']!r}: expected open or closed")
    n_conv = len(length)
    width = int(length.max())
    topic = _topics(mix, rng, n_conv, n_topics)
    draws = rng.standard_normal((n_conv, width, 3, subspace_dim),
                                dtype=np.float32)
    # an exact share of the turns after the first jump sub-topic
    later = np.argwhere(np.arange(width)[None, :] < length[:, None])
    later = later[later[:, 1] > 0]
    n_jump = int(round(float(mix.get("subtopic_share", 0.0)) * len(later)))
    jump = np.zeros((n_conv, width), bool)
    pick = rng.permutation(len(later))[:n_jump]
    jump[later[pick, 0], later[pick, 1]] = True
    return Plan(loop=mix["loop"], topic=topic, length=length, draws=draws,
                jump=jump, clients=clients, **extra)


# ------------------------------------------------------------- driving
@dataclass
class Turn:
    """One turn as the client saw it (``perf_counter`` stamps)."""

    conv: int
    turn: int
    key: tuple
    due: float
    submitted: float = float("nan")
    done: float = float("nan")
    result: object = None          # the program's answer, or its exception

    @property
    def latency_s(self) -> float:
        return self.done - self.due


def _stamp(rec: Turn) -> Callable:
    def cb(fut: cf.Future) -> None:
        rec.done = time.perf_counter()
        exc = fut.exception()
        rec.result = exc if exc is not None else fut.result()
    return cb


class OpenLoop:
    """Drives an open-loop plan; ``run`` returns the turns in due order."""

    def __init__(self, mgr, plan_: Plan, queries: np.ndarray):
        self.mgr, self.plan, self.queries = mgr, plan_, queries
        self.keys = {}
        self.live: dict = {}
        self._lock = threading.Lock()
        self._reopening: dict = {}
        self._pending: dict = {}
        self._started: set = set()
        self.lateness_s: list = []

    def open_all(self) -> None:
        """Open every session on its first conversation (set-up)."""
        for s in range(self.plan.clients):
            self.keys[s] = (s, int(self.plan.first_conv[s]))
            self.live[self.mgr.open(self.keys[s])] = self.keys[s]

    def _submit(self, s: int, rec: Turn) -> None:
        rec.key = self.keys[s]
        rec.submitted = time.perf_counter()
        fut = self.mgr.submit(rec.key, self.queries[rec.conv, rec.turn])
        fut.add_done_callback(_stamp(rec))

    def _reopen(self, s: int, first: Turn) -> None:
        self.mgr.close(self.keys[s])
        self.keys[s] = (s, first.conv)
        self.live[self.mgr.open(self.keys[s])] = self.keys[s]
        with self._lock:
            pend = self._pending[s]
            self._submit(s, pend.pop(0))
            while pend and pend[0].turn != 0:
                self._submit(s, pend.pop(0))
            self._reopening[s] = bool(pend)

    def run(self, seconds: float) -> list:
        """Offers the plan's arrivals (all due within ``seconds``)."""
        with cf.ThreadPoolExecutor(1, thread_name_prefix="bench-reopen") \
                as closer:
            return self._offer(closer)

    def _offer(self, closer: cf.ThreadPoolExecutor) -> list:
        p = self.plan
        t0 = time.perf_counter()
        turns, reopens = [], []
        for i in range(len(p.due)):
            due = t0 + float(p.due[i])
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.lateness_s.append(max(time.perf_counter() - due, 0.0))
            s = int(p.session[i])
            rec = Turn(conv=int(p.conv[i]), turn=int(p.turn[i]), key=(),
                       due=due)
            turns.append(rec)
            with self._lock:
                new_conv = rec.turn == 0 and s in self._started
                self._started.add(s)
                if new_conv or self._reopening.get(s):
                    self._pending.setdefault(s, []).append(rec)
                    if new_conv:
                        self._reopening[s] = True
                        reopens.append(closer.submit(self._reopen, s, rec))
                    continue
                self._submit(s, rec)
        for f in reopens:
            f.result()
        return turns


class ClosedLoop:
    """Drives a closed-loop plan of fresh one-turn sessions."""

    def __init__(self, mgr, plan_: Plan, queries: np.ndarray):
        self.mgr, self.plan, self.queries = mgr, plan_, queries
        self.keys = {}
        self.live: dict = {}
        self._next = 0

    def open_all(self) -> None:
        for c in range(self.plan.clients):
            self._open(c)

    def _open(self, c: int) -> None:
        self.keys[c] = (c, self._take())
        self.live[self.mgr.open(self.keys[c])] = self.keys[c]

    def _take(self) -> int:
        if self._next >= len(self.plan.length):
            raise RuntimeError("the mix's pool of conversations ran out")
        self._next += 1
        return self._next - 1

    def _submit(self, c: int, now: float) -> tuple:
        conv = self.keys[c][1]
        rec = Turn(conv=conv, turn=0, key=self.keys[c], due=now,
                   submitted=now)
        fut = self.mgr.submit(rec.key, self.queries[conv, 0])
        fut.add_done_callback(_stamp(rec))
        return fut, rec

    def run(self, seconds: float) -> list:
        t0 = time.perf_counter()
        end = t0 + seconds
        turns = []
        pending = {}
        for c in range(self.plan.clients):
            fut, rec = self._submit(c, t0)
            pending[fut] = c
            turns.append(rec)
        while pending:
            done, _ = cf.wait(list(pending), return_when=cf.FIRST_COMPLETED)
            now = time.perf_counter()
            clients = [pending.pop(f) for f in done]
            if now >= end:
                continue
            # a fresh session per turn: close every finished client's
            # session, open its next, then submit them together
            for c in clients:
                self.mgr.close(self.keys[c])
                self._open(c)
            now = time.perf_counter()
            for c in clients:
                fut, rec = self._submit(c, now)
                pending[fut] = c
                turns.append(rec)
        return turns


LOOPS = {"open": OpenLoop, "closed": ClosedLoop}


def completion_rate(done: np.ndarray, end: float, gap_s: float
                    ) -> Optional[tuple]:
    """(turns, seconds) of a closed loop's window: it opens at the end of
    the first burst of completions and closes at the end of the last
    burst that ends by ``end``; a burst is a run of completions less
    than ``gap_s`` apart (a wave resolves its turns together).  None
    when fewer than two bursts end by ``end``."""
    t = np.sort(done[np.isfinite(done)])
    if not len(t):
        return None
    cut = np.nonzero(np.diff(t) > gap_s)[0]
    ends = np.append(t[cut], t[-1])          # last completion of each burst
    ends = ends[ends <= end]
    if len(ends) < 2:
        return None
    n = int(np.sum((t > ends[0]) & (t <= ends[-1])))
    return n, float(ends[-1] - ends[0])

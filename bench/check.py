"""How ``correct`` is decided: what the timed path produced, against the
plain reference.

Numbers compared, each against its limit in the configuration file:

* ``backend_self_gap`` — every back-end answer ``ShardedRouter`` merged
  in the window: the widest gap between a served score and the
  reference's score of the same id over the stored corpus.
* ``backend_rank_gap`` — the same answers against the reference's exact
  top-``k_c``: the widest gap between the scores at one rank, so a
  better document left out shows.
* ``served_self_gap`` — every answered turn: the widest gap between a
  served score and the reference's f32 score of the same id.
* ``served_rank_deficit`` — Algorithm 1's answer step: a session's cache
  holds at least the ``k_c`` answers of its own earlier misses and of
  this turn's, so its top-``k`` scores at each rank are at least those of
  the reference's top-``k`` over that union.  The widest shortfall over
  the turns whose session has such an answer.
* ``fill_row_gap`` — cached rows, in sessions drawn from the seed: the
  widest gap between a stored element and the corpus row of its id
  (padding columns against 0).
* ``foreign_ids`` — Algorithm 1's state: a session starts empty and its
  cache holds only what its own back-end answers brought.  The count of
  ids that came from elsewhere: ids served on an L1 or back-end turn that
  none of the session's back-end answers so far holds (a first turn
  answered from L1 counts all of its ids), and ids cached, at the close,
  in the slots drawn from the seed that none of the back-end answers of
  the session holding the slot holds.  A session is left out once the L2
  tier answered one of its turns (L2 rows warm L1).  Limit 0.
* ``unanswered`` — turns that failed or were answered degraded (limit 0).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

NAMES = ("backend_self_gap", "backend_rank_gap", "served_self_gap",
         "served_rank_deficit", "fill_row_gap", "foreign_ids", "unanswered")


@dataclass
class Served:
    """What one side (the program, or the control) served in a run."""

    calls: list        # [(queries (m, d), ids (m, k_c), scores (m, k_c))]
    turns: list        # [(instance, turn, psi (d,), tier, ids, scores)]


def _rms(x) -> float:
    x = np.asarray(x, np.float64)
    if not x.size:
        return 0.0
    if not np.isfinite(x).all():
        return float("inf")
    return float(np.sqrt(np.mean(np.square(x))))


def _max(x) -> float:
    x = np.asarray(x, np.float64)
    if not x.size:
        return 0.0
    if np.isnan(x).any():
        return float("inf")
    return float(np.max(x))


def _backend(ref, served: Served, k_c, precision="highest") -> dict:
    """Top-``k_c`` (scores, ids) of every back-end query row, keyed by its
    bytes: the rows the router was sent and those of the turns served as
    back-end answers.  Ids selected on the device at ``precision``; the
    scores are the reference's exact ones where ``precision`` is
    ``highest`` (the reference), and as computed where it is lower (the
    control)."""
    rows = [q for q, _, _ in served.calls] + \
        [t[2][None] for t in served.turns if t[3] == "backend"]
    out = {}
    if not rows:
        return out
    q = np.concatenate(rows)
    q = np.unique(q, axis=0)
    for lo in range(0, len(q), 256):
        s, i = ref.knn(q[lo:lo + 256], k_c, precision)
        if precision == "highest":
            s = ref.stored_scores(q[lo:lo + 256], i)
        for r, qq in enumerate(q[lo:lo + 256]):
            out[qq.tobytes()] = (s[r], i[r])
    return out


def numbers(ref, served: Served, exact: dict, k: int, k_c: int) -> dict:
    """The compared numbers of one side; ``exact`` is ``_backend`` at the
    reference's precision."""
    self_gap, rank_gap = [], []
    for q, ids, scores in served.calls:
        real = ids >= 0
        s = ref.stored_scores(q, ids)
        self_gap.append(np.where(real, np.abs(s - scores), 0.0).ravel())
        ref_s = np.stack([exact[r.tobytes()][0] for r in q])
        gap = np.abs(np.where(real, scores, -np.inf) - ref_s)
        rank_gap.append(np.nan_to_num(gap, nan=np.inf).ravel())
    self_gap = np.concatenate(self_gap) if self_gap else np.zeros(0)
    rank_gap = np.concatenate(rank_gap) if rank_gap else np.zeros(0)
    out = {"backend_self_gap": _max(self_gap),
           "backend_self_rms": _rms(self_gap),
           "backend_rank_gap": _max(rank_gap),
           "backend_rank_rms": _rms(rank_gap)}

    # served answers: scores of the served ids
    turns = served.turns
    if turns:
        psi = np.stack([t[2] for t in turns])
        ids = np.full((len(turns), k), -1, np.int64)
        sc = np.full((len(turns), k), -np.inf)
        for r, t in enumerate(turns):
            n = min(k, len(t[4]))
            ids[r, :n], sc[r, :n] = t[4][:n], t[5][:n]
        s = ref.f32_scores(psi, ids)
        gap = np.where(ids >= 0, np.abs(s - sc), 0.0)
        out["served_self_gap"] = _max(gap)
        out["served_self_rms"] = _rms(gap)
    else:
        out["served_self_gap"] = out["served_self_rms"] = 0.0

    # Algorithm 1: the union of the session's own back-end answers so far
    union: dict = {}
    rows, cands = [], []
    for r, t in sorted(enumerate(turns), key=lambda x: (x[1][0], x[1][1])):
        inst, _turn, p, tier = t[0], t[1], t[2], t[3]
        if tier == "backend":
            union.setdefault(inst, set()).update(
                int(d) for d in exact[p.tobytes()][1] if d >= 0)
        if union.get(inst):
            rows.append(r)
            cands.append(np.fromiter(sorted(union[inst]), np.int64))
    deficit = rms = 0.0
    if rows:
        width = max(len(c) for c in cands)
        cand = np.full((len(rows), width), -1, np.int64)
        for j, c in enumerate(cands):
            cand[j, :len(c)] = c
        r_s, _ = ref.answer(psi[rows], cand, k)
        gap = np.where(np.isfinite(r_s), np.maximum(r_s - sc[rows], 0.0), 0.0)
        deficit, rms = _max(gap), _rms(gap)
        worst = np.max(gap, axis=1)
        short = [turns[rows[j]] for j in np.nonzero(worst > 1e-5)[0]]
        out["short_turns"] = [(str(t[0]), int(t[1]), t[3]) for t in short]
    out["served_rank_deficit"] = deficit
    out["served_rank_rms"] = rms
    return out


def control(ref, served: Served, k: int, k_c: int,
            precision: str = "bf16") -> Served:
    """The control: the reference in the program's place at a lower
    precision — its own exact answers for the same back-end query rows,
    and Algorithm 1's answer over the union of its own answers for every
    turn whose session has one."""
    lo = _backend(ref, served, k_c, precision)
    calls = [(q, np.stack([lo[r.tobytes()][1] for r in q]),
              np.stack([lo[r.tobytes()][0] for r in q]))
             for q, _, _ in served.calls]
    union: dict = {}
    keep, cands = [], []
    for t in sorted(served.turns, key=lambda t: (t[0], t[1])):
        if t[3] == "backend":
            union.setdefault(t[0], set()).update(
                int(d) for d in lo[t[2].tobytes()][1] if d >= 0)
        if union.get(t[0]):
            keep.append(t)
            cands.append(sorted(union[t[0]]))
    turns = []
    if keep:
        cand = np.full((len(keep), max(map(len, cands))), -1, np.int64)
        for j, c in enumerate(cands):
            cand[j, :len(c)] = c
        s, i = ref.answer(np.stack([t[2] for t in keep]), cand, k,
                          precision)
        turns = [(t[0], t[1], t[2], t[3], i[j], s[j])
                 for j, t in enumerate(keep)]
    return Served(calls, turns)


def fill_row_gap(corpus: np.ndarray, slots: list) -> float:
    """Widest gap between cached rows and their corpus rows: ``slots``
    holds (ids (capacity,), rows (capacity, width)) read back from the
    cache; an id outside the corpus reads as infinite."""
    gap = 0.0
    d = corpus.shape[1]
    for ids, rows in slots:
        real = ids >= 0
        if (ids[real] >= corpus.shape[0]).any():
            return float("inf")
        if not real.any():
            continue
        diff = np.abs(rows[real, :d] - corpus[ids[real]])
        pad = np.abs(rows[real, d:])
        gap = max(gap, _max(diff), _max(pad) if pad.size else 0.0)
    return gap


def foreign_ids(served: Served, cached: list) -> int:
    """The ``foreign_ids`` count.  The back-end answer of a turn is what
    the router returned for its query row (``served.calls``); ``cached``
    holds (session key or None for a slot no session holds, ids) per
    slot read back at the close."""
    answer = {}
    for q, ids, _ in served.calls:
        for row, got in zip(q, ids):
            answer[row.tobytes()] = got
    own: dict = {}
    via_l2: set = set()
    count = 0
    for t in sorted(served.turns, key=lambda t: (t[0], t[1])):
        key, psi, tier, ids = t[0], t[2], t[3], t[4]
        mine = own.setdefault(key, set())
        if tier == "backend" and psi.tobytes() in answer:
            mine.update(int(d) for d in answer[psi.tobytes()] if d >= 0)
        if tier in ("l2", "l2_reuse"):
            via_l2.add(key)
        if key not in via_l2 and tier in ("l1", "backend"):
            count += _outside(ids, mine)
    for key, ids in cached:
        if key not in via_l2:
            count += _outside(ids, own.get(key, set()))
    return count


def _outside(ids, mine: set) -> int:
    ids = np.asarray(ids)
    ids = ids[ids >= 0]
    return int(np.sum(~np.isin(ids, np.fromiter(mine, np.int64,
                                                len(mine)))))


def verdict(values: dict, limits: dict) -> tuple:
    """(correct, checks): every number in NAMES against its limit."""
    checks = {n: {"value": values[n], "limit": limits[n]} for n in NAMES}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks

"""Share (%) of answered turns the shared L2 tier answered (shard hits and
memo reuse)."""


def read(run):
    tiers = [t.result.tier for t in run.answered()]
    if not tiers:
        return None
    return 100.0 * sum(x in ("l2", "l2_reuse") for x in tiers) / len(tiers)

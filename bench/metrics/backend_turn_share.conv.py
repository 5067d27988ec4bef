"""Share (%) of answered turns the back end answered: 1 - the hit rate."""


def read(run):
    tiers = [t.result.tier for t in run.answered()]
    return 100.0 * tiers.count("backend") / len(tiers) if tiers else None

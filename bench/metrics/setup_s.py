"""Set-up seconds: process start to the first measured turn."""


def read(run):
    return run.setup_s

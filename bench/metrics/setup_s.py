"""setup_s: process start to window start (host clock)."""


def read(run):
    return run.setup_s

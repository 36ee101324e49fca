"""Process start to window start: tapes, compiles, warm-up (host clock)."""


def read(run):
    return run.setup_s

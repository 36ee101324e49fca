"""Verified decisions per second, over the whole window: from its opening to
the completion of the last decision it started (host clock)."""


def read(run):
    done = run.completed()
    if not done:
        return None
    return len(done) / max(d.done for d in run.decisions if d.done is not None)

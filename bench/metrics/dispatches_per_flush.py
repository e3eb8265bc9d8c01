"""dispatches_per_flush (scoring dispatch): scorer dispatches per flush."""


def read(run):
    flushes = run.counts.get("flushes")
    if not flushes:
        return None
    return run.counts.get("dispatches", 0) / flushes

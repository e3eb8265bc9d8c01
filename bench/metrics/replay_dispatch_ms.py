"""replay_dispatch_ms (replay dispatch and device): mean wall time per
matrix of optimizer.replay.replay, from dispatch to fetched result."""


def read(run):
    spans = run.spans.get("replay_dispatch", [])
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e3

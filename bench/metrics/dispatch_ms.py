"""dispatch_ms (scoring dispatch): mean wall time of
ShardedScorer.score_stack, placement to host outputs."""


def read(run):
    spans = run.spans.get("score_stack", [])
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e3

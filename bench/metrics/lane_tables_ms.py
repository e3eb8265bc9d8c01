"""lane_tables_ms (scenario lowering): mean wall time per matrix of
optimizer.scenarios.lane_tables."""


def read(run):
    spans = run.spans.get("lane_tables", [])
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e3

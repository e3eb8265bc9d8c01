"""flush_host_ms (host flush): mean per flush of the flush span less the
score_stack spans inside it: preprocessing, store append and gather,
request assembly and padding, attaching scores."""


def read(run):
    flushes = run.spans.get("flush", [])
    stacks = run.spans.get("score_stack", [])
    if not flushes:
        return None
    host = []
    for s, e in flushes:
        inner = sum(b - a for a, b in stacks if a >= s and b <= e)
        host.append(e - s - inner)
    return sum(host) / len(host) * 1e3

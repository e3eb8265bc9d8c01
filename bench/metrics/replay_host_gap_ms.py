"""replay_host_gap_ms (replay dispatch and device): mean over
consecutive matrices of (start of the next replay.dispatch span) - (end
of this replay.wait span), the program's spans in the measured window:
host time in which the closed loop has nothing queued on the device."""

import bisect

from bench import program_spans


def read(run):
    waits = program_spans.in_window(run, "replay.wait")
    starts = [e.ts for e in program_spans.in_window(run,
                                                    "replay.dispatch")]
    gaps = []
    for w in waits:
        end = w.ts + w.dur
        i = bisect.bisect_left(starts, end)
        if i < len(starts):
            gaps.append(starts[i] - end)
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3

"""replay_wait_ms (replay dispatch and device): mean wall time per
matrix of the program's replay.wait span (optimizer.replay
PendingReplay.result: from the launch's return until the outputs are
ready and fetched), in the measured window."""

from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, "replay.wait")

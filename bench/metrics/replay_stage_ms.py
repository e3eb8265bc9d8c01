"""replay_stage_ms (replay dispatch and device): mean wall time per
matrix of the program's replay.stage span (optimizer.replay: padding,
casting and placing the lane tables and the scan carry on the device),
in the measured window."""

from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, "replay.stage")

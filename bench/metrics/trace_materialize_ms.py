"""trace_materialize_ms (trace materialization): mean wall time per
matrix of the program's replay.materialize_traces span
(optimizer.replay.traces_from_result), in the measured window."""

from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, "replay.materialize_traces")

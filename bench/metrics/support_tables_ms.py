"""support_tables_ms (scenario lowering): mean wall time per matrix of
the program's replay.support_tables span (optimizer.replay: padding,
casting and placing the Karasu lanes' support ids and the support
grid), in the measured window."""

from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, "replay.support_tables")

"""scenario_build_ms (scenario lowering): mean wall time per matrix of
the program's replay.build_scenarios span
(optimizer.scenarios.build_scenarios), in the measured window."""

from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, "replay.build_scenarios")

"""support_staged_mb (scenario lowering): mean per matrix of the host
bytes of the Karasu support tables placed on the device, the ``bytes``
arg of the program's replay.support_tables span, in MB (1e6 bytes), in
the measured window."""

from bench import program_spans


def read(run):
    spans = program_spans.in_window(run, "replay.support_tables")
    if not spans:
        return None
    return sum(e.args["bytes"] for e in spans) / len(spans) / 1e6

"""replay_pad_share (replay dispatch and device): share of the scanned
lanes that are padding, 1 - sum(lanes) / sum(padded) over the args of
the program's replay.dispatch spans in the measured window."""

from bench import program_spans


def read(run):
    spans = program_spans.in_window(run, "replay.dispatch")
    if not spans:
        return None
    lanes = sum(e.args["lanes"] for e in spans)
    padded = sum(e.args["padded"] for e in spans)
    return 1.0 - lanes / padded

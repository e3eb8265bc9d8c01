"""score_step_mfu (model step and device): forward operations of the
rows actually scored (padding excluded, counted from shapes and widths
by bench/flops.py) over the traced window times the chip's bf16 peak,
in percent."""


def read(run):
    peak = run.values.get("peak_flops")
    if run.trace_summary is None or not peak or "flops" not in run.values:
        return None
    return 100.0 * run.values["flops"] / (run.trace_summary["window_s"]
                                          * peak)

"""Whole step: the matrix-multiply operations of the steps that ran in
the traced window (benchmark/flops.py, from the configuration's sizes) over
window x chips x the peak of the device kind. Chip runs only."""

from benchmark import flops


def read(run):
    tr = run["trace"]
    if not tr or not tr["step_count"] or run["device"]["platform"] != "tpu":
        return None
    peak = flops.peak_flops(run["device"]["kind"]) * run["chips"]
    return 100.0 * run["step_flops"] * tr["step_count"] / (tr["window_s"] * peak)

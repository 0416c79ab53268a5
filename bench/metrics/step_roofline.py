"""The step program's share of its roofline: the least time the chip needs
for the window's dispatches (their needed FLOPs at the bf16 peak, or their
needed bytes at the HBM bandwidth, whichever is longer; bench/work.py
counts both), over the device time of the step program in the trace.  With
these models the bytes bound it: each packet moves a table record of
about 1.2 KB and needs a few hundred FLOPs."""
from bench import work


def read(run):
    tr, w, peak = run["trace"], run["work"], run["peaks"]
    if not tr or not w or not peak or tr["step_device_s"] <= 0 or not w["dispatches"]:
        return None
    t, _ = work.roofline_seconds(w["flops"], w["bytes"], peak)
    return 100.0 * t / (tr["step_device_s"] * run["chips"])

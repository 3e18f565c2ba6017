"""The decode program's share of its HBM roofline: (least bytes one decode
step must move, ``lib.flops.decode_step_bytes`` averaged over the wave's
positions, over the HBM peak) over the device time per decode step, from
the trace's executions of the program named ``*decode_step*``."""
from lib import trace as T


def read(R):
    if not R.trace:
        return None
    runs, secs = 0, 0.0
    for dev in R.trace["devices"].values():
        n, s = T.module_stats(dev, "decode_step")
        runs, secs = runs + n, secs + s
    if not runs or secs <= 0:
        return None
    least_s = sum(R.decode_step_bytes) / len(R.decode_step_bytes) / R.peaks["hbm_bytes_s"]
    return 100.0 * least_s / (secs / runs)

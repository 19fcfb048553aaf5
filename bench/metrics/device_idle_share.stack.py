"""Share of the measured window in which no operation ran on the device:
1 - (union of the device's operation intervals) / (window), from the trace."""

from bench import trace_reduce

NAME = "device_idle_share.stack"
UNIT = "%"
LAYER = "device (TPU)"
MOVES = "frames_per_s"
SOURCE = "device_trace"


def read(record, trace):
    lo, hi = trace["lo"], trace["hi"]
    if hi <= lo or not trace["summary"]["devices"]:
        return None
    busy = trace_reduce.busy_seconds(trace["summary"], lo, hi)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))

"""The scan programs' share of their roofline: the least time the chip
needs for the work the answers hold (`bench.counts`, from the summed depth
of every dispatched answer, against `bench.peaks`), over the summed device
time of the programs whose name holds ``coadd_scan`` in the trace.  The
work is a lower bound, so the share cannot pass 100%; it is bytes-bound at
these sizes."""

from bench import counts, peaks, trace_reduce

NAME = "scan_roofline_share.stack"
UNIT = "%"
LAYER = "map + reduce, XLA lane (core/mapper.py, core/reducer.py, _coadd_scan*)"
MOVES = "frames_per_s"
SOURCE = "device_trace"
PROGRAM = "coadd_scan"


def read(record, trace):
    t_dev = trace_reduce.program_seconds(trace["summary"], PROGRAM,
                                         trace["lo"], trace["hi"])
    qs = [q for q in record.get("queries", []) if q["dispatched"]]
    if t_dev <= 0 or not qs:
        return None
    ops = nbytes = 0.0
    for q in qs:
        o, b = counts.query_work(q["samples"], q["npix"])
        ops, nbytes = ops + o, nbytes + b
    t_min, _ = counts.roofline_seconds(ops, nbytes, peaks.peaks_for(record["device_kind"]))
    return 100.0 * t_min / t_dev

"""Share of the HBM roofline: the least time the call's fixed bytes
(``work/<entry>.py``, from shapes only) take at the chip's published HBM
bandwidth, over the device-busy time inside the call's host interval;
median over the traced calls, in %. The loop is closed, so the device
runs only that call then. Memory-bound: the work counts no FLOPs."""
import statistics

from benchmarks.chip.peaks import peaks


def read(run):
    if run.trace is None:
        return None
    busy = [b for b in run.trace.busy_per_call() if b > 0]
    if not busy:
        return None
    least = run.work["bytes"] / peaks(run.device_kind).hbm_bw
    return 100.0 * least / statistics.median(busy)

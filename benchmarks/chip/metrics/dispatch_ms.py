"""Engine dispatch: the host span from the engine call until it returns,
before ``block_until_ready``; median over the calls of the window, in ms.
Covers validation, planning, the guard ladder and the enqueue."""
import statistics


def read(run):
    return 1e3 * statistics.median(t1 - t0 for t0, t1, _ in run.calls)

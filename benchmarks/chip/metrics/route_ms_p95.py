"""95th percentile of call latency, host call to ``block_until_ready``,
over every call of the window (nearest rank)."""
import math


def read(run):
    lat = sorted(end - start for start, _, end in run.calls)
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]

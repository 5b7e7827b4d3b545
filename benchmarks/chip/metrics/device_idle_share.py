"""Device idle share of the traced window: 1 - (union of device-busy
intervals) / (window length), in %."""


def read(run):
    if run.trace is None or not run.trace.devices or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)

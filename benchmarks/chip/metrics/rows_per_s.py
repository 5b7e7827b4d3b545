"""Table rows taken through the query per second: all the rows of all the
calls of the window over the window's whole length."""


def read(run):
    return run.units_per_call * len(run.calls) / run.window_s

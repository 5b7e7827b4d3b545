"""Tokens routed per second: all the tokens of all the calls of the window
over the window's whole length."""


def read(run):
    return run.units_per_call * len(run.calls) / run.window_s

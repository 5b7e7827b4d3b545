"""Set-up time: process start to the first timed call (imports, inputs
made on the device, tracing and compiling or loading from the cache, one
warm call)."""


def read(run):
    return run.setup_s

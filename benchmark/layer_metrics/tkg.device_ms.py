"""Model step programs: median device time of one execution of the
token-generation module in the traced window (first chip). ms."""


def read(run):
    if run.trace is None:
        return None
    v = run.trace.tkg_device_s()
    return None if v is None else v * 1e3

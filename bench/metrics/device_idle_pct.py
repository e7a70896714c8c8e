"""Share of the traced window (trace start to the window's close) in which
no operation ran on the device, in %."""


def read(run):
    w = run.trace.window_s()
    return 100.0 * (1.0 - run.trace.busy_s() / w) if w > 0 else None

"""Device layer: share of the traced window in which no operation ran on
the chip -- 1 minus the union of the device-operation intervals over the
window, from the profiler trace."""


def read(ctx):
    dt = ctx["trace"]
    if dt is None or not dt.devices() or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dt.busy_s() / ctx["window_s"])

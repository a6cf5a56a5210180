"""Share of the traced window in which no operation ran on the device, in %."""


def read(ctx):
    r = ctx.reduced
    if not r.window_ns or not r.devices:
        return None
    return 100.0 * (1.0 - r.busy_ns / r.window_ns)

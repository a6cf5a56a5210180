"""Device time of the Pallas kernels (``tpu_custom_call``) per round, in ms."""


def read(ctx):
    total = sum(ctx.reduced.kernel_ns.values())
    if not total or not ctx.rounds:
        return None
    return total / max(1, ctx.reduced.devices) / ctx.rounds / 1e6

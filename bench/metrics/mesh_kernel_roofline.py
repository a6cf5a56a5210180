"""Share of the roofline of the mesh uplink's Pallas kernels, ``randk_gather``
and ``scatter_accum``, in %: the least time their calls in the window could
take, the bytes each call moves (``bench.mesh_yardstick.call_bytes``, from
the compiled round's own shapes) at the chip's HBM bandwidth, over their
measured device time. Both are bound by memory."""

from bench.mesh_yardstick import call_bytes


def read(ctx):
    text = getattr(ctx.session, "compiled_text", None)
    if text is None:
        return None
    table = call_bytes(text())
    least, spent = 0.0, 0.0
    for name, ns in ctx.reduced.kernel_ns.items():
        if name not in table:
            continue
        least += ctx.reduced.kernel_count[name] * table[name] / ctx.peaks["hbm_bytes_per_s"]
        spent += ns / 1e9
    if not spent:
        return None
    return 100.0 * least / spent

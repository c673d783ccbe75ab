"""Device program dispatch per sweep: host to device copy, the
program, device to host copy, blocked (the program's dispatch span)."""

DISPATCH = ("device.execute", "device.jit_compile_and_execute")


def read(ctx):
    spans = [s["spans"] for s in ctx.sweeps]
    if not spans or not any(k in sp for sp in spans for k in DISPATCH):
        return None
    return sum(sp.get(k, 0.0) for sp in spans for k in DISPATCH) \
        / len(spans) * 1e3

"""Host-device copies per sweep: the ``device.h2d`` span (the grid
program's inputs placed on the device, waited for) and the
``device.d2h`` span (its outputs back to the host), both inside the
dispatch that ``dispatch_ms`` reads."""

SPANS = ("device.h2d", "device.d2h")


def read(ctx):
    spans = [s["spans"] for s in ctx.sweeps]
    if not spans or not all(k in sp for sp in spans for k in SPANS):
        return None
    return sum(sp[k] for sp in spans for k in SPANS) / len(spans) * 1e3

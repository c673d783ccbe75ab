"""Pad and stack per sweep: the ``device.pad_stack`` span, which fills
the zero-padded ``(G, S)`` composition columns, the parameter rows and
the ``(G, K)`` PUE and grid-CI columns of the grid program's inputs."""

SPAN = "device.pad_stack"


def read(ctx):
    spans = [s["spans"] for s in ctx.sweeps]
    if not spans or not all(SPAN in sp for sp in spans):
        return None
    return sum(sp[SPAN] for sp in spans) / len(spans) * 1e3

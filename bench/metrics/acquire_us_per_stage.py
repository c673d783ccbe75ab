"""Trace acquisition (event loop or divergence replay) host time per
stage row acquired: the ``device.acquire_traces`` span over the summed
stage counts of every trace group. Host time per simulated event, so a
change to the model's event count does not pass for a speed-up."""


def read(ctx):
    t = rows = 0
    for s in ctx.sweeps:
        if "device.acquire_traces" not in s["spans"]:
            return None
        t += s["spans"]["device.acquire_traces"]
        rows += sum(s["stages"])
    return t / rows * 1e6 if rows else None

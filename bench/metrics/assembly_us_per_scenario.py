"""Record assembly per scenario: the ``device.assemble_records`` span
(energy and carbon reports from the program's sums, the single-site
metrics and the record of every scenario), over the scenarios run."""

SPAN = "device.assemble_records"


def read(ctx):
    t = n = 0
    for s in ctx.sweeps:
        if SPAN not in s["spans"]:
            return None
        t += s["spans"][SPAN]
        n += s["scenarios"]
    return t / n * 1e6 if n else None

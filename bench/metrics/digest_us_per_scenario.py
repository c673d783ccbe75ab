"""Key digests per scenario: the runner's ``sweep.key_digest`` span
(each scenario's cache key, a sha256 over its whole config tree) and
the device path's ``device.group`` span (trace-key digests and the
grouping by them), over the scenarios run."""

SPANS = ("sweep.key_digest", "device.group")


def read(ctx):
    t = n = 0
    for s in ctx.sweeps:
        if not all(k in s["spans"] for k in SPANS):
            return None
        t += sum(s["spans"][k] for k in SPANS)
        n += s["scenarios"]
    return t / n * 1e6 if n else None

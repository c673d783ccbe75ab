"""Sweep-runner time per scenario: each sweep's wall time less its
trace acquisition and device dispatch spans (key digests, cache lookup
and store, pad and stack, record assembly), over the scenarios run."""

DISPATCH = ("device.execute", "device.jit_compile_and_execute")


def read(ctx):
    own = n = 0
    for s in ctx.sweeps:
        sp = s["spans"]
        if "device.acquire_traces" not in sp:
            return None
        own += (s["wall_s"] - sp["device.acquire_traces"]
                - sum(sp.get(k, 0.0) for k in DISPATCH))
        n += s["scenarios"]
    return own / n * 1e6 if n else None

"""Share of the memory roofline that the grid program ``_group_kernel``
reaches in the traced sweeps: the least time (live unpadded bytes over
the chips' HBM bandwidth, ``harness/kernel_bytes.py``) over the
program's device time (its ``XLA Modules`` events, averaged over the
chips used)."""

from kernel_bytes import live_bytes


def read(ctx):
    tr = ctx.trace
    if tr is None or tr["kernel_s"] <= 0:
        return None
    traced = [s for s in ctx.sweeps if s["traced"]]
    nbytes = sum(live_bytes(s["stages"], s["ks"]) for s in traced)
    least_s = nbytes / (ctx.peak["hbm_bytes_per_s"] * tr["devices"])
    return 100.0 * least_s / tr["kernel_s"]

"""XLA executables built or loaded inside the measured window, counted
from JAX's monitoring events (a backend compile or a persistent-cache
load). Set-up warms every bucket, so 0 is expected."""


def read(ctx):
    return ctx.compiles

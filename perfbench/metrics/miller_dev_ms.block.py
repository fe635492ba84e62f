"""Device time of the Miller loops per block: top-level device operations
under the scope ``bls.stage3/miller_loop``. A block runs each staged
program once: the time per run, over the runs wholly inside the traced
slice. None for a program without named scopes."""

from harness import scopes


def read(ctx):
    return scopes.device_ms_per_block(ctx, "bls.stage3/miller_loop")

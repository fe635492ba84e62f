"""Device time of the Miller loops per run of stage 3's program (one run
per chunk of a segment): top-level device operations under the scope
``bls.stage3/miller_loop``, over the runs wholly inside the traced
slice. None for a program without named scopes."""

from harness import scopes


def read(ctx):
    return scopes.device_ms_per_block(ctx, "bls.stage3/miller_loop")

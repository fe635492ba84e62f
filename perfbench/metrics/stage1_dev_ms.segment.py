"""Device time of stage 1 per run of its program (one run per chunk of
a segment): top-level device operations under the scope ``bls.stage1``
(decompression, SSWU and the shared square-root ladder, the map to G2),
over the runs wholly inside the traced slice. None for a program
without named scopes."""

from harness import scopes


def read(ctx):
    return scopes.device_ms_per_block(ctx, "bls.stage1")

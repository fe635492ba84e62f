"""Device time of the key gather and stage 2 per block: top-level device
operations under the scopes ``bls.gather`` and ``bls.stage2`` (key sums,
the G2 ladder of the subgroup check and the random scaling, the G1
ladder). A block runs each staged program once: the time per run, over
the runs wholly inside the traced slice. None for a program without
named scopes."""

from harness import scopes


def read(ctx):
    return scopes.device_ms_per_block(ctx, "bls.gather", "bls.stage2")

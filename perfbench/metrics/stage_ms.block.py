"""Dispatch-to-sync time of the staged program per block, summed over
its stages (gather and stages 1-3), from ``bls_device_stage_seconds``."""


def read(ctx):
    blocks = ctx.run["submissions"]
    _n, s = ctx.delta.hist("bls_device_stage_seconds")
    return 1e3 * s / blocks if blocks else None

"""Host pack time per segment (packers), from
``bls_device_pack_seconds{phase="total"}`` over the segment's chunks."""


def read(ctx):
    segments = ctx.run["submissions"]
    _n, s = ctx.delta.hist("bls_device_pack_seconds", phase="total")
    return 1e3 * s / segments if segments else None

"""Host pack time per block (packers), from
``bls_device_pack_seconds{phase="total"}``."""


def read(ctx):
    blocks = ctx.run["submissions"]
    _n, s = ctx.delta.hist("bls_device_pack_seconds", phase="total")
    return 1e3 * s / blocks if blocks else None

"""Host-to-device bytes shipped per signature set (key table: with the
table resident, pubkeys cross as indices), from
``bls_device_h2d_bytes_total``."""


def read(ctx):
    sets = ctx.run["sets"]
    return ctx.delta.count("bls_device_h2d_bytes_total") / sets if sets else None

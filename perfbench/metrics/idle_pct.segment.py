"""Share of the traced window in which no operation ran on the device:
between a segment's chunks, the host's set preparation, key resolution
and packing."""


def read(ctx):
    return ctx.trace["idle_pct"] if ctx.trace else None

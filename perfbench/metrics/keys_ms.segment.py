"""Host key resolution per segment (key table), from
``bls_device_key_table_resolve_seconds{phase="total"}``: the program's
``DeviceKeyTable.resolve_sets`` over the segment's chunks, lock waits
included. None for a program that does not time it."""

FAMILY = "bls_device_key_table_resolve_seconds"


def read(ctx):
    segments = ctx.run["submissions"]
    if FAMILY not in ctx.delta.after or not segments:
        return None
    _n, s = ctx.delta.hist(FAMILY, phase="total")
    return 1e3 * s / segments

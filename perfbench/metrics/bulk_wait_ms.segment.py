"""Bulk-class wait per segment, from
``verification_scheduler_bulk_wait_seconds{kind="chain_segment"}``:
the submit-to-flush waits of a segment's chunks, summed (linger,
throttle excursions and the flushes of the chunks queued ahead). None
for a program without that histogram."""

FAMILY = "verification_scheduler_bulk_wait_seconds"


def read(ctx):
    segments = ctx.run["submissions"]
    if FAMILY not in ctx.delta.after or not segments:
        return None
    _n, s = ctx.delta.hist(FAMILY, kind="chain_segment")
    return 1e3 * s / segments

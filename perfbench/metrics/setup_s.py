"""Set-up: the process's time from its start to the window's start
(imports and device, registry, key table, compile rungs, signed traffic,
warm-up)."""


def read(ctx):
    return ctx.run["setup_s"]

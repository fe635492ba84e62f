"""The window's length over the blocks verified in it: the time to
import a block's signatures."""


def read(ctx):
    blocks = ctx.run["submissions"]
    return 1e3 * ctx.run["window_s"] / blocks if blocks else None

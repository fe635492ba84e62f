"""Share of the traced window in which the device ran nothing while the
program's key table was resolving keys (a ``key_table.*`` span open):
the device idle that host key resolution leaves, at most
``idle_pct.block``. None for a program without those spans."""

from harness import scopes


def read(ctx):
    return scopes.idle_pct_under(ctx, "key_table.")

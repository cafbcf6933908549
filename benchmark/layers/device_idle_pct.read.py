from _lib import idle_pct


def read(ctx):
    return idle_pct(ctx)

from _lib import busy_pct


def read(ctx):
    return busy_pct(ctx, "read", "decode")

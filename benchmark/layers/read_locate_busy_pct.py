from _spans import busy_pct


def read(ctx):
    return busy_pct(ctx, "read", "locate")

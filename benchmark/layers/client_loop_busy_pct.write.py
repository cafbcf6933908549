from _loop import busy_pct


def read(ctx):
    return busy_pct(ctx)

from _rebuild import write_slowdown_pct


def read(ctx):
    return write_slowdown_pct(ctx)

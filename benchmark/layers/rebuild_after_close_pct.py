from _rebuild import after_close_pct


def read(ctx):
    return after_close_pct(ctx)

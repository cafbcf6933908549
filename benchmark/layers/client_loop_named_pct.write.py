from _loop import named_pct


def read(ctx):
    return named_pct(ctx)

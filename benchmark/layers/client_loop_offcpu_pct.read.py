from _loop import offcpu_pct


def read(ctx):
    return offcpu_pct(ctx)

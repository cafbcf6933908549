from _spans import share_pct


def read(ctx):
    return share_pct(ctx, "read", ("self",), ("wall",))

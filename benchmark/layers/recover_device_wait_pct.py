from _spans import BOUNDARY, share_pct


def read(ctx):
    return share_pct(ctx, "read", ("dev_fetch",), BOUNDARY)

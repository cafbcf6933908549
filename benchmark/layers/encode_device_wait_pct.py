from _spans import BOUNDARY, share_pct


def read(ctx):
    return share_pct(ctx, "write", ("dev_fetch",), BOUNDARY)

from _classes import rate_mbps


def read(ctx):
    return rate_mbps(ctx, "read")

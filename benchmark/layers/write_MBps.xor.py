from _goals import family_mbps


def read(ctx):
    return family_mbps(ctx, "xor")

from _lib import encode_boundary_mbps


def read(ctx):
    return encode_boundary_mbps(ctx)

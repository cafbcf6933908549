from _lib import recover_boundary_mbps


def read(ctx):
    return recover_boundary_mbps(ctx)

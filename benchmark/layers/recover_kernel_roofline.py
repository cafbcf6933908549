from _lib import recover_roofline


def read(ctx):
    return recover_roofline(ctx)

from _lib import encode_roofline


def read(ctx):
    return encode_roofline(ctx)

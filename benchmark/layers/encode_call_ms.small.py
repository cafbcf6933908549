from _lib import encode_call_ms


def read(ctx):
    return encode_call_ms(ctx)

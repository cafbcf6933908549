from _classes import p95_ms


def read(ctx):
    return p95_ms(ctx, "read")

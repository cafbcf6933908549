from _spans import ms_per_op


def read(ctx):
    return ms_per_op(ctx, "write", "send", "ack")

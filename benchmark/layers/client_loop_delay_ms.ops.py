from _loop import delay_ms


def read(ctx):
    return delay_ms(ctx)

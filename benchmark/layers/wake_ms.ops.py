from _loop import wake_ms


def read(ctx):
    return wake_ms(ctx)

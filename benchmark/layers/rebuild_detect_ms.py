from _rebuild import detect_ms


def read(ctx):
    return detect_ms(ctx)

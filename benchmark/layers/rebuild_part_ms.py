from _rebuild import part_ms


def read(ctx):
    return part_ms(ctx)

from _spans import ms_per_op


def read(ctx):
    """The ``waves`` row a ``read_file``: a read plan's part reads side
    by side, or the whole native gather."""
    return ms_per_op(ctx, "read", "waves")

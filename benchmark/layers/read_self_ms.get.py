from _spans import ms_per_op


def read(ctx):
    """The ``read_file`` root's self time a read: what no span under it
    covers."""
    return ms_per_op(ctx, "read", "self")

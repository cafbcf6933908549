from _spans import ms_per_op


def read(ctx):
    """The ``rmw_patch`` span's milliseconds a ``pwrite``: the region
    allocated, assembled from what was read back, and patched (every
    call that is not whole stripes opens it, read-back or not)."""
    return ms_per_op(ctx, "write", "rmw_patch")

from _counts import counts


def read(ctx):
    """Share of the parts the scatter sessions sent that went as
    descriptors on the shared-memory ring, not as bytes on a socket."""
    got = counts(ctx, "write", "ring_parts", "socket_parts")
    if got is None or not sum(got):
        return None
    return 100.0 * got[0] / sum(got)

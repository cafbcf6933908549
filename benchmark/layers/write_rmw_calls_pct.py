from _counts import counts


def read(ctx):
    """Share of the window's pwrite calls that read stripes back before
    they could encode: those that start inside a stripe of live data."""
    got = counts(ctx, "write", "rmw_reads")
    if got is None:
        return None
    return 100.0 * got[0] / ctx["phases"]["write"]["reps"]

from _counts import counts


def read(ctx):
    """Share of the window's whole chunks that the windowed write
    carried to the end, of those and the ones its whole-part fallback
    wrote (not eligible, or the window raised)."""
    got = counts(ctx, "write", "window_chunks", "fallback_chunks")
    if got is None or not sum(got):
        return None
    return 100.0 * got[0] / sum(got)

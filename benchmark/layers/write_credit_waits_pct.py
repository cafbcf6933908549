from _counts import counts


def read(ctx):
    """Share of the windowed write's segments that found the credit
    gate shut and waited for acknowledgements before they could go."""
    got = counts(ctx, "write", "window_credit_waits", "window_segments")
    if got is None or not got[1]:
        return None
    return 100.0 * got[0] / got[1]

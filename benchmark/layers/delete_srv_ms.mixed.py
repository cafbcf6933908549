from _counts import counts
from _spans import phase_ms


def read(ctx):
    """The master's handler time an ``unlink`` (``unlink_srv``, laid
    under the call's root span from the reply's ``srv_us``)."""
    ms, got = phase_ms(ctx, "write", "unlink_srv"), counts(
        ctx, "write", "unlinks")
    if ms is None or got is None or not got[0]:
        return None
    return ms / got[0]

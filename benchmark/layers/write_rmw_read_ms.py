from _counts import counts
from _spans import phase_ms


def read(ctx):
    """The ``rmw_read`` span's milliseconds a ``pwrite`` that read
    stripes back before it could encode."""
    ms, got = phase_ms(ctx, "write", "rmw_read"), counts(
        ctx, "write", "rmw_reads")
    if ms is None or got is None or not got[0]:
        return None
    return ms / got[0]

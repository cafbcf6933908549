from _counts import counts


def read(ctx):
    """Share of the 64 KiB blocks the window's reads asked for that the
    client's BlockCache answered, of those, the ones it had to fetch and
    the ones of reads that pass it by (4 MiB or more at a time)."""
    got = counts(ctx, "read", "cache_hit_blocks", "cache_miss_blocks",
                 "cache_bypass_blocks")
    if got is None or not sum(got):
        return None
    return 100.0 * got[0] / sum(got)

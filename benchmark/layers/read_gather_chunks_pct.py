from _counts import counts


def read(ctx):
    """Share of the chunk ranges read that the one native gather served
    whole (``stripe_gather_fast``), of those and the ones a read plan's
    waves served."""
    got = counts(ctx, "read", "gather_chunks", "planned_chunks")
    if got is None or not sum(got):
        return None
    return 100.0 * got[0] / sum(got)

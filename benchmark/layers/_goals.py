"""What the write path wrote under one goal family, in a cell that
keeps several goals side by side. A program without the family counts
(the parent of the PR that brought them) gives None, and the metric is
left out."""

from _counts import counts


def family_mbps(ctx, family: str):
    """Bytes the chunkservers acknowledged under one goal family
    (``<family>_payload_bytes``: ``copies``, ``xor`` or ``ec``), in MB
    over the window's seconds, summed over the sessions."""
    got = counts(ctx, "write", family + "_payload_bytes")
    return None if got is None else got[0] / 1e6 / ctx["window_s"]

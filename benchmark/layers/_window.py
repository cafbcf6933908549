"""The windowed whole-chunk write's trips to a worker thread (the count
``window_trips``, beside ``window_segments``): one a segment, one more
for each reap a full ring or a shut credit gate made on its own.
None on a program without the count (the parent's) or where no segment
went through the window."""

from _counts import counts


def trips_per_segment(ctx):
    got = counts(ctx, "write", "window_trips", "window_segments")
    if got is None or not got[1]:
        return None
    return got[0] / got[1]

from _classes import p95_ms


def read(ctx):
    """STAT and DELETE together: what a call the master alone answers
    waits behind the long ones on the client's one loop."""
    return p95_ms(ctx, "stat", "delete")

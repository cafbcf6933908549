from _counts import counts


def read(ctx):
    """What the read-modify-write branch moved beyond what the callers
    handed it: bytes read back, and bytes of the whole-stripe regions
    encoded and sent less the payload, over the payload."""
    got = counts(ctx, "write", "rmw_read_bytes", "rmw_region_bytes",
                 "payload_bytes")
    if got is None or not got[2]:
        return None
    read_back, region, payload = got
    return 100.0 * (read_back + region - payload) / payload

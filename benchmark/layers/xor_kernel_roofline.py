from _lib import xor_roofline


def read(ctx):
    # no xor call crossed the boundary: nothing to hold against a roofline
    if not getattr(ctx.get("tap"), "xor_calls", None):
        return None
    return xor_roofline(ctx)

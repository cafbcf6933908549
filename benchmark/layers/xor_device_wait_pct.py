from _spans import share_pct

XOR_LEGS = ("xor_dev_stage", "xor_dev_put", "xor_dev_run", "xor_dev_fetch")


def read(ctx):
    """xor parity's fetch leg over its four legs, the rule
    ``encode_device_wait_pct`` reads the products' by; None on a
    program without the xor rows."""
    return share_pct(ctx, "write", ("xor_dev_fetch",), XOR_LEGS)

from _lib import master_rpc_ms_per_op


def read(ctx):
    return master_rpc_ms_per_op(ctx)

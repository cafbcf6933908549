from _rebuild import write_through_loss_mbps


def read(ctx):
    return write_through_loss_mbps(ctx)

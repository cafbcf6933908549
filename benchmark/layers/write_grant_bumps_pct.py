from _rebuild import grant_bumps_pct


def read(ctx):
    return grant_bumps_pct(ctx)

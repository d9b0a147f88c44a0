"""Share of the traced window in which no operation ran on the device,
averaged over the chips used.  Moves ``samples_per_s``."""
UNIT = "%"


def read(ctx):
    red = ctx.reduced
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])

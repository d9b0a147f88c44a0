"""The whole clock's share of the chips' peak: the least time the clocks'
required work takes at the published peaks (operations over peak FLOP/s or
bytes over peak bandwidth, the larger; ``chipbench.work.mf_clock``) over
the traced window they ran in.  Moves ``samples_per_s``."""
from chipbench.peaks import least_seconds

UNIT = "%"


def read(ctx):
    w = ctx.work["clock"]
    least, _ = least_seconds(w["flops"], w["bytes"], ctx.kind, ctx.chips)
    return 100.0 * least * ctx.clocks / ctx.reduced["window_s"]

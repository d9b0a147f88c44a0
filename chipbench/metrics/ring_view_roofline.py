"""``ring_view``'s share of its roofline: the least time of the calls'
required work (``chipbench.work.ring_view`` per call on one chip, at the
published peaks) over their summed device time in the trace.  Moves
``samples_per_s``."""
from chipbench.peaks import least_seconds
from chipbench.reduce import kernel_calls

UNIT = "%"
# The Pallas call carries no name: the kernel is the TPU custom call that
# takes (uclock [W,1], cview [R,P], base [1,d], ring [W,P,d]) and returns
# the views [R,d].
NAME = (r"= f32\[\d+,\d+\]\S* custom-call\(s32\[\d+,1\]\S* %\S+, "
        r"s32\[\d+,\d+\]\S* %\S+, f32\[1,\d+\]\S* %\S+, "
        r"f32\[\d+,\d+,\d+\]\S* %\S+\), "
        r"custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    calls, secs = kernel_calls(ctx.reduced, NAME)
    if not calls:
        return None
    w = ctx.work["ring_view"]
    least, _ = least_seconds(w["flops"], w["bytes"], ctx.kind)
    return 100.0 * least * calls / secs

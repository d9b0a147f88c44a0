"""``vap_suffix_norms``'s share of its roofline: the least time of the
calls' required work (``chipbench.work.vap_suffix_norms`` per call on one
chip, at the published peaks) over their summed device time in the trace.
Moves ``samples_per_s``."""
from chipbench.peaks import least_seconds
from chipbench.reduce import kernel_calls

UNIT = "%"
# The Pallas call carries no name: the kernel is the TPU custom call that
# takes (uclock [W,1], the clock [1,1], ring [W,P,d]) and returns the
# norms [W+1,P].
NAME = (r"= f32\[\d+,\d+\]\S* custom-call\(s32\[\d+,1\]\S* %\S+, "
        r"s32\[1,1\]\S* %\S+, f32\[\d+,\d+,\d+\]\S* %\S+\), "
        r"custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    calls, secs = kernel_calls(ctx.reduced, NAME)
    if not calls:
        return None
    w = ctx.work["vap_suffix_norms"]
    least, _ = least_seconds(w["flops"], w["bytes"], ctx.kind)
    return 100.0 * least * calls / secs

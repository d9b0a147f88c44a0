"""Share of the traced window the chips spent in collectives, averaged
over the chips: the self time of every operation whose kind is a
collective's (``chipbench.reduce.COLLECTIVE``: all-gather, all-reduce,
...) over the devices and the window.  The kind is the HLO op's, so an
operation that only takes a collective's result as an operand is not
counted.  Moves ``samples_per_s``."""
from chipbench.reduce import COLLECTIVE, short_name

UNIT = "%"


def read(ctx):
    red = ctx.reduced
    secs = sum(s for name, (_, s) in red["op_time"].items()
               if COLLECTIVE.match(short_name(name).split(" ")[0]))
    if not secs:
        return None
    return 100.0 * secs / red["n_devices"] / red["window_s"]

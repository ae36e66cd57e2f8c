"""mfu.decode: the decode step's share of the chips' roofline, in %.

The work is what the steps require (the counters of the configuration's
family, families/<family>.py); the roofline time is the larger of its
FLOPs over peak FLOP/s and its bytes over peak HBM bytes/s; the time is
the traced window's, on the host clock.
"""

from chipbench import readers


def read(run):
    return readers.step_mfu(run)

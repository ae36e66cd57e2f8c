"""idle_share.prefill: % of the traced window with no op on a device,
averaged over the devices."""

from chipbench import readers


def read(run):
    return readers.idle_share(run)

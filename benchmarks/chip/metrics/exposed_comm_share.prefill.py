"""exposed_comm_share.prefill: % of the traced window in which a device's
TensorCore waits on communication, averaged over the devices.

A TensorCore runs one op at a time, so time in any of these is time in
which it computes nothing: a synchronous collective, the ``-done`` wait
of an asynchronous one, a fusion that runs a collective (XLA's
reduce-scatter shows as ``%fusion.N = ... kind=kCustom,
calls=%all-reduce-scatter...``), or the Pallas DMA exchange kernel, which
holds the core while its DMAs run (matched by its signature,
``chipbench.readers.EXCHANGE``; a Pallas kernel that computes is not
counted).  An event's text is its HLO instruction as the trace prints
it.  Async ``-start`` ops only issue a collective and are not counted.
"""

import re

from chipbench import readers

_OPS = r"(all-gather|reduce-scatter|all-reduce-scatter|all-reduce|all-to-all" \
       r"|collective-permute)"
COMM = re.compile(
    rf"^%?{_OPS}(-done)?(\.\d+)? "
    rf"|kind=kCustom, calls=%{_OPS}"
    rf"|{readers.EXCHANGE.pattern}"
)


def read(run):
    return readers.busy_share(run, COMM)

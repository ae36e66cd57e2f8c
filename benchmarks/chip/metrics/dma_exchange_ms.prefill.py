"""dma_exchange_ms.prefill: device ms per prefill call in the Pallas DMA
exchange kernel (``a2a_chunk_exchange``), averaged over the devices.

The kernel's event is an HLO custom-call, ``%shard_map.N = bf16[4,1024,
4096] custom-call(bf16[1024,4096] ...), custom_call_target=
"tpu_custom_call"``: eight a layer (four chunks for each of the up and
gate projections).  It is matched by its signature
(``chipbench.readers.EXCHANGE``), so no other Pallas kernel is counted.
"""

from chipbench import readers

KERNEL = readers.EXCHANGE


def read(run):
    return readers.ms_per_step(run, KERNEL)

"""up_gate_ms.prefill: device ms per prefill call in the MLP's up and gate
projections, averaged over the devices.

That is the FiCCO AG->GEMM: every op under the program's ``mlp/up_gate``
scope (``repro.models.layers.mlp_apply``), which holds the DMA exchange
(``ficco/exchange``), the chunk GEMMs (``ficco/gemm``), the assembly of
their rows and the gated activation.  Each op's scope is found by
joining its event to the compiled program (``chipbench.scopes``).
"""

from chipbench import scopes

SCOPE = "mlp/up_gate"


def read(run):
    return scopes.ms_per_step(run, SCOPE)

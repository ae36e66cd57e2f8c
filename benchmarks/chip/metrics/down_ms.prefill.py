"""down_ms.prefill: device ms per prefill call in the MLP's down
projection, averaged over the devices: the ops under the program's
``mlp/down`` scope, the GEMM and the reduce-scatter of its output
(``chipbench.scopes``)."""

from chipbench import scopes

SCOPE = "mlp/down"


def read(run):
    return scopes.ms_per_step(run, SCOPE)

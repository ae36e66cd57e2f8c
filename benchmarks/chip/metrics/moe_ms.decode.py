"""moe_ms.decode: device ms per decode step in the expert layers, averaged
over the devices.

The ops under the program's ``moe`` scope: the router (``moe/router``),
the sort and gather of the held experts' assignments
(``moe/dispatch``), the weighted scatter-add (``moe/combine``) and the
shared experts (``moe/shared``); and the held experts' own work, which
the compiler leaves outside the scope: the grouped GEMMs
(``moe/experts``), which the TPU compiler turns into ``ragged-dot``
custom calls that carry no scope, and the layer scan's slices of the
held experts' weights out of the stack (experts x hidden x expert
width, or its transpose) (``chipbench.scopes``).
"""

from chipbench import scopes

SCOPE = "moe"


def read(run):
    c = scopes.cell()
    if c is None:
        return None
    m = c["config"]
    held, d, ff = (m["n_routed_experts"], m["hidden_size"],
                   m["moe_intermediate_size"])
    weights = {f"{held},{d},{ff}", f"{held},{ff},{d}"}

    def expert_work(chain, sig):
        return (sig.startswith("%ragged-dot")
                or scopes.result_dims(sig) in weights)

    return scopes.ms_per_step(run, SCOPE, extra=expert_work)

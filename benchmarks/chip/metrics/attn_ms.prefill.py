"""attn_ms.prefill: device ms per prefill call in attention, averaged
over the devices: the ops under the program's ``attn`` scope, from the
all-gather before the q/k/v projections (``attn/qkv``, with RoPE) through
the blockwise attention (``attn/core``) to the o projection and its
reduce-scatter (``attn/o``) (``chipbench.scopes``)."""

from chipbench import scopes

SCOPE = "attn"


def read(run):
    return scopes.ms_per_step(run, SCOPE)

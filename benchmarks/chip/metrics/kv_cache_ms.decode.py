"""kv_cache_ms.decode: device ms per decode step spent moving the KV
cache, averaged over the devices.

The ops under the program's ``kv_cache`` scope (the write of the step's
keys and values, ``repro.models.layers.attn_decode``), and the ops in
the ``layers`` scan whose result is a layer's cache or the stack of them
(batch x cache length x KV heads x head size last): the scan's slice of
each layer's cache out of the stack, its relayout, a write that the
compiler fused into another scope's op, and the write of the layer's
cache back (``chipbench.scopes``).  The scan's slices and relayouts of
the layer's weights lie in the same scope and are left out by their
shape.
"""

from chipbench import scopes

SCOPE = "kv_cache"
LAYERS = "layers"


def read(run):
    c = scopes.cell()
    if c is None:
        return None
    mix, m = c["traffic"], c["config"]["model"]
    cache = (f'{mix["batch"]},{mix["cache_len"]},{m["num_kv_heads"]},'
             f'{m["head_dim"]}')

    def moves_cache(chain, sig):
        dims = scopes.result_dims(sig)
        return LAYERS in chain and (dims == cache
                                    or dims.endswith("," + cache))

    return scopes.ms_per_step(run, SCOPE, extra=moves_cache)

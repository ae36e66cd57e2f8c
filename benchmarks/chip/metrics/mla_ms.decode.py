"""mla_ms.decode: device ms per decode step in latent attention, averaged
over the devices.

The ops under the program's ``mla`` scope, from the q projection
(``mla/q``) and the latent's down-projection, norm and rope key
(``mla/latent``) through the cache writes (``mla/cache``), the
up-projection of the whole latent cache (``mla/expand``) and attention
(``mla/core``) to the o projection (``mla/o``); and the moves of the
latent cache that the compiler puts outside any scope: a copy or
relayout whose result is a layer's latent or rope-key cache (batch x
cache length x latent, or x 1 x rope dim) or the stack of them
(``chipbench.scopes``).
"""

from chipbench import scopes

SCOPE = "mla"


def read(run):
    c = scopes.cell()
    if c is None:
        return None
    mix, m = c["traffic"], c["config"]
    caches = (f'{mix["batch"]},{mix["cache_len"]},{m["kv_lora_rank"]}',
              f'{mix["batch"]},{mix["cache_len"]},1,'
              f'{m["qk_rope_head_dim"]}')

    def moves_cache(chain, sig):
        dims = scopes.result_dims(sig)
        return any(dims == d or dims.endswith("," + d) for d in caches)

    return scopes.ms_per_step(run, SCOPE, extra=moves_cache)

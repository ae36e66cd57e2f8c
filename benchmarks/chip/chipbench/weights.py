"""Weights made from ``--seed``, in the benchmark's own layout.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed_key, leaf),
layer)``, so one layer's weights can be made alone (the reference does,
layer by layer) or all layers at once in one jitted call on the device
(the program's parameters), with the same values either way.  Weights are
drawn in float32 and served in the configuration's dtype; the reference
upcasts those served values.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.model import Dims

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down")
_LEAF_ID = {name: i for i, name in enumerate(
    ("embed", "unembed") + LAYER_LEAVES, start=1
)}
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key for any whole ``seed``: the high word is folded in, so seeds
    that differ above 32 bits give different weights."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def layer_shapes(m: Dims) -> dict[str, tuple[int, int]]:
    qd, kvd = m.num_heads * m.head_dim, m.num_kv_heads * m.head_dim
    return {
        "wq": (m.d_model, qd), "wk": (m.d_model, kvd),
        "wv": (m.d_model, kvd), "wo": (qd, m.d_model),
        "w_up": (m.d_model, m.d_ff), "w_gate": (m.d_model, m.d_ff),
        "w_down": (m.d_ff, m.d_model),
    }


def _draw(key, leaf: str, layer, shape, std: float, dtype) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_ID[leaf]), layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def layer_weights(m: Dims, key, layer) -> dict[str, jax.Array]:
    """One layer's matrices in the served dtype (norm scales are ones)."""
    dt = jnp.dtype(m.dtype)
    return {
        name: _draw(key, name, layer, shape, 1.0 / math.sqrt(shape[0]), dt)
        for name, shape in layer_shapes(m).items()
    }


def embed(m: Dims, key) -> jax.Array:
    return _draw(key, "embed", 0, (m.vocab_size, m.d_model), EMBED_STD,
                 jnp.dtype(m.dtype))


def unembed(m: Dims, key) -> jax.Array:
    """The output projection, (d_model, vocab): the embedding's transpose
    when they are tied."""
    if m.tie_embeddings:
        return embed(m, key).T
    return _draw(key, "unembed", 0, (m.d_model, m.vocab_size), EMBED_STD,
                 jnp.dtype(m.dtype))


def _norm(m: Dims) -> dict:
    if m.norm == "rmsnorm":
        return {"scale": jnp.ones((m.d_model,), jnp.dtype(m.dtype))}
    if m.norm == "nonparametric_ln":
        return {}
    raise ValueError(f"norm {m.norm!r}")


def program_params(m: Dims, key: jax.Array) -> dict:
    """The program's parameter tree (layers stacked on a leading axis,
    one layer per period), made from ``seed_key(seed)``.  Jit it with
    ``m`` static, and the program's shardings as ``out_shardings`` where
    it is sharded."""
    stack = jax.vmap(lambda l: layer_weights(m, key, l))(
        jnp.arange(m.num_layers)
    )
    norms = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (m.num_layers, *a.shape)), _norm(m)
    )
    layer = {
        "norm1": norms,
        "attn": {k: stack[k] for k in ("wq", "wk", "wv", "wo")},
        "norm2": norms,
        "ffn": {k: stack[k] for k in ("w_up", "w_gate", "w_down")},
    }
    params = {"embed": embed(m, key), "final_norm": _norm(m),
              "layers": [layer]}
    if not m.tie_embeddings:
        params["unembed"] = unembed(m, key)
    return params


def leaf_groups(m: Dims) -> list[str]:
    """Names of the program's leaves, in the benchmark's terms, for
    comparisons made leaf by leaf."""
    out = ["embed"] + list(LAYER_LEAVES)
    if not m.tie_embeddings:
        out.append("unembed")
    return out


def program_leaf(params: dict, name: str) -> jax.Array:
    """The program tree's leaf for a :func:`leaf_groups` name."""
    if name in ("embed", "unembed"):
        return params[name]
    group = "attn" if name in ("wq", "wk", "wv", "wo") else "ffn"
    return params["layers"][0][group][name]

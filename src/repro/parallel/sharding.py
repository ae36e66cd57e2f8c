"""Mesh-aware sharding helpers.

Logical axes used throughout the model code:
  * batch dims  -> ("pod", "data")   (pure data parallel across pods)
  * model dims  -> "model"           (TP / EP / head / expert sharding)
  * sequence    -> "data" for the context-parallel long-decode cache

``constrain`` degrades to a no-op when no mesh is active (single-device
smoke tests) and silently drops axis names the active mesh does not have
(so the same model code runs on (data, model), (pod, data, model) and
single-device meshes).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"


def _active_mesh():
    """The mesh installed by ``jax.sharding.set_mesh``, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _filter_spec(spec: P, axis_names) -> P:
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in axis_names else None
        sub = tuple(a for a in entry if a in axis_names)
        return sub if sub else None

    return P(*(keep(e) for e in spec))


def constrain(x: jax.Array, *spec_entries) -> jax.Array:
    """with_sharding_constraint against the ambient mesh (or no-op).

    Entries are dropped when the mesh lacks the axis OR the dimension is
    not divisible by the axis size (e.g. kv=4 heads on a 16-way model
    axis) — uneven shardings trigger involuntary full rematerialization
    in the SPMD partitioner.
    """
    mesh = _active_mesh()
    if mesh is None:
        return x
    names = set()
    for n in mesh.shape:
        names.add(n)
    spec = _filter_spec(P(*spec_entries), names)
    entries = list(spec) + [None] * (x.ndim - len(spec))
    for i, e in enumerate(entries):
        if e is None:
            continue
        size = _axis_size(mesh, e)
        if size <= 1 or x.shape[i] % size:
            entries[i] = None
    return jax.lax.with_sharding_constraint(x, P(*entries))


def batch_spec(*rest) -> tuple:
    """Spec entries for a (batch, ...) activation."""
    return (BATCH_AXES, *rest)


def filter_pspec(spec: P, mesh) -> P:
    """Public helper: drop axes absent from ``mesh`` from a PartitionSpec."""
    return _filter_spec(spec, set(mesh.shape))


# ---------------------------------------------------------------------------
# Launch-time spec fix-up: divisibility + FSDP augmentation
# ---------------------------------------------------------------------------

def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        return mesh.shape.get(entry, 1)
    n = 1
    for a in entry:
        n *= mesh.shape.get(a, 1)
    return n


def fix_param_spec(spec: P, shape, mesh, *, fsdp_axis: str = "data") -> P:
    """Make a parameter spec legal + memory-efficient on ``mesh``:

      1. drop axes the mesh doesn't have,
      2. drop entries whose dimension is not divisible by the axis size
         (e.g. seamless's 256206 vocab over a 16-way axis),
      3. FSDP: if the ``data`` axis is unused and the leaf is a real weight
         (>= 2 dims, >= 2^16 elements), shard its largest divisible,
         not-yet-sharded dimension over ``data`` — this is what keeps
         400B-class models' parameters + Adam moments within HBM at 256
         chips (ZeRO-3-style 2D weight sharding).
    """
    import math

    names = set(mesh.shape)
    spec = _filter_spec(spec, names)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used: set[str] = set()
    for i, e in enumerate(entries):
        if e is None:
            continue
        size = _axis_size(mesh, e)
        if shape[i] % size:
            entries[i] = None
            continue
        used.update([e] if isinstance(e, str) else list(e))
    n_elems = math.prod(shape) if shape else 1
    if (
        fsdp_axis in names
        and fsdp_axis not in used
        and len(shape) >= 2
        and n_elems >= 1 << 16
    ):
        ax = mesh.shape[fsdp_axis]
        candidates = [
            i
            for i in range(len(shape))
            if entries[i] is None and shape[i] % ax == 0 and shape[i] >= ax
        ]
        if candidates:
            best = max(candidates, key=lambda i: shape[i])
            entries[best] = fsdp_axis
    return P(*entries)


def fix_param_specs(specs, shapes, mesh) -> "object":
    """Tree version of fix_param_spec (specs/shapes share structure)."""
    return jax.tree.map(
        lambda sp, sh: fix_param_spec(sp, sh.shape, mesh),
        specs,
        shapes,
        is_leaf=lambda x: isinstance(x, P),
    )


def cache_leaf_spec(shape, mesh) -> P:
    """Decode-cache sharding rule.

    Layout (periods, B, ...): batch over (pod, data) when divisible; the
    largest remaining dimension >= 1024 divisible by the model axis is
    sharded over 'model' (the 32k KV time axis, or Mamba's d_inner);
    when batch is unsharded (long_500k B=1) the 'data' axis joins the
    sequence dimension — context-parallel cache reads.
    """
    names = set(mesh.shape)
    rank = len(shape)
    entries: list = [None] * rank
    dp = 1
    batch_axes = tuple(a for a in BATCH_AXES if a in names)
    for a in batch_axes:
        dp *= mesh.shape[a]
    batch_sharded = False
    if rank >= 2 and dp > 1 and shape[1] % dp == 0 and shape[1] >= dp:
        entries[1] = batch_axes if len(batch_axes) > 1 else batch_axes[0]
        batch_sharded = True
    model = mesh.shape.get(MODEL_AXIS, 1)
    rest = sorted(
        range(2, rank), key=lambda i: shape[i], reverse=True
    )
    model_used = False
    for i in rest:
        if (
            not model_used
            and model > 1
            and shape[i] >= 1024
            and shape[i] % model == 0
        ):
            if not batch_sharded and dp > 1 and shape[i] % (model * dp) == 0:
                entries[i] = (*batch_axes, MODEL_AXIS)
            else:
                entries[i] = MODEL_AXIS
            model_used = True
            break
    return P(*entries)


def cache_specs(cache_shapes, mesh):
    return jax.tree.map(
        lambda l: cache_leaf_spec(l.shape, mesh), cache_shapes
    )

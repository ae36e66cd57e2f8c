"""Compile the main-path Pallas kernels, and the decode step, for a TPU
v5e that is described, not attached.

Interpret mode accepts what the chip's compiler refuses (unaligned
slices, scoped VMEM overruns), so the kernels are compiled here at the
widths TinyLlama-1.1B uses at tensor parallelism 4: K = d_model = 2048,
n_local = d_ff / 4 = 1408, shard rows m_s = tokens / 4.  Nothing runs;
the compiler only accepts or refuses.  The decode step is compiled at
published widths to read what the chip's compiler makes of its cache.
"""

import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.machine import TPU_V5E
from repro.core.workload import GemmShape
from repro.kernels.chunked_gemm import chunked_matmul
from repro.kernels.dma_exchange import ficco_uniform_fused_1d_dma
from repro.kernels.ficco_ag_matmul import ficco_ag_matmul_fused
from repro.models.model import build_model
from repro.serve.engine import SERVE_STEP_DONATE, make_serve_step
from repro.tune import default_variant
from repro.tune.prune import check_variant

G = 4
K, N_LOCAL = 2048, 1408
D_FF = 5632


def _describe(topology_name: str):
    """The devices of a v5e slice described by libtpu; no chip needed.
    Skips only where libtpu is not installed: any other failure fails."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed; no TPU can be described")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology_name
    )


def _tp_mesh(devices):
    return Mesh(
        np.array(devices).reshape(len(devices)), ("tp",),
        axis_types=(AxisType.Auto,),
    )


@pytest.fixture(scope="module")
def topo():
    return _describe("v5e:2x2")


@pytest.fixture(scope="module")
def mesh(topo):
    return _tp_mesh(topo.devices)


@pytest.fixture(scope="module")
def mesh8():
    return _tp_mesh(_describe("v5e:2x4").devices)


def _compile_ag(kernel, mesh, m_s, k, n_local, variant=None):
    g = mesh.shape["tp"]
    x = jax.ShapeDtypeStruct(
        (g * m_s, k), jnp.bfloat16, sharding=NamedSharding(mesh, P("tp"))
    )
    w = jax.ShapeDtypeStruct(
        (k, g * n_local), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, "tp")),
    )
    fn = jax.jit(jax.shard_map(
        lambda a, b: kernel(a, b, axis_name="tp", variant=variant),
        mesh=mesh,
        in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"),
        check_vma=False,
    ))
    return fn.lower(x, w).compile()


def test_chunked_matmul_compiles_at_mlp_width(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2048, 5632), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(chunked_matmul).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m_s", [256, 1024])
@pytest.mark.parametrize(
    "kernel", [ficco_uniform_fused_1d_dma, ficco_ag_matmul_fused],
    ids=["dma_exchange", "ficco_ag_matmul"],
)
def test_dma_kernels_compile_on_four_chips(mesh, kernel, m_s):
    compiled = _compile_ag(kernel, mesh, m_s, K, N_LOCAL)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "kernel", [ficco_uniform_fused_1d_dma, ficco_ag_matmul_fused],
    ids=["dma_exchange", "ficco_ag_matmul"],
)
def test_dma_kernels_compile_on_eight_chips(mesh8, kernel):
    """TP=8 on a v5e 2x4 host: n_local = d_ff / 8 = 704 is not a whole
    number of 128-lane tiles, so the step GEMM takes it in one piece."""
    compiled = _compile_ag(kernel, mesh8, 512, K, D_FF // 8)
    assert "tpu_custom_call" in compiled.as_text()


def test_prune_refuses_what_the_compiler_refuses_for_vmem(mesh):
    """A (K, n_local) weight panel of 64 MiB leaves no room in the fused
    kernel's scoped VMEM: the pruner and the compiler both refuse it,
    while both accept the prefill shape above."""
    variant = default_variant("ficco_ag_matmul", TPU_V5E, group=G)
    m_s, k, n_local = 64, 8192, 4096
    reason = check_variant(
        variant, GemmShape(G * m_s, G * n_local, k, 2), TPU_V5E, group=G
    )
    assert reason is not None and reason.startswith("vmem"), reason
    with pytest.raises(Exception, match="vmem"):
        _compile_ag(ficco_ag_matmul_fused, mesh, m_s, k, n_local, variant)
    fits = GemmShape(G * 1024, G * N_LOCAL, K, 2)
    assert check_variant(variant, fits, TPU_V5E, group=G) is None


# one instruction of a compiled module: its name, result dims and opcode
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = \w+\[(?P<dims>[\d,]*)\]\S* "
    r"(?P<op>[\w\-]+)\(", re.M)


@pytest.mark.parametrize("arch", ["olmo-1b", "yi-9b"])
def test_decode_step_keeps_its_cache_in_place(topo, arch):
    """The serving step at published widths (two layers, a batch of 4
    over 2,048 slots), one query head per KV head and grouped: the
    donated cache is the step's output buffer, and no instruction copies
    the cache or a layer of it (a relayout, a slice or a write-back),
    as the step before the cache was written in place did."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    model = build_model(dataclasses.replace(get_config(arch), num_layers=2))
    cache = jax.eval_shape(lambda: model.init_cache(4, 2048))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    compiled = jax.jit(
        make_serve_step(model), donate_argnums=SERVE_STEP_DONATE
    ).lower(
        on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0))),
        on_chip(cache),
        jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    leaves = jax.tree.leaves(cache)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == cache_bytes
    # no temporary holds as much as one layer's keys
    assert memory.temp_size_in_bytes < cache_bytes / len(leaves) / 2
    shapes = {",".join(map(str, a.shape[i:]))
              for a in jax.tree.leaves(cache) for i in (0, 1)}
    copies = [m.group(0) for m in _INSTRUCTION.finditer(compiled.as_text())
              if m.group("dims") in shapes
              and "copy" in (m.group("op"), m.group("name")[:4])]
    assert not copies, copies

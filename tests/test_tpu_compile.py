"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached.

Interpret mode accepts what the chip's compiler refuses (unaligned
slices, scoped VMEM overruns), so the kernels are compiled here at the
widths TinyLlama-1.1B uses at tensor parallelism 4: K = d_model = 2048,
n_local = d_ff / 4 = 1408, shard rows m_s = tokens / 4.  Nothing runs;
the compiler only accepts or refuses.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.machine import TPU_V5E
from repro.core.workload import GemmShape
from repro.kernels.chunked_gemm import chunked_matmul
from repro.kernels.dma_exchange import ficco_uniform_fused_1d_dma
from repro.kernels.ficco_ag_matmul import ficco_ag_matmul_fused
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

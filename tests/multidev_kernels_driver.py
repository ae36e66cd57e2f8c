"""Remote-DMA Pallas kernel checks on 8 simulated devices (subprocess).

Validates the TPU DMA-offload kernels against lax-collective oracles using
the Mosaic TPU interpreter, which simulates cross-device DMAs + semaphores.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import dataclasses  # noqa: E402
import functools  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.dma_exchange import (  # noqa: E402
    a2a_chunk_exchange,
    ficco_uniform_fused_1d_dma,
)
from repro.kernels.ficco_ag_matmul import ficco_ag_matmul_fused  # noqa: E402
from repro.overlap.moe import ficco_a2a_ffn, serial_a2a_ffn  # noqa: E402
from repro.tune import default_variant  # noqa: E402

G = 8
# The fused kernel's shapes on 8 devices stay at 16 shard rows: with more,
# the Mosaic interpreter stalls before the kernel body runs.  Devices that
# entered the kernel block in semaphore waits inside their callbacks, and
# the others never finish copying a VMEM scratch's initial value to the
# host.  A kernel that only handshakes and remote-copies stalls the same
# way on 8 devices once it allocates a 256 x 128 f32 VMEM scratch, and
# completes with 16 rows, or on 4 devices with 1024.  The larger shapes
# run on a 4-device sub-mesh.
G_FUSED = 4
AXIS = "tp"
failures = []


def check(name, fn):
    try:
        fn()
        print(f"ok {name}")
    except Exception:
        failures.append(name)
        print(f"FAIL {name}")
        traceback.print_exc()


def mesh(g=G):
    return make_mesh((g,), (AXIS,), devices=jax.devices()[:g])


def exchange_matches_all_gather():
    m = mesh()
    rng = np.random.default_rng(0)
    for shape, dtype in [((8, 128), jnp.float32), ((16, 256), jnp.bfloat16)]:
        x = jnp.asarray(rng.standard_normal((G * shape[0], shape[1])), dtype)

        def body(xs):
            got = a2a_chunk_exchange(
                xs, axis_name=AXIS, group=G, interpret=True
            )
            want = ref.a2a_chunk_exchange_ref(xs, axis_name=AXIS)
            return got, want

        got, want = jax.jit(
            jax.shard_map(
                body, mesh=m,
                in_specs=P(AXIS, None),
                out_specs=(P(AXIS, None, None), P(AXIS, None, None)),
                check_vma=False,
            )
        )(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def dma_schedule_matches_serial():
    m = mesh()
    rng = np.random.default_rng(1)
    ms, k, n_local = 64, 128, 128  # per-device shard
    x = jnp.asarray(rng.standard_normal((G * ms, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, G * n_local)), jnp.float32)

    def body(xs, ws):
        got = ficco_uniform_fused_1d_dma(
            xs, ws, axis_name=AXIS, interpret=True
        )
        want = ref.ag_matmul_ref(xs, ws, axis_name=AXIS)
        return got, want

    got, want = jax.jit(
        jax.shard_map(
            body, mesh=m,
            in_specs=(P(AXIS, None), P(None, AXIS)),
            out_specs=(P(None, AXIS), P(None, AXIS)),
            check_vma=False,
        )
    )(x, w)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def fused_kernel_matches_serial():
    rng = np.random.default_rng(2)
    for g, ms, k, n_local, dtype in [
        (G_FUSED, 64, 128, 128, jnp.float32),
        (G_FUSED, 32, 256, 128, jnp.bfloat16),
        (G, 16, 128, 128, jnp.float32),
        (G, 16, 128, 128, jnp.bfloat16),
    ]:
        m = mesh(g)
        x = jnp.asarray(rng.standard_normal((g * ms, k)), dtype)
        w = jnp.asarray(rng.standard_normal((k, g * n_local)), dtype)

        def body(xs, ws):
            got = ficco_ag_matmul_fused(
                xs, ws, axis_name=AXIS, interpret=True
            )
            want = ref.ag_matmul_ref(xs, ws, axis_name=AXIS)
            return got, want

        got, want = jax.jit(
            jax.shard_map(
                body, mesh=m,
                in_specs=(P(AXIS, None), P(None, AXIS)),
                out_specs=(P(None, AXIS), P(None, AXIS)),
                check_vma=False,
            )
        )(x, w)
        tol = (
            dict(rtol=2e-2, atol=2e-2)
            if dtype == jnp.bfloat16
            else dict(rtol=1e-5, atol=1e-5)
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
        )


def ag_fused_variants_bit_identical():
    """Chunk-count / buffer-depth / dispatch-order variants of the fused
    AG kernel must be BIT-identical to the default: every output row is
    one full-K dot whichever slot/step order produced its operand."""
    for g, ms in ((G_FUSED, 64), (G, 16)):
        _ag_fused_variants_bit_identical(g, ms)


def _ag_fused_variants_bit_identical(g, ms):
    m = mesh(g)
    rng = np.random.default_rng(3)
    k, n_local = 128, 128
    x = jnp.asarray(rng.standard_normal((g * ms, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, g * n_local)), jnp.float32)
    base = default_variant("ficco_ag_matmul", group=g)
    variants = [
        base,
        dataclasses.replace(base, chunks=2 * g),
        dataclasses.replace(base, buffer_depth=3),
        dataclasses.replace(base, chunks=2 * g, buffer_depth=3),
        dataclasses.replace(base, dispatch_order="reverse"),
    ]

    def run(v):
        def body(xs, ws):
            return ficco_ag_matmul_fused(
                xs, ws, axis_name=AXIS, interpret=True, variant=v
            )

        return np.asarray(
            jax.jit(
                jax.shard_map(
                    body, mesh=m,
                    in_specs=(P(AXIS, None), P(None, AXIS)),
                    out_specs=P(None, AXIS),
                    check_vma=False,
                )
            )(x, w)
        )

    want = run(variants[0])
    for v in variants[1:]:
        np.testing.assert_array_equal(run(v), want, err_msg=v.digest())


def dma_schedule_variants_match():
    """dma_exchange variants: chunk/order cuts are bit-identical (same
    full-K row dots, different step batching); a blocked step-GEMM tile
    keeps the full-K contraction so it matches to float tolerance."""
    m = mesh()
    rng = np.random.default_rng(4)
    ms, k, n_local = 64, 128, 128
    x = jnp.asarray(rng.standard_normal((G * ms, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, G * n_local)), jnp.float32)
    base = default_variant("dma_exchange", group=G)

    def run(v):
        def body(xs, ws):
            return ficco_uniform_fused_1d_dma(
                xs, ws, axis_name=AXIS, interpret=True, variant=v
            )

        return np.asarray(
            jax.jit(
                jax.shard_map(
                    body, mesh=m,
                    in_specs=(P(AXIS, None), P(None, AXIS)),
                    out_specs=(P(None, AXIS)),
                    check_vma=False,
                )
            )(x, w)
        )

    want = run(base)
    for v in (
        dataclasses.replace(base, chunks=4),
        dataclasses.replace(base, dispatch_order="reverse"),
    ):
        np.testing.assert_array_equal(run(v), want, err_msg=v.digest())
    tiled = dataclasses.replace(base, block_m=64, block_n=64)
    np.testing.assert_allclose(
        run(tiled), want, rtol=1e-6, atol=1e-6, err_msg=tiled.digest()
    )


def a2a_ffn_variants_bit_identical():
    """MoE dispatch variants (chunk count, dispatch order) reassemble
    outputs in capacity order, so results are bit-identical to the
    serial all-to-all baseline's chunking-free layout."""
    m = mesh()
    rng = np.random.default_rng(5)
    e, c, d, f = 16, 16, 32, 64  # 16 global experts over 8 devices
    x = jnp.asarray(rng.standard_normal((G * e, c, d)), jnp.float32)
    w_up = jnp.asarray(
        rng.standard_normal((e, d, f)) / np.sqrt(d), jnp.float32
    )
    w_down = jnp.asarray(
        rng.standard_normal((e, f, d)) / np.sqrt(f), jnp.float32
    )
    base = default_variant("ficco_a2a_ffn", group=G)

    def run(v):
        def body(xs, wu, wd):
            return ficco_a2a_ffn(xs, wu, wd, axis_name=AXIS, variant=v)

        return np.asarray(
            jax.jit(
                jax.shard_map(
                    body, mesh=m,
                    in_specs=(P(AXIS, None, None), P(AXIS, None, None),
                              P(AXIS, None, None)),
                    out_specs=P(AXIS, None, None),
                    check_vma=False,
                )
            )(x, w_up, w_down)
        )

    want = run(base)
    for v in (
        dataclasses.replace(base, chunks=4),
        dataclasses.replace(base, dispatch_order="reverse"),
        dataclasses.replace(base, chunks=4, dispatch_order="reverse"),
    ):
        np.testing.assert_array_equal(run(v), want, err_msg=v.digest())

    # and the chunked pipeline agrees with the one-shot serial baseline
    def serial_body(xs, wu, wd):
        return serial_a2a_ffn(xs, wu, wd, axis_name=AXIS)

    serial = np.asarray(
        jax.jit(
            jax.shard_map(
                serial_body, mesh=m,
                in_specs=(P(AXIS, None, None), P(AXIS, None, None),
                          P(AXIS, None, None)),
                out_specs=P(AXIS, None, None),
                check_vma=False,
            )
        )(x, w_up, w_down)
    )
    np.testing.assert_allclose(want, serial, rtol=1e-5, atol=1e-5)


def main():
    assert len(jax.devices()) == G
    check("exchange_matches_all_gather", exchange_matches_all_gather)
    check("dma_schedule_matches_serial", dma_schedule_matches_serial)
    check("fused_kernel_matches_serial", fused_kernel_matches_serial)
    check("ag_fused_variants_bit_identical", ag_fused_variants_bit_identical)
    check("dma_schedule_variants_match", dma_schedule_variants_match)
    check("a2a_ffn_variants_bit_identical", a2a_ffn_variants_bit_identical)
    if failures:
        print("FAILED:", failures)
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()

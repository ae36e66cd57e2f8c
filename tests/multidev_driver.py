"""Multi-device correctness driver (run in a subprocess with 8 host devices).

The main pytest process must keep seeing ONE device (smoke tests / benches),
so everything that needs a real mesh runs here, spawned by
``tests/test_overlap_multidev.py``.  Prints one line per check and a final
``ALL-OK`` sentinel on success.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import functools  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core.schedule_types import Schedule  # noqa: E402
from repro.overlap import (  # noqa: E402
    ficco_a2a_ffn,
    ficco_linear,
    run_schedule,
    serial_a2a_ffn,
)

G = 8
AXIS = "tp"

failures: list[str] = []


def check(name: str, fn):
    try:
        fn()
        print(f"ok {name}")
    except Exception:
        failures.append(name)
        print(f"FAIL {name}")
        traceback.print_exc()


def tp_mesh():
    return make_mesh((G,), (AXIS,))


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5
    )


def run_sharded(fn, mesh, x, w):
    wrapped = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(AXIS, None), P(None, AXIS)),
            out_specs=P(None, AXIS),
            check_vma=False,
        )
    )
    return wrapped(x, w)


def schedules_allclose():
    mesh = tp_mesh()
    rng = np.random.default_rng(0)
    for m, n, k in [(128, 64, 64), (256, 128, 128), (512, 256, 64)]:
        for dtype in (jnp.float32, jnp.bfloat16):
            x = jnp.asarray(
                rng.standard_normal((m, k)), dtype=dtype
            )
            w = jnp.asarray(rng.standard_normal((k, n)), dtype=dtype)
            ref = np.asarray(
                (x.astype(jnp.float32) @ w.astype(jnp.float32))
            )
            for sched in Schedule:
                if sched is Schedule.UNIFORM_FUSED_2D and k % G:
                    continue
                fn = functools.partial(
                    run_schedule, sched, axis_name=AXIS
                )
                got = np.asarray(
                    run_sharded(fn, mesh, x, w)
                ).astype(np.float32)
                np.testing.assert_allclose(
                    got,
                    ref,
                    err_msg=f"{sched} {m}x{n}x{k} {dtype}",
                    **tol(dtype),
                )


def ficco_linear_auto():
    mesh = tp_mesh()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    ref = np.asarray(x @ w)
    for schedule in ("auto", "serial", "uniform-fused-1d", "hetero-fused-1d"):
        fn = functools.partial(
            ficco_linear, axis_name=AXIS, schedule=schedule
        )
        got = np.asarray(run_sharded(fn, mesh, x, w))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def ficco_linear_indivisible_falls_back():
    """M/g not divisible by g again -> serial fallback, still correct."""
    mesh = tp_mesh()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((8 * 9, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    ref = np.asarray(x @ w)
    fn = functools.partial(
        ficco_linear, axis_name=AXIS, schedule="uniform-fused-1d"
    )
    got = np.asarray(run_sharded(fn, mesh, x, w))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def moe_dispatch_equivalence():
    mesh = tp_mesh()
    rng = np.random.default_rng(3)
    e, c, d, f = 16, 32, 64, 128  # 16 experts over 8 devices
    e_local = e // G
    x = jnp.asarray(rng.standard_normal((G * e, c, d)), jnp.float32)
    w_up = jnp.asarray(
        rng.standard_normal((e, d, f)) / np.sqrt(d), jnp.float32
    )
    w_down = jnp.asarray(
        rng.standard_normal((e, f, d)) / np.sqrt(f), jnp.float32
    )

    def run(fn):
        wrapped = jax.jit(
            jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=(P(AXIS, None, None), P(AXIS, None, None),
                          P(AXIS, None, None)),
                out_specs=P(AXIS, None, None),
                check_vma=False,
            )
        )
        return np.asarray(wrapped(x, w_up, w_down))

    serial = run(functools.partial(serial_a2a_ffn, axis_name=AXIS))
    ficco = run(functools.partial(ficco_a2a_ffn, axis_name=AXIS))
    np.testing.assert_allclose(ficco, serial, rtol=1e-5, atol=1e-5)
    ficco2 = run(
        functools.partial(ficco_a2a_ffn, axis_name=AXIS, chunks=4)
    )
    np.testing.assert_allclose(ficco2, serial, rtol=1e-5, atol=1e-5)


def hlo_uses_async_collectives():
    """The FiCCO schedules must lower to one chunk collective per step so
    XLA's scheduler can pipeline them (the DMA-offload story)."""
    mesh = tp_mesh()
    x = jnp.zeros((256, 128), jnp.float32)
    w = jnp.zeros((128, 128), jnp.float32)
    fn = functools.partial(
        run_schedule, Schedule.UNIFORM_FUSED_1D, axis_name=AXIS
    )
    wrapped = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(AXIS, None), P(None, AXIS)),
            out_specs=P(None, AXIS),
            check_vma=False,
        )
    )
    txt = wrapped.lower(x, w).compile().as_text()
    n_ag = txt.count("all-gather-start") or txt.count("all-gather(")
    assert n_ag >= G, f"expected >= {G} chunk all-gathers, found {n_ag}"


def ficco_in_model_matches_gspmd():
    """A reduced dense model under mesh: overlap ficco_auto forward must
    equal the gspmd_serial forward (the production integration path)."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import OverlapConfig
    from repro.models.model import build_model
    from repro.parallel.context import overlap_context

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("tinyllama-1.1b").reduced()
    cfg = dataclasses.replace(
        cfg, num_heads=4, num_kv_heads=4, d_ff=512, d_model=256
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)),
        jnp.int32,
    )

    def fwd(params, toks):
        logits, _ = model.forward(params, {"tokens": toks})
        return logits

    with jax.sharding.set_mesh(mesh):
        base = np.asarray(jax.jit(fwd)(params, toks), np.float32)
        ov = OverlapConfig(mode="ficco_auto")

        def fwd_ficco(params, toks):
            with overlap_context(ov):
                logits, _ = model.forward(params, {"tokens": toks})
            return logits

        got = np.asarray(jax.jit(fwd_ficco)(params, toks), np.float32)
        ov2 = OverlapConfig(mode="uniform-fused-1d")

        def fwd_uf(params, toks):
            with overlap_context(ov2):
                logits, _ = model.forward(params, {"tokens": toks})
            return logits

        got2 = np.asarray(jax.jit(fwd_uf)(params, toks), np.float32)
    np.testing.assert_allclose(got, base, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(got2, base, rtol=2e-3, atol=2e-3)


def shard_map_decode_attn_matches_reference():
    """Explicit flash-decode == cache_attention reference."""
    from repro.parallel import decode_attn
    from repro.models.layers import cache_attention

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(7)
    b, s, h, kv, d = 4, 4096, 8, 4, 32
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((b, 1, kv, d)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((b, 1, kv, d)), jnp.float32)
    k_c = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v_c = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    pos = jnp.int32(2500)

    with jax.sharding.set_mesh(mesh):
        out, k2, v2 = jax.jit(decode_attn.shard_map_attn_decode)(
            q, k_new, v_new, k_c, v_c, pos
        )
    # reference: dense update + cache_attention
    k_ref = k_c.at[:, 2500].set(k_new[:, 0])
    v_ref = v_c.at[:, 2500].set(v_new[:, 0])
    want = cache_attention(q, k_ref, v_ref, valid_len=pos + 1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(np.asarray(k2), np.asarray(k_ref))
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v_ref))


def pallas_dma_backend_in_model():
    """overlap.backend=pallas_dma routes the TP MLP up-projections through
    the Pallas ICI-DMA kernel (interpret mode) — must match gspmd."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import OverlapConfig
    from repro.models.model import build_model
    from repro.obs import metrics
    from repro.parallel.context import overlap_context

    mesh = make_mesh((8,), ("model",))
    cfg = get_config("tinyllama-1.1b").reduced()
    cfg = dataclasses.replace(
        cfg, num_layers=1, num_heads=4, num_kv_heads=4, d_ff=512,
        d_model=256,
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (1, 64)),
        jnp.int32,
    )

    def fwd(params, toks):
        logits, _ = model.forward(params, {"tokens": toks})
        return logits

    with jax.sharding.set_mesh(mesh):
        base = np.asarray(jax.jit(fwd)(params, toks), np.float32)
        ov = OverlapConfig(mode="uniform-fused-1d", backend="pallas_dma")

        def fwd_pallas(params, toks):
            with overlap_context(ov):
                logits, _ = model.forward(params, {"tokens": toks})
            return logits

        got = np.asarray(jax.jit(fwd_pallas)(params, toks), np.float32)
    np.testing.assert_allclose(got, base, rtol=2e-3, atol=2e-3)
    reg = metrics.get_metrics()
    assert reg.counter("tp/pallas_dma.dma").value > 0
    assert reg.counter("tp/pallas_dma.xla").value == 0


def main():
    assert len(jax.devices()) == G, jax.devices()
    check("schedules_allclose", schedules_allclose)
    check("ficco_in_model_matches_gspmd", ficco_in_model_matches_gspmd)
    check("pallas_dma_backend_in_model", pallas_dma_backend_in_model)
    check("shard_map_decode_attn_matches_reference",
          shard_map_decode_attn_matches_reference)
    check("ficco_linear_auto", ficco_linear_auto)
    check("ficco_linear_indivisible_falls_back",
          ficco_linear_indivisible_falls_back)
    check("moe_dispatch_equivalence", moe_dispatch_equivalence)
    check("hlo_uses_async_collectives", hlo_uses_async_collectives)
    if failures:
        print("FAILED:", failures)
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()

"""Drive the main path once on a TPU and check what comes out.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips, overlap path only

One chip: TinyLlama-1.1B at its published widths (22 layers, d_model 2048,
bfloat16, random weights from the seed) serves a few requests through
``DecodeEngine``; the Pallas GEMM runs at MLP width; the jitted
design-space engine evaluates the registry grid.  Four chips, on a
(data=1, model=4) mesh: TinyLlama prefill with FiCCO schedules on the
Pallas DMA kernels against GSPMD, and the DMA kernels against their
serial oracles at the same tensor-parallel widths.

Each phase prints one JSON line of what it found.  The last line of a run
that passes is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "tinyllama-1.1b"
SEED = 0
# bfloat16 tolerances, stated before the first chip run.  Logits: the
# largest |difference| over the largest |reference logit|.  Paths that
# differ only in summation order drift apart by about 2e-3 per layer at
# these widths (CPU, 2 and 4 layers), so about 4e-2 over 22 layers; a
# wrong cache position, mask or gather is off by order 1.
LOGIT_TOL = 1e-1
# Kernel outputs: the repo's bf16 kernel tolerance (tests/test_kernels.py).
BF16_TOL = dict(rtol=2e-2, atol=2e-2)
# Design-space engine: the registry's jax-vs-numpy tolerance
# (tests/test_engine.py::TestJaxEngineAgreement).
ENGINE_RTOL = 1e-9


def report(phase: str, **found) -> None:
    print(json.dumps({"phase": phase, **found}), flush=True)


def require_tpu():
    """Phase 0: refuse to run anywhere but on a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX sees {dev.platform!r}"
        )
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()))
    return dev


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def fallback_count() -> int:
    from repro.obs import metrics

    return metrics.get_metrics().counter(
        "overlap/resolve.autotune_fallback"
    ).value


def serve_phase(cfg, *, seed=SEED, n_req=4, prompt_len=32, new_tokens=16):
    """Phase 1: serve ``n_req`` requests; decode logits == forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import build_model
    from repro.serve.engine import DecodeEngine, Request, make_prefill

    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))

    cache_len = prompt_len + new_tokens
    eng = DecodeEngine(cfg, params, batch_size=n_req, cache_len=cache_len)
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n_req, prompt_len), dtype=np.int32
    )

    def serve():
        reqs = [Request(p.copy(), max_new_tokens=new_tokens) for p in prompts]
        t = time.perf_counter()
        eng.run(reqs)
        return [r.out for r in reqs], time.perf_counter() - t

    first, first_s = serve()  # compiles the decode step
    again, warm_s = serve()
    tokens = np.asarray(first)
    if tokens.shape != (n_req, new_tokens):
        raise AssertionError(f"served tokens of shape {tokens.shape}")
    if tokens.min() < 0 or tokens.max() >= cfg.vocab_size:
        raise AssertionError("served a token outside the vocabulary")
    if first != again:
        raise AssertionError("two runs of the same requests differ")

    # The decode path, position by position, against one forward pass.
    toks = jnp.asarray(prompts)
    t = time.perf_counter()
    want = jax.jit(make_prefill(model))(params, {"tokens": toks})
    want.block_until_ready()
    forward_s = time.perf_counter() - t
    cache = model.init_cache(n_req, cache_len)
    got = []
    for pos in range(prompt_len):
        logits, cache = eng.step_fn(
            params, cache, toks[:, pos:pos + 1], jnp.int32(pos)
        )
        got.append(logits[:, 0])
    got = jnp.stack(got, axis=1)
    finite = bool(jnp.isfinite(got).all() & jnp.isfinite(want).all())
    err = rel_err(got, want)
    report(
        "serve", arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
        dtype=cfg.dtype, params=n_params, init_s=init_s,
        requests=n_req, prompt_len=prompt_len, new_tokens=new_tokens,
        logits_shape=list(want.shape), finite=finite,
        logit_rel_err=err, logit_tol=LOGIT_TOL,
        first_run_s=first_s, warm_run_s=warm_s,
        decode_tok_per_s=n_req * new_tokens / warm_s,
        forward_compile_and_run_s=forward_s,
        autotune_fallback=fallback_count(),
    )
    if not finite:
        raise AssertionError("non-finite logits")
    if err > LOGIT_TOL:
        raise AssertionError(f"decode vs forward logits: {err} > {LOGIT_TOL}")


def kernel_phase(*, seed=SEED, m=4096, k=2048, n=5632):
    """Phase 2: the Pallas GEMM at MLP width against ``jnp.dot``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.bfloat16)
    t = time.perf_counter()
    compiled = ops.matmul.lower(x, w).compile()
    compile_s = time.perf_counter() - t
    custom_call = "tpu_custom_call" in compiled.as_text()
    got = compiled(x, w)
    want = jnp.dot(x, w, preferred_element_type=jnp.float32)
    times = []
    for _ in range(5):
        t = time.perf_counter()
        compiled(x, w).block_until_ready()
        times.append(time.perf_counter() - t)
    report(
        "kernel", op="ops.matmul", shape=[m, k, n], dtype="bfloat16",
        tpu_custom_call=custom_call, compile_s=compile_s,
        max_rel_err=rel_err(got, want),
        median_call_s=sorted(times)[len(times) // 2],
    )
    if not custom_call:
        raise AssertionError("no tpu_custom_call in the compiled GEMM")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), **BF16_TOL
    )


def engine_phase():
    """Phase 3: jitted design-space engine against the NumPy engine."""
    import jax
    import numpy as np

    from repro.core import get_engine
    from repro.core.workload import machine_grid, scenario_grid

    scenarios, machines = scenario_grid(), machine_grid()
    ref = get_engine("numpy").evaluate(scenarios, machines)
    t = time.perf_counter()
    got = get_engine("jax").evaluate(scenarios, machines)
    first_s = time.perf_counter() - t
    valid_equal = bool(np.array_equal(got.valid, ref.valid))
    a, b = got.total[ref.valid], ref.total[ref.valid]
    err = float(np.max(np.abs(a - b) / np.abs(b)))
    report(
        "engine", scenarios=len(scenarios), machines=len(machines),
        default_device=str(jax.devices()[0]), valid_equal=valid_equal,
        max_rel_err=err, rtol=ENGINE_RTOL, compile_and_run_s=first_s,
        autotune_fallback=fallback_count(),
    )
    if not valid_equal:
        raise AssertionError("jax and numpy engines disagree on validity")
    np.testing.assert_allclose(a, b, rtol=ENGINE_RTOL)


def four_chip_phase(cfg, mesh, *, seed=SEED, batch=4, seq=1024):
    """TinyLlama prefill, ficco_auto on the DMA kernels vs gspmd_serial,
    and the DMA kernels against their oracles at the same widths."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import OverlapConfig
    from repro.kernels import ops, ref
    from repro.models.model import build_model
    from repro.obs import metrics
    from repro.overlap.moe import ficco_a2a_ffn, serial_a2a_ffn
    from repro.parallel.sharding import MODEL_AXIS, fix_param_specs
    from repro.serve.engine import make_prefill

    g = mesh.shape[MODEL_AXIS]
    serial = build_model(dataclasses.replace(
        cfg, overlap=OverlapConfig(mode="gspmd_serial")
    ))
    ficco = build_model(dataclasses.replace(
        cfg, overlap=OverlapConfig(mode="ficco_auto", backend="pallas_dma")
    ))
    shapes = jax.eval_shape(serial.init, jax.random.PRNGKey(seed))
    specs = fix_param_specs(serial.param_specs(), shapes, mesh)
    params = jax.jit(
        serial.init,
        out_shardings=jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda s: isinstance(s, P),
        ),
    )(jax.random.PRNGKey(seed))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq), dtype=np.int32
    ))
    reg = metrics.get_metrics()
    dma, xla = (reg.counter(f"tp/pallas_dma.{p}") for p in ("dma", "xla"))
    with jax.sharding.set_mesh(mesh):
        t = time.perf_counter()
        want = jax.jit(make_prefill(serial))(params, {"tokens": toks})
        want.block_until_ready()
        serial_s = time.perf_counter() - t
        t = time.perf_counter()
        compiled = jax.jit(make_prefill(ficco)).lower(
            params, {"tokens": toks}
        ).compile()
        ficco_compile_s = time.perf_counter() - t
        got = compiled(params, {"tokens": toks})
    err = rel_err(got, want)
    finite = bool(jnp.isfinite(got).all())
    report(
        "ficco_prefill", arch=cfg.name, layers=cfg.num_layers,
        mesh=dict(mesh.shape), batch=batch, seq=seq,
        logits_shape=list(got.shape), finite=finite,
        logit_rel_err=err, logit_tol=LOGIT_TOL,
        dma_linears=dma.value, xla_linears=xla.value,
        tpu_custom_call="tpu_custom_call" in compiled.as_text(),
        serial_compile_and_run_s=serial_s, ficco_compile_s=ficco_compile_s,
        autotune_fallback=fallback_count(),
    )
    if not finite or err > LOGIT_TOL:
        raise AssertionError(f"ficco vs gspmd logits: {err} > {LOGIT_TOL}")
    if dma.value == 0 or xla.value != 0:
        raise AssertionError("a pallas_dma linear did not run the DMA path")

    # The kernels alone, at this phase's TP widths.
    m_s, k, n_local = batch * seq // g, cfg.d_model, cfg.d_ff // g
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    x = jax.random.normal(keys[0], (g * m_s, k), jnp.bfloat16)
    w = jax.random.normal(keys[1], (k, g * n_local), jnp.bfloat16)
    rows, cols = P(MODEL_AXIS, None), P(None, MODEL_AXIS)

    def sharded(body, in_specs, out_specs):
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ))

    chunk = x[: g * (m_s // g)]
    got = sharded(
        functools.partial(ops.chunk_exchange, axis_name=MODEL_AXIS, group=g),
        rows, P(MODEL_AXIS, None, None),
    )(chunk)
    want = sharded(
        functools.partial(ref.a2a_chunk_exchange_ref, axis_name=MODEL_AXIS),
        rows, P(MODEL_AXIS, None, None),
    )(chunk)
    exchange_equal = bool(jnp.array_equal(got, want))
    want_mm = sharded(
        functools.partial(ref.ag_matmul_ref, axis_name=MODEL_AXIS),
        (rows, cols), cols,
    )(x, w)
    outs = {
        name: sharded(
            functools.partial(fn, axis_name=MODEL_AXIS), (rows, cols), cols
        )(x, w)
        for name, fn in (("ag_matmul_dma", ops.ag_matmul_dma),
                         ("ag_matmul_fused", ops.ag_matmul_fused))
    }
    e_local, cap = 2, 256
    xe = jax.random.normal(keys[2], (g * e_local * g, cap, k), jnp.bfloat16)
    w_up = jax.random.normal(
        keys[3], (g * e_local, k, n_local), jnp.bfloat16
    ) / np.sqrt(k)
    w_down = jax.random.normal(
        keys[0], (g * e_local, n_local, k), jnp.bfloat16
    ) / np.sqrt(n_local)
    experts = P(MODEL_AXIS, None, None)
    a2a = {
        name: sharded(
            functools.partial(fn, axis_name=MODEL_AXIS),
            (experts, experts, experts), experts,
        )(xe, w_up, w_down)
        for name, fn in (("ficco", ficco_a2a_ffn), ("serial", serial_a2a_ffn))
    }
    report(
        "dma_kernels", m_s=m_s, k=k, n_local=n_local, dtype="bfloat16",
        exchange_equal=exchange_equal,
        ag_matmul_dma_rel_err=rel_err(outs["ag_matmul_dma"], want_mm),
        ag_matmul_fused_rel_err=rel_err(outs["ag_matmul_fused"], want_mm),
        a2a_ffn_rel_err=rel_err(a2a["ficco"], a2a["serial"]),
        experts=g * e_local, capacity=cap,
        autotune_fallback=fallback_count(),
    )
    if not exchange_equal:
        raise AssertionError("a2a_chunk_exchange != lax.all_gather")
    for name in ("ag_matmul_dma", "ag_matmul_fused"):
        np.testing.assert_allclose(
            np.asarray(outs[name], np.float32),
            np.asarray(want_mm, np.float32),
            err_msg=name, **BF16_TOL,
        )
    np.testing.assert_allclose(
        np.asarray(a2a["ficco"], np.float32),
        np.asarray(a2a["serial"], np.float32),
        err_msg="ficco_a2a_ffn", **BF16_TOL,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip overlap phase")
    args = ap.parse_args(argv)

    dev = require_tpu()
    import jax

    from repro.configs import get_config
    from repro.launch.cache import use_compile_cache

    report("compile_cache", dir=use_compile_cache())
    cfg = get_config(ARCH)
    if args.chips == 4:
        from repro.launch.mesh import make_mesh

        if len(jax.devices()) < 4:
            raise SystemExit(f"--chips 4: JAX sees {len(jax.devices())}")
        four_chip_phase(cfg, make_mesh((1, 4), ("data", "model")))
    else:
        serve_phase(cfg)
        kernel_phase()
        engine_phase()
    if fallback_count():
        raise AssertionError("the autotuner fell back to the static tree")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Mixture-of-Experts FFN: a top-k router and two dispatch paths.

The router scores every expert of the layer (``num_experts``); the layer
computes the experts it holds (``MoEConfig.held_experts``, all of them
by default), so a layer that holds a share of the experts gives its
share's part of the output, as one chip of an expert-parallel
deployment does (without the exchange, which lives in
``repro.overlap.moe``).  Each MoE layer counts, at trace time, the path
that served it (``moe/dropless`` or ``moe/capacity``):

* **dropless** (``capacity_factor=None``): every (token, held expert)
  assignment is computed.  The assignments are sorted by expert and run
  through a grouped GEMM (``lax.ragged_dot``), then scatter-added back,
  weighted by the router's probabilities, renormalised over the top-k or
  not (``norm_topk_prob``), as DeepSeek-V2 runs them.
* **capacity**: sort-based dispatch into (E, C, D) expert batches with
  capacity clipping, so it scales to Arctic's 128 experts x 1M tokens
  under GSPMD: the expert batches are sharded over the ``model`` axis
  (expert parallelism) and XLA lowers the gather/scatter to all-to-alls —
  the exact data-dependent A2A -> expert-GEMM pattern of the paper's EP
  scenarios (Table I g13–g16).  Tokens past an expert's capacity are
  dropped.

Supports DeepSeek-style shared experts and Arctic's dense residual FFN.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models import layers
from repro.obs import metrics as _metrics
from repro.parallel.sharding import BATCH_AXES, MODEL_AXIS, constrain


def moe_init(rng, d_model: int, cfg: MoEConfig, dtype):
    r = jax.random.split(rng, 6)
    e, ff = cfg.num_experts, cfg.d_ff_expert
    held = cfg.held[1]
    p = {
        "router": layers.dense_init(r[0], d_model, e, jnp.float32),
        "w_gate": _expert_init(r[1], held, d_model, ff, dtype),
        "w_up": _expert_init(r[2], held, d_model, ff, dtype),
        "w_down": _expert_init(r[3], held, ff, d_model, dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = layers.mlp_init(
            r[4], d_model, ff * cfg.num_shared_experts, dtype
        )
    if cfg.dense_residual_ff:
        p["dense_residual"] = layers.mlp_init(
            r[5], d_model, cfg.dense_residual_ff, dtype
        )
    return p


def _expert_init(rng, e, d_in, d_out, dtype):
    std = 1.0 / jnp.sqrt(d_in)
    return (jax.random.normal(rng, (e, d_in, d_out)) * std).astype(dtype)


def moe_param_specs(cfg: MoEConfig):
    p = {
        "router": P(None, None),
        "w_gate": P(MODEL_AXIS, None, None),  # expert parallel
        "w_up": P(MODEL_AXIS, None, None),
        "w_down": P(MODEL_AXIS, None, None),
    }
    if cfg.num_shared_experts:
        p["shared"] = layers.mlp_param_specs()
    if cfg.dense_residual_ff:
        p["dense_residual"] = layers.mlp_param_specs()
    return p


def _route(params, xf, cfg: MoEConfig):
    """The top-k weights and experts of the router's probabilities over
    all experts (float32), and the two aux losses."""
    t, e, k = xf.shape[0], cfg.num_experts, cfg.top_k
    logits = (xf.astype(jnp.float32)) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # (T, k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # aux losses (GShard load balance + router z-loss)
    me = probs.mean(0)  # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (t * k)
    )
    lb_loss = cfg.load_balance_loss * e * jnp.sum(me * ce)
    z_loss = cfg.router_z_loss * jnp.mean(
        jax.nn.logsumexp(logits, axis=-1) ** 2
    )
    return top_w, top_e, (lb_loss, z_loss)


def _dropless(params, xf, top_w, top_e, cfg: MoEConfig):
    """The held experts' part of the output, every assignment computed:
    (T, D) in float32."""
    t, d = xf.shape
    first, held = cfg.held
    k = cfg.top_k
    with jax.named_scope("dispatch"):
        local = top_e.reshape(-1) - first  # (T*k,)
        mine = (local >= 0) & (local < held)
        # the assignments of experts held elsewhere sort last, past the
        # groups, where the grouped GEMM leaves its rows zero
        order = jnp.argsort(jnp.where(mine, local, held), stable=True)
        tok = jnp.repeat(jnp.arange(t), k)[order]
        w = jnp.where(mine, top_w.reshape(-1), 0.0)[order]
        sizes = jnp.bincount(jnp.where(mine, local, held),
                             length=held + 1)[:held].astype(jnp.int32)
        rows = xf[tok]  # (T*k, D)
    with jax.named_scope("experts"):
        h = jax.lax.ragged_dot(rows, params["w_up"], sizes)
        g = jax.lax.ragged_dot(rows, params["w_gate"], sizes)
        h = jax.nn.silu(g) * h
        out = jax.lax.ragged_dot(h, params["w_down"], sizes)  # (T*k, D)
    with jax.named_scope("combine"):
        routed = jnp.where(w[:, None] > 0,
                           out.astype(jnp.float32) * w[:, None], 0.0)
        return jnp.zeros((t, d), jnp.float32).at[tok].add(routed)


def _capacity(params, xf, top_w, top_e, cfg: MoEConfig):
    """Sorted capacity dispatch over every expert: (T, D) in x's dtype,
    tokens past an expert's capacity dropped."""
    t, d = xf.shape
    e, k = cfg.num_experts, cfg.top_k
    capacity = int(max(cfg.capacity_factor * t * k / e, 4))

    with jax.named_scope("dispatch"):
        flat_e = top_e.reshape(-1)  # (T*k,)
        flat_w = top_w.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t), k)
        order = jnp.argsort(flat_e, stable=True)
        se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]
        counts = jnp.bincount(flat_e, length=e)
        starts = jnp.cumsum(counts) - counts  # exclusive prefix
        pos = jnp.arange(t * k) - starts[se]  # position within expert
        keep = pos < capacity
        slot = jnp.where(keep, se * capacity + pos, e * capacity)  # tail

        disp = jnp.zeros((e * capacity + 1, d), xf.dtype).at[slot].set(
            xf[stok])
        expert_in = disp[: e * capacity].reshape(e, capacity, d)
        expert_in = constrain(expert_in, MODEL_AXIS, None, None)

    # ---- expert FFN (A2A -> grouped GEMM: the paper's EP hot spot) ---
    with jax.named_scope("experts"):
        h = jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"])
        g = jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"])
        h = jax.nn.silu(g) * h
        h = constrain(h, MODEL_AXIS, None, None)
        expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
        expert_out = constrain(expert_out, MODEL_AXIS, None, None)

    with jax.named_scope("combine"):
        flat_out = jnp.concatenate(
            [expert_out.reshape(e * capacity, d), jnp.zeros((1, d), xf.dtype)]
        )
        routed = flat_out[slot] * sw[:, None].astype(xf.dtype)
        return jnp.zeros((t, d), xf.dtype).at[stok].add(
            jnp.where(keep[:, None], routed, 0)
        )


def moe_apply(params, x: jax.Array, cfg: MoEConfig):
    """x: (B, S, D) -> (out, aux_losses)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    dropless = cfg.capacity_factor is None
    if not dropless and cfg.held != (0, cfg.num_experts):
        raise ValueError("a layer that holds a share of the experts runs "
                         "dropless (capacity_factor=None)")
    _metrics.get_metrics().counter(
        "moe/dropless" if dropless else "moe/capacity").inc()
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            top_w, top_e, (lb_loss, z_loss) = _route(params, xf, cfg)
        if dropless:
            y = _dropless(params, xf, top_w, top_e, cfg)
        else:
            y = _capacity(params, xf, top_w, top_e, cfg)
        out = y.astype(x.dtype).reshape(b, s, d)

        if "shared" in params:
            with jax.named_scope("shared"):
                out = out + layers.mlp_apply(params["shared"], x)
        if "dense_residual" in params:
            out = out + layers.mlp_apply(params["dense_residual"], x)
        out = constrain(out, BATCH_AXES, None, None)
    return out, lb_loss + z_loss

"""Model assembly: all six architecture families behind one interface.

A model is a stack of **periods**: the smallest repeating layer pattern
(dense: 1 layer; Jamba: 8 layers = 1 attention + 7 Mamba with MoE on odd
layers; xLSTM: 8 = 7 mLSTM + 1 sLSTM; ...).  Periods are scanned with
``lax.scan`` over stacked parameters so 80-layer models compile fast at
512-way SPMD, and each period is optionally rematerialized.  An MoE model
may open with dense layers (``MoEConfig.first_dense_layers``,
DeepSeek-V2's first_k_dense_replace): they are a stack of their own,
``params["lead"]`` and ``cache["lead"]``, run before the periods, whose
parameters and cache are then ``params["layers"]`` and
``cache["layers"]``.  A model without them has the one stack, and its
cache is that stack's list.

Interface (used by train/serve/launch):
    model = build_model(config)
    params        = model.init(rng)
    specs         = model.param_specs()          # PartitionSpec pytree
    logits, aux   = model.forward(params, batch)
    cache         = model.init_cache(batch, cache_len)
    logits, cache = model.decode_step(params, cache, tokens, pos)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import Family, ModelConfig
from repro.models import layers, mamba, mla, moe, xlstm
from repro.models.layers import AttnDims
from repro.parallel.sharding import BATCH_AXES, MODEL_AXIS, constrain


# ---------------------------------------------------------------------------
# Layer pattern
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str  # attn | mla | mamba | mlstm | slstm
    ffn: str  # mlp | moe | none


def layer_pattern(cfg: ModelConfig) -> list[LayerSpec]:
    """The repeating period of layer kinds for this architecture."""
    if cfg.family is Family.SSM:
        x = cfg.xlstm
        period = x.slstm_every
        return [
            LayerSpec(
                "slstm" if i % x.slstm_every == x.slstm_offset else "mlstm",
                "none",
            )
            for i in range(period)
        ]
    if cfg.family is Family.HYBRID:
        h = cfg.hybrid
        period = h.attn_every
        out = []
        for i in range(period):
            mixer = "attn" if i % h.attn_every == h.attn_offset else "mamba"
            ffn = (
                "moe"
                if cfg.moe and i % cfg.moe.every_k_layers
                == cfg.moe.every_k_layers - 1
                else "mlp"
            )
            out.append(LayerSpec(mixer, ffn))
        return out
    mixer = "mla" if cfg.mla else "attn"
    if cfg.moe:
        period = cfg.moe.every_k_layers
        return [
            LayerSpec(mixer, "moe" if i == period - 1 else "mlp")
            for i in range(period)
        ]
    return [LayerSpec(mixer, "mlp")]


def lead_layers(cfg: ModelConfig) -> int:
    """Dense layers before the periods (DeepSeek-V2: 1)."""
    return cfg.moe.first_dense_layers if cfg.moe else 0


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _attn_dims(cfg: ModelConfig) -> AttnDims:
    return AttnDims(
        cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    )


# ---------------------------------------------------------------------------
# Single-layer init / apply / decode
# ---------------------------------------------------------------------------

def _layer_init(rng, spec: LayerSpec, cfg: ModelConfig, *, cross: bool):
    dt = _dtype(cfg)
    r = jax.random.split(rng, 8)
    p: dict[str, Any] = {"norm1": layers.norm_init(cfg.d_model, cfg.norm, dt)}
    if spec.mixer == "attn":
        p["attn"] = layers.attn_init(r[0], _attn_dims(cfg), dt)
    elif spec.mixer == "mla":
        p["attn"] = mla.mla_init(r[0], cfg.d_model, cfg.num_heads, cfg.mla, dt)
    elif spec.mixer == "mamba":
        p["mixer"] = mamba.mamba_init(r[0], cfg.d_model, cfg.hybrid.mamba, dt)
    elif spec.mixer == "mlstm":
        p["mixer"] = xlstm.mlstm_init(
            r[0], cfg.d_model, cfg.num_heads, cfg.xlstm, dt
        )
    elif spec.mixer == "slstm":
        p["mixer"] = xlstm.slstm_init(r[0], cfg.d_model, cfg.xlstm, dt)
    if cross:
        p["norm_cross"] = layers.norm_init(cfg.d_model, cfg.norm, dt)
        p["cross"] = layers.attn_init(r[1], _attn_dims(cfg), dt)
    if spec.ffn != "none":
        p["norm2"] = layers.norm_init(cfg.d_model, cfg.norm, dt)
        if spec.ffn == "moe":
            p["ffn"] = moe.moe_init(r[2], cfg.d_model, cfg.moe, dt)
        else:
            p["ffn"] = layers.mlp_init(r[2], cfg.d_model, cfg.d_ff, dt)
    return p


def _layer_specs(spec: LayerSpec, cfg: ModelConfig, *, cross: bool):
    s: dict[str, Any] = {"norm1": _norm_spec(cfg)}
    if spec.mixer == "attn":
        s["attn"] = layers.attn_param_specs()
    elif spec.mixer == "mla":
        s["attn"] = mla.mla_param_specs()
    elif spec.mixer == "mamba":
        s["mixer"] = mamba.mamba_param_specs()
    elif spec.mixer == "mlstm":
        s["mixer"] = xlstm.mlstm_param_specs()
    elif spec.mixer == "slstm":
        s["mixer"] = xlstm.slstm_param_specs()
    if cross:
        s["norm_cross"] = _norm_spec(cfg)
        s["cross"] = layers.attn_param_specs()
    if spec.ffn != "none":
        s["norm2"] = _norm_spec(cfg)
        s["ffn"] = (
            moe.moe_param_specs(cfg.moe)
            if spec.ffn == "moe"
            else layers.mlp_param_specs()
        )
    return s


def _norm_spec(cfg: ModelConfig):
    if cfg.norm == "rmsnorm":
        return {"scale": P(None)}
    if cfg.norm == "layernorm":
        return {"scale": P(None), "bias": P(None)}
    return {}


def _window(cfg: ModelConfig) -> Optional[int]:
    if cfg.sliding_window:
        return cfg.sliding_window
    return None


def _layer_apply(
    p,
    spec: LayerSpec,
    cfg: ModelConfig,
    x,
    positions,
    *,
    enc_out=None,
    causal: bool = True,
):
    aux = jnp.zeros((), jnp.float32)
    h = layers.apply_norm(p["norm1"], x, cfg.norm)
    if spec.mixer == "attn":
        if causal:
            y = layers.attn_apply(
                p["attn"], h, _attn_dims(cfg),
                rope_theta=cfg.rope_theta, positions=positions,
                window=_window(cfg),
            )
        else:  # encoder self-attention: bidirectional
            dims = _attn_dims(cfg)
            b, s, _ = h.shape
            q = (h @ p["attn"]["wq"]).reshape(b, s, dims.num_heads,
                                              dims.head_dim)
            k = (h @ p["attn"]["wk"]).reshape(b, s, dims.num_kv_heads,
                                              dims.head_dim)
            v = (h @ p["attn"]["wv"]).reshape(b, s, dims.num_kv_heads,
                                              dims.head_dim)
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
            o = layers.blockwise_attention(q, k, v, causal=False)
            y = o.reshape(b, s, -1) @ p["attn"]["wo"]
    elif spec.mixer == "mla":
        y = mla.mla_apply(
            p["attn"], h, cfg.num_heads, cfg.mla,
            positions=positions, rope_theta=cfg.rope_theta,
            window=_window(cfg),
        )
    elif spec.mixer == "mamba":
        y = mamba.mamba_apply(p["mixer"], h, cfg.hybrid.mamba)
    elif spec.mixer == "mlstm":
        y = xlstm.mlstm_apply(p["mixer"], h, cfg.num_heads, cfg.xlstm)
    else:  # slstm
        y = xlstm.slstm_apply(p["mixer"], h, cfg.xlstm)
    x = x + y

    if enc_out is not None:
        h = layers.apply_norm(p["norm_cross"], x, cfg.norm)
        y = layers.attn_apply(
            p["cross"], h, _attn_dims(cfg),
            rope_theta=cfg.rope_theta, positions=positions,
            kv_for_cross=enc_out,
        )
        x = x + y

    if spec.ffn != "none":
        h = layers.apply_norm(p["norm2"], x, cfg.norm)
        if spec.ffn == "moe":
            y, l = moe.moe_apply(p["ffn"], h, cfg.moe)
            aux = aux + l
        else:
            y = layers.mlp_apply(p["ffn"], h)
        x = x + y
    # Megatron tensor-SEQUENCE parallelism: the residual stream lives
    # sequence-sharded over the model axis (paper Fig. 3a start state);
    # each block's projections all-gather it -> the data-dependent
    # AG->GEMM pair FiCCO overlaps.  Also cuts activation memory g-fold.
    return constrain(x, BATCH_AXES, MODEL_AXIS, None), aux


def _layer_init_cache(
    spec: LayerSpec, cfg: ModelConfig, batch: int, cache_len: int, dt
):
    if spec.mixer in ("attn",):
        s = min(cache_len, cfg.sliding_window or cache_len)
        shape = layers.kv_cache_shape(_attn_dims(cfg), batch, s)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if spec.mixer == "mla":
        return mla.mla_init_cache(batch, cache_len, cfg.mla, dt)
    if spec.mixer == "mamba":
        return mamba.mamba_init_cache(batch, cfg.d_model, cfg.hybrid.mamba, dt)
    if spec.mixer == "mlstm":
        return xlstm.mlstm_init_cache(batch, cfg.d_model, cfg.num_heads,
                                      cfg.xlstm)
    return xlstm.slstm_init_cache(batch, cfg.d_model, cfg.xlstm)


_CROSS = ("cross_k", "cross_v")


def _layer_decode(p, spec: LayerSpec, cfg: ModelConfig, x, cache, layer,
                  pos, *, has_cross: bool = False):
    """One layer's decode step.  ``cache`` holds this pattern position's
    leaves stacked over periods and ``layer`` indexes them: attention
    caches take this token's row in place, recurrent states are replaced
    whole, cross-attention K/V are only read."""
    h = layers.apply_norm(p["norm1"], x, cfg.norm)
    if spec.mixer == "attn":
        y, written = layers.attn_decode(
            p["attn"], h, cache, layer, pos, _attn_dims(cfg),
            rope_theta=cfg.rope_theta, window=_window(cfg),
        )
    elif spec.mixer == "mla":
        y, written = mla.mla_decode(
            p["attn"], h, cache, layer, pos, cfg.num_heads, cfg.mla,
            rope_theta=cfg.rope_theta,
        )
    else:
        state = {k: layers.cache_layer(a, layer)
                 for k, a in cache.items() if k not in _CROSS}
        if spec.mixer == "mamba":
            y, state = mamba.mamba_decode(
                p["mixer"], h, state, cfg.hybrid.mamba
            )
        elif spec.mixer == "mlstm":
            y, state = xlstm.mlstm_decode(
                p["mixer"], h, state, cfg.num_heads, cfg.xlstm
            )
        else:
            y, state = xlstm.slstm_decode(p["mixer"], h, state, cfg.xlstm)
        written = {k: layers.write_state(cache[k], layer, a)
                   for k, a in state.items()}
    cache = {**cache, **written}
    x = x + y
    if has_cross:
        h = layers.apply_norm(p["norm_cross"], x, cfg.norm)
        b = x.shape[0]
        dims = _attn_dims(cfg)
        q = (h @ p["cross"]["wq"]).reshape(b, 1, dims.num_heads,
                                           dims.head_dim)
        out = layers.cache_attention(
            q, layers.cache_layer(cache["cross_k"], layer),
            layers.cache_layer(cache["cross_v"], layer),
        )
        y = out.reshape(b, 1, -1) @ p["cross"]["wo"]
        x = x + y
    if spec.ffn != "none":
        h = layers.apply_norm(p["norm2"], x, cfg.norm)
        if spec.ffn == "moe":
            y, _ = moe.moe_apply(p["ffn"], h, cfg.moe)
        else:
            y = layers.mlp_apply(p["ffn"], h)
        x = x + y
    return x, cache


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    """Decoder LM (all families) with optional encoder (audio enc-dec)."""

    def __init__(self, config: ModelConfig):
        self.config = config
        self.pattern = layer_pattern(config)
        self.n_lead = lead_layers(config)
        self.lead_pattern = [LayerSpec(self.pattern[0].mixer, "mlp")]
        stacked = config.num_layers - self.n_lead
        if stacked % len(self.pattern):
            raise ValueError(
                f"{config.name}: {stacked} layers not divisible "
                f"by period {len(self.pattern)}"
            )
        self.n_periods = stacked // len(self.pattern)
        self.is_encdec = config.encdec is not None

    # ---- init -----------------------------------------------------------
    def init(self, rng) -> dict:
        cfg = self.config
        dt = _dtype(cfg)
        r = jax.random.split(rng, 8)
        std = 0.02
        params: dict[str, Any] = {
            "embed": (
                jax.random.normal(r[0], (cfg.vocab_size, cfg.d_model)) * std
            ).astype(dt),
            "final_norm": layers.norm_init(cfg.d_model, cfg.norm, dt),
            "layers": self._init_stack(r[1], self.pattern, self.n_periods,
                                       cross=self.is_encdec),
        }
        if self.n_lead:
            params["lead"] = self._init_stack(
                r[5], self.lead_pattern, self.n_lead, cross=False
            )
        if not cfg.tie_embeddings:
            params["unembed"] = (
                jax.random.normal(r[2], (cfg.d_model, cfg.vocab_size)) * std
            ).astype(dt)
        if self.is_encdec:
            params["encoder"] = self._init_enc_stack(r[3])
            params["enc_norm"] = layers.norm_init(cfg.d_model, cfg.norm, dt)
        if cfg.frontend and cfg.frontend.embed_dim:
            params["frontend_proj"] = layers.dense_init(
                r[4], cfg.frontend.embed_dim, cfg.d_model, dt
            )
        return params

    def _init_stack(self, rng, pattern, n_periods: int, *, cross: bool):
        def init_period(r):
            rs = jax.random.split(r, len(pattern))
            return [
                _layer_init(rs[i], s, self.config, cross=cross)
                for i, s in enumerate(pattern)
            ]

        rngs = jax.random.split(rng, n_periods)
        periods = [init_period(r) for r in rngs]
        # stack over periods
        return jax.tree.map(lambda *xs: jnp.stack(xs), *periods)

    def _init_enc_stack(self, rng):
        cfg = self.config
        n = cfg.encdec.encoder_layers
        spec = LayerSpec("attn", "mlp")
        rngs = jax.random.split(rng, n)
        ps = [_layer_init(r, spec, cfg, cross=False) for r in rngs]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)

    # ---- sharding specs ---------------------------------------------------
    def param_specs(self) -> dict:
        cfg = self.config

        def stack(pattern, cross):
            # prepend scan dim (periods) to every leaf
            return jax.tree.map(
                lambda sp: P(None, *sp),
                [_layer_specs(s, cfg, cross=cross) for s in pattern],
                is_leaf=lambda x: isinstance(x, P),
            )

        specs: dict[str, Any] = {
            "embed": P(MODEL_AXIS, None),
            "final_norm": _norm_spec(cfg),
            "layers": stack(self.pattern, self.is_encdec),
        }
        if self.n_lead:
            specs["lead"] = stack(self.lead_pattern, False)
        if not cfg.tie_embeddings:
            specs["unembed"] = P(None, MODEL_AXIS)
        if self.is_encdec:
            enc = _layer_specs(LayerSpec("attn", "mlp"), cfg, cross=False)
            specs["encoder"] = jax.tree.map(
                lambda sp: P(None, *sp), enc,
                is_leaf=lambda x: isinstance(x, P),
            )
            specs["enc_norm"] = _norm_spec(cfg)
        if cfg.frontend and cfg.frontend.embed_dim:
            specs["frontend_proj"] = P(None, None)
        return specs

    # ---- forward ----------------------------------------------------------
    def _run_stack(self, stack_params, x, positions, pattern, n_periods,
                   *, enc_out=None, causal=True):
        cfg = self.config

        def period_fn(x, period_params):
            aux = jnp.zeros((), jnp.float32)
            for i, spec in enumerate(pattern):
                x, a = _layer_apply(
                    period_params[i], spec, cfg, x, positions,
                    enc_out=enc_out, causal=causal,
                )
                aux = aux + a
            return x, aux

        if cfg.remat:
            policy = (
                jax.checkpoint_policies.dots_saveable
                if cfg.remat_policy == "dots"
                else jax.checkpoint_policies.nothing_saveable
            )
            period_fn = jax.checkpoint(period_fn, policy=policy)

        with jax.named_scope("layers"):
            if cfg.scan_layers and n_periods > 1:
                def body(x, pp):
                    x, aux = period_fn(x, pp)
                    return x, aux

                x, auxs = lax.scan(body, x, stack_params)
                return x, jnp.sum(auxs)
            # unrolled
            aux = jnp.zeros((), jnp.float32)
            for i in range(n_periods):
                pp = jax.tree.map(lambda a, i=i: a[i], stack_params)
                x, a = period_fn(x, pp)
                aux = aux + a
            return x, aux

    def _encode(self, params, enc_frames):
        cfg = self.config
        x = enc_frames.astype(_dtype(cfg))
        pos = jnp.broadcast_to(
            jnp.arange(x.shape[1]), x.shape[:2]
        )

        def enc_fn(x, lp):
            y, aux = _layer_apply(
                lp, LayerSpec("attn", "mlp"), cfg, x, pos, causal=False
            )
            return y, aux

        if cfg.remat:
            enc_fn = jax.checkpoint(enc_fn)
        if cfg.scan_layers and cfg.encdec.encoder_layers > 1:
            x, _ = lax.scan(lambda c, lp: enc_fn(c, lp), x, params["encoder"])
        else:
            for i in range(cfg.encdec.encoder_layers):
                lp = jax.tree.map(lambda a, i=i: a[i], params["encoder"])
                x, _ = enc_fn(x, lp)
        return layers.apply_norm(params["enc_norm"], x, cfg.norm)

    def forward(self, params, batch: dict):
        """batch keys: tokens (B, S); optional prefix_embeds (B, P, d) |
        enc_frames (B, S_enc, d).  Returns (logits, aux_loss)."""
        cfg = self.config
        tokens = batch["tokens"]
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(_dtype(cfg))
            x = constrain(x, BATCH_AXES, MODEL_AXIS, None)

        enc_out = None
        if self.is_encdec:
            enc_out = self._encode(params, batch["enc_frames"])

        if cfg.frontend is not None and "prefix_embeds" in batch:
            pe = batch["prefix_embeds"].astype(_dtype(cfg))
            if "frontend_proj" in params:
                pe = pe @ params["frontend_proj"]
            x = jnp.concatenate([pe, x], axis=1)

        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        if self.n_lead:
            x, lead_aux = self._run_stack(
                params["lead"], x, positions, self.lead_pattern, self.n_lead
            )
        x, aux = self._run_stack(
            params["layers"], x, positions, self.pattern, self.n_periods,
            enc_out=enc_out,
        )
        if self.n_lead:
            aux = aux + lead_aux
        x = layers.apply_norm(params["final_norm"], x, cfg.norm)
        if cfg.frontend is not None and "prefix_embeds" in batch:
            x = x[:, -tokens.shape[1]:]  # logits over the text segment
        logits = self._unembed(params, x)
        return logits, aux

    def _unembed(self, params, x):
        with jax.named_scope("logits"):
            w = (
                params["embed"].T
                if self.config.tie_embeddings
                else params["unembed"]
            )
            logits = x @ w.astype(x.dtype)
            return constrain(logits, BATCH_AXES, None, MODEL_AXIS)

    def loss(self, params, batch: dict):
        """Vocab-parallel-safe cross entropy: all reductions run over the
        (possibly model-axis-sharded) vocab dimension — no gather ops that
        would force GSPMD to replicate the fp32 logits."""
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        lg = logits[:, :-1].astype(jnp.float32)
        lg = constrain(lg, BATCH_AXES, None, MODEL_AXIS)
        tg = labels[:, 1:]
        m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
        logz = (
            jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
        )
        vocab_iota = jnp.arange(lg.shape[-1])[None, None, :]
        gold = jnp.sum(
            jnp.where(vocab_iota == tg[..., None], lg, 0.0), axis=-1
        )
        ce = jnp.mean(logz - gold)
        return ce + aux, {"ce": ce, "aux": aux}

    # ---- decode ------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int, *, enc_len: int = 0):
        cfg = self.config
        dt = _dtype(cfg)

        def stack_cache(pattern, n_periods, cross):
            caches = [
                _layer_init_cache(s, cfg, batch, cache_len, dt)
                for s in pattern
            ]
            if cross:
                shape = layers.kv_cache_shape(_attn_dims(cfg), batch, enc_len)
                for c in caches:
                    c["cross_k"] = jnp.zeros(shape, dt)
                    c["cross_v"] = jnp.zeros(shape, dt)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_periods, *a.shape)), caches
            )

        stack = stack_cache(self.pattern, self.n_periods, self.is_encdec)
        if not self.n_lead:
            return stack
        return {"lead": stack_cache(self.lead_pattern, self.n_lead, False),
                "layers": stack}

    def cache_batch_dims(self, cache):
        """For each leaf of ``cache`` (as ``init_cache`` makes it), the
        dimension that holds the batch, after the periods dimension: the
        K/V caches hold it where their layout puts it, the rest first."""
        kv = 1 + layers.KV_CACHE_BATCH_AXIS

        def stack(caches):
            return [{name: kv if name in ("k", "v") + _CROSS else 1
                     for name in c} for c in caches]

        if self.n_lead:
            return {k: stack(v) for k, v in cache.items()}
        return stack(cache)

    def prefill_cross(self, params, cache, enc_frames):
        """Enc-dec: run the encoder and fill per-layer cross K/V."""
        cfg = self.config
        enc_out = self._encode(params, enc_frames)
        dims = _attn_dims(cfg)
        b, s_enc, _ = enc_out.shape

        def fill(period_params, period_cache):
            for i in range(len(self.pattern)):
                pa = period_params[i]["cross"]
                k = (enc_out @ pa["wk"]).reshape(
                    b, s_enc, dims.num_kv_heads, dims.head_dim
                )
                v = (enc_out @ pa["wv"]).reshape(
                    b, s_enc, dims.num_kv_heads, dims.head_dim
                )
                period_cache[i]["cross_k"] = layers.to_cache_order(
                    k).astype(_dtype(cfg))
                period_cache[i]["cross_v"] = layers.to_cache_order(
                    v).astype(_dtype(cfg))
            return period_cache

        def body(_, args):
            pp, pc = args
            return None, fill(pp, pc)

        _, new_cache = lax.scan(body, None, (params["layers"], cache))
        return new_cache

    def decode_step(self, params, cache, tokens, pos):
        """tokens: (B, 1) int32; pos: scalar position. -> (logits, cache).

        The layer loop carries the whole cache: each layer writes this
        token's row into its entry in place and reads its entry where it
        lies, so a caller that donates ``cache`` keeps one buffer."""
        cfg = self.config
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(_dtype(cfg))

        with jax.named_scope("layers"):
            if self.n_lead:
                x, lead = self._decode_stack(
                    params["lead"], cache["lead"], self.lead_pattern,
                    self.n_lead, x, pos,
                )
                x, stack = self._decode_stack(
                    params["layers"], cache["layers"], self.pattern,
                    self.n_periods, x, pos,
                )
                cache = {"lead": lead, "layers": stack}
            else:
                x, cache = self._decode_stack(
                    params["layers"], cache, self.pattern, self.n_periods,
                    x, pos,
                )
        x = layers.apply_norm(params["final_norm"], x, cfg.norm)
        logits = self._unembed(params, x)
        return logits, cache

    def _decode_stack(self, stack_params, cache, pattern, n_periods, x,
                      pos):
        """One stack's layers of a decode step: (x, its cache)."""
        cfg = self.config

        def body(carry, args):
            x, cache = carry
            pp, layer = args
            cache = list(cache)
            for i, spec in enumerate(pattern):
                x, cache[i] = _layer_decode(
                    pp[i], spec, cfg, x, cache[i], layer, pos,
                    has_cross="cross_k" in cache[i],
                )
            return (x, cache), None

        if cfg.scan_layers and n_periods > 1:
            (x, cache), _ = lax.scan(
                body, (x, cache), (stack_params, jnp.arange(n_periods)),
            )
        else:
            for i in range(n_periods):
                pp = jax.tree.map(lambda a, i=i: a[i], stack_params)
                (x, cache), _ = body((x, cache), (pp, i))
        return x, cache


def build_model(config: ModelConfig) -> Model:
    return Model(config)

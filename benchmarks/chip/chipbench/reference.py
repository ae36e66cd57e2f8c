"""Plain float32 reference for the dense family, and its low-precision control.

It follows the published descriptions of the family's two members here:
OLMo (arXiv:2402.00838: non-parametric LayerNorm, tied embeddings) and
Yi (arXiv:2403.04652: RMSNorm, grouped-query attention), both with
rotary positions (rotate-half form), SwiGLU MLPs and no biases.  It
imports nothing of the program and takes nothing the program made: it
draws its own weights from the seed (``chipbench.weights``) and upcasts
them.  Departures from the published models, kept because the program
makes the same choice and the comparison is not about them, are listed
in PERF.md (norm epsilon, Yi's RoPE base).

Every product runs at ``Precision.HIGHEST`` (on a TPU, float32 products
otherwise run in bfloat16 passes).  It runs layer by layer, and in blocks
of sequences, so that it fits beside nothing else on the chip.

``quant="fp8"`` is the control: every product's operands rounded to
float8 (e4m3, per-tensor scale), the step below the bfloat16 the
configurations state.  A comparison that passes this control is too loose.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import weights
from chipbench.model import Dims

HIGHEST = lax.Precision.HIGHEST
_F8_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with a per-tensor scale; the gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def _mm(a, b, quant: Optional[str]):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif quant is not None:
        raise ValueError(f"quant {quant!r}")
    return jnp.matmul(a, b, precision=HIGHEST)


def norm(m: Dims, x: jax.Array) -> jax.Array:
    """The configuration's norm; RMSNorm's scale is 1 (as initialised)."""
    if m.norm == "rmsnorm":
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m.norm_eps)
    if m.norm == "nonparametric_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + m.norm_eps)
    raise ValueError(m.norm)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE over positions 0..S-1; x: (S, heads, hd)."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(m: Dims, q, k, v, quant):
    """Causal softmax attention of one sequence; q: (S, H, hd), k, v:
    (S, KV, hd).  One KV group at a time, recomputed in the backward."""
    s = q.shape[0]
    rep = m.num_heads // m.num_kv_heads
    qg = q.reshape(s, m.num_kv_heads, rep, m.head_dim).transpose(1, 2, 0, 3)
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    mask = np.tril(np.ones((s, s), bool))

    @jax.checkpoint
    def group(args):
        qq, kk, vv = args  # (rep, S, hd), (S, hd), (S, hd)
        scores = _mm(qq, kk.T, quant) / math.sqrt(m.head_dim)
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return _mm(p, vv, quant)  # (rep, S, hd)

    out = lax.map(group, (qg, kg, vg))  # (KV, rep, S, hd)
    return out.transpose(2, 0, 1, 3).reshape(s, m.num_heads * m.head_dim)


def layer(m: Dims, x: jax.Array, w: dict, quant=None) -> jax.Array:
    """One decoder layer over a block of sequences; x: (b, S, d) f32."""
    b, s, d = x.shape

    def attn_one(h):  # (S, d)
        q = _mm(h, w["wq"], quant).reshape(s, m.num_heads, m.head_dim)
        k = _mm(h, w["wk"], quant).reshape(s, m.num_kv_heads, m.head_dim)
        v = _mm(h, w["wv"], quant).reshape(s, m.num_kv_heads, m.head_dim)
        o = _attention(m, rope(q, m.rope_theta), rope(k, m.rope_theta), v,
                       quant)
        return _mm(o, w["wo"], quant)

    x = x + lax.map(attn_one, norm(m, x))
    h = norm(m, x).reshape(b * s, d)
    gate = jax.nn.silu(_mm(h, w["w_gate"], quant))
    y = _mm(gate * _mm(h, w["w_up"], quant), w["w_down"], quant)
    return x + y.reshape(b, s, d)


def logits(m: Dims, x: jax.Array, unembed: jax.Array, quant=None):
    """Final norm and output projection of one sequence: (S, d) -> (S, V)."""
    return _mm(norm(m, x), unembed, quant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# Serving: the gap of a chosen token below the reference's best
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 2))
def _layer_w(m: Dims, key, quant, x, l):
    w = _f32(weights.layer_weights(m, key, l))
    return layer(m, x, w, quant)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_rows(m: Dims, key, tokens):
    return weights.embed(m, key)[tokens].astype(jnp.float32)


def hidden(m: Dims, seed: int, blocks: Sequence, quant=None) -> list:
    """Hidden states before the final norm, float32, of each block of
    token rows in ``blocks`` (each (b, S), on the device it should run
    on), computed layer by layer.  Blocks on different devices run side
    by side: pure data parallelism, with no exchange between them."""
    key = weights.seed_key(seed)
    xs = [_embed_rows(m, key, t) for t in blocks]
    for l in range(m.num_layers):
        xs = [_layer_w(m, key, quant, x, jnp.int32(l)) for x in xs]
    return xs


@functools.partial(jax.jit, static_argnums=(0, 5))
def _gaps(m: Dims, key, h32, chosen, h_ctrl, quant):
    u = weights.unembed(m, key).astype(jnp.float32)

    def one(args):
        x32, ch, xc = args
        l32 = logits(m, x32, u)
        best = jnp.max(l32, -1)
        gap = best - jnp.take_along_axis(l32, ch[:, None], -1)[:, 0]
        if xc is None:
            return gap, jnp.zeros_like(gap)
        top = jnp.argmax(logits(m, xc, u, quant), -1)
        cgap = best - jnp.take_along_axis(l32, top[:, None], -1)[:, 0]
        return gap, cgap

    return lax.map(one, (h32, chosen, h_ctrl))


def token_gaps(m: Dims, seed: int, blocks: Sequence, chosen: Sequence, *,
               control: bool = False):
    """For each position of each block of token rows: how far the logit
    of the token the program put there (``chosen``, same shapes) lies
    below the reference's best logit.  With ``control``, also the same
    gap of the token that the float8 control puts first (zeros
    without).  Returns two lists of (b, S) float32 arrays."""
    h32 = hidden(m, seed, blocks)
    hc = hidden(m, seed, blocks, "fp8") if control else [None] * len(h32)
    key = weights.seed_key(seed)
    out = [_gaps(m, key, h, c, x, "fp8" if control else None)
           for h, c, x in zip(h32, chosen, hc)]
    return [o[0] for o in out], [o[1] for o in out]


# ---------------------------------------------------------------------------
# Training: three AdamW steps, layer by layer
# ---------------------------------------------------------------------------

def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``peak_lr``, then cosine decay to ``min_lr``."""
    if step < opt["warmup_steps"]:
        return opt["peak_lr"] * step / max(opt["warmup_steps"], 1)
    t = (step - opt["warmup_steps"]) / max(
        opt["decay_steps"] - opt["warmup_steps"], 1)
    t = min(max(t, 0.0), 1.0)
    return opt["min_lr"] + 0.5 * (opt["peak_lr"] - opt["min_lr"]) * (
        1 + math.cos(math.pi * t))


def _ce(m: Dims, x, unembed, tokens, quant):
    """Mean next-token cross entropy of one block of sequences."""
    def one(args):
        xs, ts = args
        lg = logits(m, xs[:-1], unembed, quant)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, ts[1:, None], -1)[:, 0]
        return jnp.sum(lse - gold)

    return jnp.sum(lax.map(jax.checkpoint(one), (x, tokens)))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer_vjp(m: Dims, x, w, dy, quant):
    _, vjp = jax.vjp(lambda x, w: layer(m, x, w, quant), x, w)
    return vjp(dy)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _head_vjp(m: Dims, x, unembed, tokens, n_pred: int, quant):
    loss, vjp = jax.vjp(
        lambda x, u: _ce(m, x, u, tokens, quant) / n_pred, x, unembed)
    dx, du = vjp(jnp.float32(1.0))
    return loss, dx, du


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_fwd(m: Dims, x, w, quant):
    return layer(m, x, w, quant)


def _loss_and_grads(m: Dims, params: dict, tokens, quant, block: int):
    """Loss and gradients of every leaf, blocks of ``block`` sequences."""
    b = tokens.shape[0]
    n_pred = b * (tokens.shape[1] - 1)
    emb = params["embed"]
    un = emb.T if m.tie_embeddings else params["unembed"]
    grads = {k: jnp.zeros_like(v) for k, v in params.items()}
    loss = 0.0
    for i in range(0, b, block):
        tk = tokens[i:i + block]
        xs = [emb[tk]]
        for l in range(m.num_layers):
            w = {k: params[k][l] for k in weights.LAYER_LEAVES}
            xs.append(_layer_fwd(m, xs[-1], w, quant))
        part, dx, du = _head_vjp(m, xs[-1], un, tk, n_pred, quant)
        loss += float(part)
        if m.tie_embeddings:
            grads["embed"] = grads["embed"] + du.T
        else:
            grads["unembed"] = grads["unembed"] + du
        for l in reversed(range(m.num_layers)):
            w = {k: params[k][l] for k in weights.LAYER_LEAVES}
            dx, dw = _layer_vjp(m, xs[l], w, dx, quant)
            for k in weights.LAYER_LEAVES:
                grads[k] = grads[k].at[l].add(dw[k])
        grads["embed"] = grads["embed"].at[tk].add(dx)
    return loss, grads


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_f32(m: Dims, key, l):
    return _f32(weights.layer_weights(m, key, l))


def initial_params(m: Dims, seed: int) -> dict:
    """The seed's weights upcast to float32, in the benchmark's layout:
    ``embed`` (V, d), optional ``unembed`` (d, V), and each layer leaf
    stacked over layers."""
    key = weights.seed_key(seed)
    per = [_layer_f32(m, key, jnp.int32(l)) for l in range(m.num_layers)]
    params = {k: jnp.stack([p[k] for p in per]) for k in weights.LAYER_LEAVES}
    params["embed"] = weights.embed(m, key).astype(jnp.float32)
    if not m.tie_embeddings:
        params["unembed"] = weights.unembed(m, key).astype(jnp.float32)
    return params


def _norm2(a) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(a))))


def train_reference(m: Dims, seed: int, batches: Sequence, opt: dict,
                    quant=None, block: int = 1) -> dict:
    """AdamW (global-norm clipping, decoupled weight decay) for
    ``len(batches)`` steps from the seed's weights.  Returns the loss of
    each step, the norm of each leaf's first (clipped) gradient, and the
    norm of each leaf's change over all the steps."""
    params = initial_params(m, seed)
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    vel = {k: jnp.zeros_like(v) for k, v in params.items()}
    b1, b2 = opt["b1"], opt["b2"]
    losses, first = [], None
    for t, tokens in enumerate(batches, start=1):
        loss, g = _loss_and_grads(m, params, jnp.asarray(tokens), quant,
                                  block)
        losses.append(loss)
        gnorm = math.sqrt(sum(float(jnp.sum(jnp.square(v)))
                              for v in g.values()))
        scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        lr = lr_at(opt, t)
        for k in params:
            gk = g[k] * scale
            mom[k] = b1 * mom[k] + (1 - b1) * gk
            vel[k] = b2 * vel[k] + (1 - b2) * gk * gk
            mh = mom[k] / (1 - b1 ** t)
            vh = vel[k] / (1 - b2 ** t)
            params[k] = params[k] - lr * (
                mh / (jnp.sqrt(vh) + opt["eps"])
                + opt["weight_decay"] * params[k])
        if first is None:
            first = {k: _norm2(g[k] * scale) for k in g}
        del g
    p0 = initial_params(m, seed)
    change = {k: _norm2(params[k] - p0[k]) for k in params}
    return {"losses": losses, "grad_norms": first, "change_norms": change}

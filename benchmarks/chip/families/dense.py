"""The dense family: GQA attention, gated MLP, rotary positions, no biases.

Its two members here follow their published descriptions: OLMo
(arXiv:2402.00838: non-parametric LayerNorm, tied embeddings) and Yi
(arXiv:2403.04652: RMSNorm, grouped-query attention).  Everything the
benchmark knows of the family is in this file (``chipbench.family`` says
what a family provides):

* :class:`Dims`, the sizes, built from the configuration file alone, and
  :func:`program_config`, the program's own config object built from the
  same file.  The benchmark refuses a file whose sizes the program's
  registry would not run as written; this family refuses any that is not
  of the dense family.  Other families bring their own sizes and their
  own refusal, in files of their own.
* Weights made from ``--seed``, in the benchmark's own layout.  Every
  leaf is drawn from its own key, ``fold_in(fold_in(seed_key, leaf),
  layer)``, so one layer's weights can be made alone (the reference does,
  layer by layer) or all layers at once in one jitted call on the device
  (the program's parameters), with the same values either way.  Weights
  are drawn in float32 and served in the configuration's dtype; the
  reference upcasts those served values.
* The work a step requires, counted from shapes: FLOPs and HBM bytes.
  These are the counts that every ``mfu.*`` metric divides by, so they
  count what the algorithm needs and nothing the program chooses to add:
  no remat recompute, no masked attention blocks, no padding rows, no
  unused cache slots.
* The plain float32 reference and its low-precision control.  It imports
  nothing of the program and takes nothing the program made: it draws
  its own weights from the seed and upcasts them.  Departures from the
  published models, kept because the program makes the same choice and
  the comparison is not about them, are listed in PERF.md (norm
  epsilon, Yi's RoPE base).  Every product runs at
  ``Precision.HIGHEST`` (on a TPU, float32 products otherwise run in
  bfloat16 passes).  It runs layer by layer, and in blocks of sequences,
  so that it fits beside nothing else on the chip.  ``quant="fp8"`` is
  the control: every product's operands rounded to float8 (e4m3,
  per-tensor scale), the step below the bfloat16 the configurations
  state.  A comparison that passes this control is too loose.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.family import Work, seed_key
from chipbench.family import fp8 as _fp8  # noqa: F401  (the control's rounding)
from chipbench.family import mm as _mm

# ---------------------------------------------------------------------------
# Sizes, and the program's config object
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dims:
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    norm: str  # rmsnorm | nonparametric_ln
    norm_eps: float
    tie_embeddings: bool
    rope_theta: float
    dtype: str

    @classmethod
    def from_config(cls, cfg: dict[str, Any]) -> "Dims":
        m = cfg["model"]
        return cls(**{f.name: m[f.name] for f in dataclasses.fields(cls)})


# Keys of the file's "model" group that the program's config object holds
# under the same name.  The rest (norm_eps) are read by the reference
# only; the program fixes them in code.
_PROGRAM_KEYS = (
    "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
    "d_ff", "vocab_size", "norm", "tie_embeddings", "rope_theta", "dtype",
)


def dims(cfg: dict[str, Any]) -> Dims:
    """The sizes of a configuration file of this family."""
    return Dims.from_config(cfg)


def program_config(cfg: dict[str, Any]):
    """The program's ``ModelConfig`` for this file: the registry entry
    named by ``arch`` with the file's sizes and overlap settings."""
    from repro.configs import get_config
    from repro.configs.base import OverlapConfig

    base = get_config(cfg["arch"])
    m = cfg["model"]
    changes = {k: m[k] for k in _PROGRAM_KEYS}
    changes["overlap"] = OverlapConfig(**cfg["overlap"])
    changes.update(cfg.get("options", {}))
    out = dataclasses.replace(base, **changes)
    if out.resolved_head_dim != m["head_dim"]:
        raise ValueError(f"{cfg['arch']}: head_dim {out.resolved_head_dim}")
    if out.family.value != "dense" or out.moe or out.mla:
        raise ValueError(f"{cfg['arch']}: not of the dense family")
    return out


# ---------------------------------------------------------------------------
# Weights made from the seed
# ---------------------------------------------------------------------------

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down")
_LEAF_ID = {name: i for i, name in enumerate(
    ("embed", "unembed") + LAYER_LEAVES, start=1
)}
EMBED_STD = 0.02


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


# ---------------------------------------------------------------------------
# The work a step requires
# ---------------------------------------------------------------------------

BF16 = 2
F32 = 4


def layer_matmul_params(m: Dims) -> int:
    q = m.d_model * m.num_heads * m.head_dim
    kv = 2 * m.d_model * m.num_kv_heads * m.head_dim
    o = m.num_heads * m.head_dim * m.d_model
    mlp = 3 * m.d_model * m.d_ff
    return q + kv + o + mlp


def matmul_params(m: Dims) -> int:
    """Parameters that take part in a matrix product for every token:
    all layers and the output projection (the embedding lookup is not a
    product)."""
    return m.num_layers * layer_matmul_params(m) + m.vocab_size * m.d_model


def param_count(m: Dims) -> int:
    n = matmul_params(m)
    if not m.tie_embeddings:
        n += m.vocab_size * m.d_model  # the input embedding table
    return n


def _attn_pair_flops(m: Dims, pairs: float) -> float:
    """Scores and weighted values for ``pairs`` (query, key) pairs."""
    return m.num_layers * 2 * 2 * m.num_heads * m.head_dim * pairs


def forward(m: Dims, batch: int, seq: int) -> Work:
    """One causal forward over ``batch`` sequences of ``seq`` tokens."""
    tokens = batch * seq
    flops = 2.0 * tokens * matmul_params(m)
    flops += _attn_pair_flops(m, batch * seq * (seq + 1) / 2)
    return Work(flops, 0.0)


def train_step(m: Dims, batch: int, seq: int) -> Work:
    """Forward and backward (twice the forward), no recompute, and AdamW
    over bf16 weights and gradients with float32 moments.  Bytes: the
    weights read by the forward and the backward, the gradients written,
    and the optimizer's reads (weight, gradient, two moments) and writes
    (weight, two moments)."""
    fwd = forward(m, batch, seq)
    p = param_count(m)
    byts = p * (2 * BF16 + BF16) + p * (BF16 + BF16 + 2 * F32)
    byts += p * (BF16 + 2 * F32)
    return Work(3 * fwd.flops, float(byts))


def prefill(m: Dims, batch: int, seq: int) -> Work:
    """Forward; bytes are the weights read once and the logits written."""
    fwd = forward(m, batch, seq)
    byts = param_count(m) * BF16 + batch * seq * m.vocab_size * BF16
    return Work(fwd.flops, float(byts))


def kv_bytes_per_token(m: Dims) -> int:
    return m.num_layers * 2 * m.num_kv_heads * m.head_dim * BF16


def decode_step(m: Dims, positions: Iterable[int]) -> Work:
    """One decode step of the requests still active, each at its
    position (0-based) in its own sequence.  Bytes: every weight once,
    the cache entries up to each request's position, and the new entry
    written.  Finished or padding rows and unused slots do not count."""
    pos = list(positions)
    if not pos:
        return Work()
    n = len(pos)
    ctx = sum(p + 1 for p in pos)
    flops = 2.0 * n * matmul_params(m) + _attn_pair_flops(m, ctx)
    byts = matmul_params(m) * BF16 + kv_bytes_per_token(m) * (ctx + n)
    return Work(flops, float(byts))


def decode_batch(m: Dims, prompt_lens, new_tokens, steps: int) -> Work:
    """All ``steps`` decode steps of one static batch: request ``i`` is
    active from position 0 until it has emitted its last token, at
    position ``prompt_lens[i] + new_tokens[i] - 2``."""
    total = Work()
    for s in range(steps):
        total = total + decode_step(m, [
            s for p, n in zip(prompt_lens, new_tokens) if s <= p + n - 2
        ])
    return total


# ---------------------------------------------------------------------------
# The plain float32 reference
# ---------------------------------------------------------------------------

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
    w = _f32(layer_weights(m, key, l))
    return layer(m, x, w, quant)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_rows(m: Dims, key, tokens):
    return embed(m, key)[tokens].astype(jnp.float32)


def hidden(m: Dims, seed: int, blocks: Sequence, quant=None) -> list:
    """Hidden states before the final norm, float32, of each block of
    token rows in ``blocks`` (each (b, S), on the device it should run
    on), computed layer by layer.  Blocks on different devices run side
    by side: pure data parallelism, with no exchange between them."""
    key = seed_key(seed)
    xs = [_embed_rows(m, key, t) for t in blocks]
    for l in range(m.num_layers):
        xs = [_layer_w(m, key, quant, x, jnp.int32(l)) for x in xs]
    return xs


@functools.partial(jax.jit, static_argnums=(0, 5))
def _gaps(m: Dims, key, h32, chosen, h_ctrl, quant):
    u = unembed(m, key).astype(jnp.float32)

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
    key = seed_key(seed)
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
            w = {k: params[k][l] for k in LAYER_LEAVES}
            xs.append(_layer_fwd(m, xs[-1], w, quant))
        part, dx, du = _head_vjp(m, xs[-1], un, tk, n_pred, quant)
        loss += float(part)
        if m.tie_embeddings:
            grads["embed"] = grads["embed"] + du.T
        else:
            grads["unembed"] = grads["unembed"] + du
        for l in reversed(range(m.num_layers)):
            w = {k: params[k][l] for k in LAYER_LEAVES}
            dx, dw = _layer_vjp(m, xs[l], w, dx, quant)
            for k in LAYER_LEAVES:
                grads[k] = grads[k].at[l].add(dw[k])
        grads["embed"] = grads["embed"].at[tk].add(dx)
    return loss, grads


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_f32(m: Dims, key, l):
    return _f32(layer_weights(m, key, l))


def initial_params(m: Dims, seed: int) -> dict:
    """The seed's weights upcast to float32, in the benchmark's layout:
    ``embed`` (V, d), optional ``unembed`` (d, V), and each layer leaf
    stacked over layers."""
    key = seed_key(seed)
    per = [_layer_f32(m, key, jnp.int32(l)) for l in range(m.num_layers)]
    params = {k: jnp.stack([p[k] for p in per]) for k in LAYER_LEAVES}
    params["embed"] = embed(m, key).astype(jnp.float32)
    if not m.tie_embeddings:
        params["unembed"] = unembed(m, key).astype(jnp.float32)
    return params


def _norm2(a) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(a))))


def train_reference(m: Dims, seed: int, batches: Sequence, opt: dict,
                    quant=None, block: int = 1) -> dict:
    """AdamW (global-norm clipping, decoupled weight decay) for
    ``len(batches)`` steps from the seed's   Returns the loss of
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

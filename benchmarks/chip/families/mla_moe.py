"""The MLA + MoE family: latent attention with YaRN RoPE, a dense first
layer, then layers of routed experts beside shared ones.

Its member here is DeepSeek-V2-Lite (arXiv:2405.04434; its
``config.json`` on Hugging Face), as one chip of an expert-parallel
deployment holds it.  The configuration file keeps the published
``config.json`` keys at its top level; ``n_routed_experts`` there is the
number of routed experts this chip holds, from ``held_first``, of the
router's ``router_experts``.  Everything the benchmark knows of the
family is in this file (``chipbench.family`` says what a family
provides):

* :class:`Dims`, the sizes, built from the configuration file alone, and
  :func:`program_config`, the program's config object built from the
  same file.  A file that is not MLA and MoE, or that asks for what the
  program does not run (q compression, another scoring or top-k method,
  routed scaling, grouped routing, tied embeddings, biases, another
  activation or norm epsilon), is refused.
* Weights made from ``--seed``, one leaf at a time: ``fold_in(fold_in(
  seed_key, leaf), layer)``, and for a routed expert ``fold_in`` of its
  number over the whole layer, so a held share holds the uncut layer's
  experts.  Drawn in float32 and served in the configuration's dtype
  (the router in float32, as the program keeps it); the reference
  upcasts those served values.
* The work a step requires, counted from shapes: FLOPs and HBM bytes.
  Attention counts in its absorbed form (q times ``w_uk`` into the
  latent, scores and values over the latent and the rope key, the output
  times ``w_uv``) over the cache positions in use.  The routed experts
  count ``t 6 8/64`` assignments of a step's ``t`` tokens, and the
  weights of ``8 (1 - (58/64)^t)`` held experts: what uniform routing
  gives in expectation (assumed; the configuration says so).
* The plain float32 reference and its float8 control.  It imports
  nothing of the program and takes nothing the program made.  It runs
  layer by layer, and in blocks of sequences, every product at
  ``Precision.HIGHEST``; its MoE computes every held expert for every
  token and masks by the top-6, with the router's probabilities not
  renormalised.  Its departures from DeepSeek's published modelling
  code, each also in PERF.md: rotate-half RoPE (the published code
  de-interleaves q_pe and k_pe first: with random weights, a fixed
  permutation of the rope columns), RMSNorm scales of 1, random weights,
  and the experts of the other chips left out (as the program leaves
  them out).  ``quant="fp8"`` is the control: every product's operands
  rounded to float8 (e4m3, per-tensor scale).
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
from chipbench.family import mm as _mm

# The program's named scopes of this family's layers (``models/mla.py``,
# ``models/moe.py``), and the children of each.  The scope join
# (``chipbench.scopes``) knows the scopes of the layers the benchmark had
# before this family; it learns these when the family is loaded, which
# every run, scope reader and compile of the family's cells does first.
SCOPES = {"mla": ("q", "latent", "cache", "expand", "core", "o"),
          "moe": ("router", "dispatch", "experts", "combine", "shared")}


def _teach_scopes() -> None:
    from chipbench import scopes

    for top, children in SCOPES.items():
        if top not in scopes.TOP:
            scopes.TOP = scopes.TOP + (top,)
        scopes.CHILDREN.setdefault(top, children)


_teach_scopes()

# ---------------------------------------------------------------------------
# Sizes, and the program's config object
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Yarn:
    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


@dataclasses.dataclass(frozen=True)
class Dims:
    num_layers: int
    first_dense: int  # dense layers before the MoE ones
    d_model: int
    num_heads: int
    nope_dim: int  # q/k head, the part without rope
    rope_dim: int  # q/k head, the roped part; one shared key head
    kv_rank: int  # the latent
    v_dim: int
    d_ff: int  # the dense layer's width
    d_expert: int  # one expert's width
    router_experts: int  # the router's width
    held_first: int  # the experts this chip holds: held_first ..
    held: int  # .. held_first + held - 1
    shared: int  # shared experts
    top_k: int
    norm_topk: bool
    vocab_size: int
    norm_eps: float
    rope_theta: float
    yarn: Yarn
    dtype: str

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim


# The published keys this family runs only at one value: anything else is
# a mechanism the program does not have.
_FIXED = {
    "q_lora_rank": None, "scoring_func": "softmax", "topk_method": "greedy",
    "routed_scaling_factor": 1, "n_group": 1, "topk_group": 1,
    "moe_layer_freq": 1, "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
}


def dims(cfg: dict[str, Any]) -> Dims:
    """The sizes of a configuration file of this family."""
    y = cfg["rope_scaling"]
    if y.get("type") != "yarn":
        raise ValueError(f"rope_scaling {y!r}: YaRN only")
    heads = cfg["num_attention_heads"]
    if cfg["num_key_value_heads"] != heads:
        raise ValueError("MLA has one key per head")
    return Dims(
        num_layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], num_heads=heads,
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        kv_rank=cfg["kv_lora_rank"], v_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        router_experts=cfg["router_experts"], held_first=cfg["held_first"],
        held=cfg["n_routed_experts"], shared=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        vocab_size=cfg["vocab_size"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        yarn=Yarn(float(y["factor"]),
                  int(y["original_max_position_embeddings"]),
                  float(y["beta_fast"]), float(y["beta_slow"]),
                  float(y["mscale"]), float(y["mscale_all_dim"])),
        dtype=cfg["dtype"],
    )


def program_config(cfg: dict[str, Any]):
    """The program's ``ModelConfig`` for this file: the registry entry
    named by ``arch`` with the file's sizes, its held experts and its
    overlap settings."""
    from repro.configs import get_config
    from repro.configs.base import (MLAConfig, MoEConfig, OverlapConfig,
                                    YarnScaling)

    for k, v in _FIXED.items():
        if cfg.get(k) != v:
            raise ValueError(f"{cfg['arch']}: {k} = {cfg.get(k)!r}, the "
                             f"program runs {v!r} only")
    m = dims(cfg)
    if not 0 <= m.held_first <= m.held_first + m.held <= m.router_experts:
        raise ValueError(f"experts {m.held_first} + {m.held} of "
                         f"{m.router_experts}")
    base = get_config(cfg["arch"])
    y = m.yarn
    out = dataclasses.replace(
        base, num_layers=m.num_layers, d_model=m.d_model,
        num_heads=m.num_heads, num_kv_heads=m.num_heads, d_ff=m.d_ff,
        vocab_size=m.vocab_size, rope_theta=m.rope_theta, dtype=m.dtype,
        norm="rmsnorm", tie_embeddings=False,
        mla=MLAConfig(
            kv_lora_rank=m.kv_rank, q_lora_rank=0, rope_head_dim=m.rope_dim,
            nope_head_dim=m.nope_dim, v_head_dim=m.v_dim,
            rope_scaling=YarnScaling(
                factor=y.factor, original_max_position=y.original_max_position,
                beta_fast=y.beta_fast, beta_slow=y.beta_slow,
                mscale=y.mscale, mscale_all_dim=y.mscale_all_dim),
        ),
        moe=MoEConfig(
            num_experts=m.router_experts, top_k=m.top_k,
            num_shared_experts=m.shared, d_ff_expert=m.d_expert,
            first_dense_layers=m.first_dense, norm_topk_prob=m.norm_topk,
            capacity_factor=None, held_experts=(m.held_first, m.held),
        ),
        overlap=OverlapConfig(**cfg["overlap"]),
        **cfg.get("options", {}),
    )
    if out.family.value != "moe" or out.hybrid or out.encdec:
        raise ValueError(f"{cfg['arch']}: not of the MLA + MoE family")
    return out


# ---------------------------------------------------------------------------
# Weights made from the seed
# ---------------------------------------------------------------------------

ATTN_LEAVES = ("wq", "w_dkv", "w_kr", "w_uk", "w_uv", "wo")
MLP_LEAVES = ("w_up", "w_gate", "w_down")
EXPERT_LEAVES = ("e_up", "e_gate", "e_down")
SHARED_LEAVES = ("s_up", "s_gate", "s_down")
_LEAF_ID = {name: i for i, name in enumerate(
    ("embed", "unembed", "router") + ATTN_LEAVES + MLP_LEAVES
    + EXPERT_LEAVES + SHARED_LEAVES, start=1)}
EMBED_STD = 0.02


def attn_shapes(m: Dims) -> dict[str, tuple[int, int]]:
    h = m.num_heads
    return {
        "wq": (m.d_model, h * m.qk_dim), "w_dkv": (m.d_model, m.kv_rank),
        "w_kr": (m.d_model, m.rope_dim), "w_uk": (m.kv_rank, h * m.nope_dim),
        "w_uv": (m.kv_rank, h * m.v_dim), "wo": (h * m.v_dim, m.d_model),
    }


def _mlp_shapes(d: int, ff: int) -> dict[str, tuple[int, int]]:
    return {"up": (d, ff), "gate": (d, ff), "down": (ff, d)}


def _draw(key, leaf: str, layer, shape, std: float, dtype,
          expert=None) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_ID[leaf]), layer)
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _std(shape) -> float:
    return 1.0 / math.sqrt(shape[0])


def attn_weights(m: Dims, key, layer) -> dict[str, jax.Array]:
    dt = jnp.dtype(m.dtype)
    return {n: _draw(key, n, layer, s, _std(s), dt)
            for n, s in attn_shapes(m).items()}


def dense_weights(m: Dims, key, layer) -> dict[str, jax.Array]:
    dt = jnp.dtype(m.dtype)
    return {f"w_{n}": _draw(key, f"w_{n}", layer, s, _std(s), dt)
            for n, s in _mlp_shapes(m.d_model, m.d_ff).items()}


def moe_weights(m: Dims, key, layer, experts=None) -> dict[str, jax.Array]:
    """One MoE layer: the router (float32), the routed experts numbered
    ``experts`` over the whole layer (default: the held ones), stacked,
    and the shared experts as one MLP of ``shared`` times the width."""
    dt = jnp.dtype(m.dtype)
    if experts is None:
        experts = jnp.arange(m.held_first, m.held_first + m.held)
    out = {"router": _draw(key, "router", layer,
                           (m.d_model, m.router_experts),
                           1.0 / math.sqrt(m.d_model), jnp.float32)}
    for n, s in _mlp_shapes(m.d_model, m.d_expert).items():
        out[f"e_{n}"] = jax.vmap(lambda e, n=n, s=s: _draw(
            key, f"e_{n}", layer, s, _std(s), dt, expert=e))(experts)
    for n, s in _mlp_shapes(m.d_model, m.d_expert * m.shared).items():
        out[f"s_{n}"] = _draw(key, f"s_{n}", layer, s, _std(s), dt)
    return out


def embed(m: Dims, key) -> jax.Array:
    return _draw(key, "embed", 0, (m.vocab_size, m.d_model), EMBED_STD,
                 jnp.dtype(m.dtype))


def unembed(m: Dims, key) -> jax.Array:
    return _draw(key, "unembed", 0, (m.d_model, m.vocab_size), EMBED_STD,
                 jnp.dtype(m.dtype))


def program_params(m: Dims, key: jax.Array) -> dict:
    """The program's parameter tree: the dense first layers under
    ``lead``, the MoE layers under ``layers``, each stacked on a leading
    axis, made from ``seed_key(seed)``.  Jit it with ``m`` static."""
    dt = jnp.dtype(m.dtype)
    ones = functools.partial(jnp.ones, dtype=dt)

    def stacked(make, layers):
        return jax.vmap(lambda l: make(m, key, l))(layers)

    def attn(w, n):
        return {**{k: w[k] for k in ATTN_LEAVES},
                "kv_norm": {"scale": ones((n, m.kv_rank))}}

    def norm(n):
        return {"scale": ones((n, m.d_model))}

    n_lead, n_moe = m.first_dense, m.num_layers - m.first_dense
    moe = jnp.arange(n_lead, m.num_layers)
    ma, mw = stacked(attn_weights, moe), stacked(moe_weights, moe)
    params = {
        "embed": embed(m, key),
        "unembed": unembed(m, key),
        "final_norm": {"scale": ones((m.d_model,))},
        "layers": [{
            "norm1": norm(n_moe), "attn": attn(ma, n_moe),
            "norm2": norm(n_moe),
            "ffn": {"router": mw["router"], "w_up": mw["e_up"],
                    "w_gate": mw["e_gate"], "w_down": mw["e_down"],
                    "shared": {"w_up": mw["s_up"], "w_gate": mw["s_gate"],
                               "w_down": mw["s_down"]}},
        }],
    }
    if n_lead:
        lead = jnp.arange(n_lead)
        params["lead"] = [{
            "norm1": norm(n_lead),
            "attn": attn(stacked(attn_weights, lead), n_lead),
            "norm2": norm(n_lead), "ffn": stacked(dense_weights, lead)}]
    return params


# ---------------------------------------------------------------------------
# The work a step requires
# ---------------------------------------------------------------------------

BF16 = 2
F32 = 4


def attn_params(m: Dims) -> int:
    return sum(a * b for a, b in attn_shapes(m).values())


def expert_params(m: Dims) -> int:
    return 3 * m.d_model * m.d_expert


def held_reached(m: Dims, tokens: int) -> float:
    """Held experts that ``tokens`` tokens reach, in expectation under
    uniform routing: each misses a given expert with probability
    (E - k) / E."""
    e = m.router_experts
    return m.held * (1.0 - ((e - m.top_k) / e) ** tokens)


def _attn_token_flops(m: Dims) -> float:
    """A token's attention projections: q, the latent and rope key, then
    either q_nope times ``w_uk`` into the latent and the context times
    ``w_uv`` (the absorbed form, decode) or the key and value expanded
    from the latent (prefill); one multiply-add per weight either way."""
    return 2.0 * attn_params(m)


def _attn_pair_flops(m: Dims) -> float:
    """Scores over latent and rope key, and the latent's weighted sum,
    for one (query, key) pair of one layer."""
    return 2.0 * m.num_heads * (2 * m.kv_rank + m.rope_dim)


def _moe_token_flops(m: Dims) -> float:
    """Router and shared experts, for every token of a MoE layer."""
    return 2.0 * (m.d_model * m.router_experts + m.shared * expert_params(m))


def _routed_flops(m: Dims, tokens: int) -> float:
    """The held experts' share of ``tokens`` tokens' assignments."""
    return (tokens * m.top_k * m.held / m.router_experts
            * 2.0 * expert_params(m))


def _weight_bytes(m: Dims, tokens: int) -> float:
    """Weights a pass over ``tokens`` tokens reads: all but the routed
    experts (the router in float32), and the held experts it reaches."""
    n_moe = m.num_layers - m.first_dense
    dense = m.first_dense * 3 * m.d_model * m.d_ff
    per_moe = m.shared * expert_params(m)
    byts = (m.num_layers * attn_params(m) + dense + n_moe * per_moe
            + m.d_model * m.vocab_size) * BF16
    byts += n_moe * m.d_model * m.router_experts * F32
    byts += n_moe * held_reached(m, tokens) * expert_params(m) * BF16
    return byts


def kv_bytes_per_token(m: Dims) -> int:
    """The latent and rope key of one position, over all layers."""
    return m.num_layers * (m.kv_rank + m.rope_dim) * BF16


def _token_flops(m: Dims) -> float:
    """Every token's projections and FFNs, without attention pairs and
    routed experts."""
    n_moe = m.num_layers - m.first_dense
    return (m.num_layers * _attn_token_flops(m)
            + m.first_dense * 2.0 * 3 * m.d_model * m.d_ff
            + n_moe * _moe_token_flops(m)
            + 2.0 * m.d_model * m.vocab_size)


def decode_step(m: Dims, positions: Iterable[int]) -> Work:
    """One decode step of the requests still active, each at its
    position (0-based).  Bytes: the weights it reads, the latent cache up
    to each request's position, and the new entry written."""
    pos = list(positions)
    if not pos:
        return Work()
    n = len(pos)
    ctx = sum(p + 1 for p in pos)
    n_moe = m.num_layers - m.first_dense
    flops = (n * _token_flops(m) + n_moe * _routed_flops(m, n)
             + m.num_layers * _attn_pair_flops(m) * ctx)
    byts = _weight_bytes(m, n) + kv_bytes_per_token(m) * (ctx + n)
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


def prefill(m: Dims, batch: int, seq: int) -> Work:
    """One causal forward over ``batch`` sequences of ``seq`` tokens,
    keys and values expanded from the latent (the form a prompt takes);
    bytes: the weights read once and the logits written."""
    t = batch * seq
    n_moe = m.num_layers - m.first_dense
    pairs = batch * seq * (seq + 1) / 2
    flops = (t * _token_flops(m) + n_moe * _routed_flops(m, t)
             + m.num_layers * 2.0 * m.num_heads * (m.qk_dim + m.v_dim)
             * pairs)
    byts = _weight_bytes(m, t) + t * m.vocab_size * BF16
    return Work(flops, float(byts))


# ---------------------------------------------------------------------------
# The plain float32 reference
# ---------------------------------------------------------------------------

def rms(m: Dims, x: jax.Array) -> jax.Array:
    """RMSNorm with its scale of 1 (as initialised)."""
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m.norm_eps)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(m: Dims) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies of the rope dimensions."""
    y, d, base = m.yarn, m.rope_dim, m.rope_theta

    def corr(turns):
        return (d * math.log(y.original_max_position
                             / (turns * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    return (extra / y.factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(m: Dims) -> float:
    y = m.yarn
    return m.qk_dim ** -0.5 * yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def rope(m: Dims, x: jax.Array) -> jax.Array:
    """YaRN rotate-half RoPE over positions 0..S-1; x: (S, heads, d)."""
    s, _, d = x.shape
    y = m.yarn
    scale = yarn_mscale(y.factor, y.mscale) / yarn_mscale(
        y.factor, y.mscale_all_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(m)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(m: Dims, h: jax.Array, w: dict, quant) -> jax.Array:
    """Causal MLA over one sequence, h: (S, d) normed; keys and values
    expanded from the normed latent."""
    s = h.shape[0]
    hd = m.num_heads
    q = _mm(h, w["wq"], quant).reshape(s, hd, m.qk_dim)
    q_nope, q_rope = q[..., :m.nope_dim], rope(m, q[..., m.nope_dim:])
    c = rms(m, _mm(h, w["w_dkv"], quant))  # (S, r)
    k_rope = rope(m, _mm(h, w["w_kr"], quant)[:, None, :])[:, 0]  # (S, dr)
    k_nope = _mm(c, w["w_uk"], quant).reshape(s, hd, m.nope_dim)
    v = _mm(c, w["w_uv"], quant).reshape(s, hd, m.v_dim)
    mask = np.tril(np.ones((s, s), bool))

    def head(args):
        qn, qr, kn, vv = args  # (S, dn), (S, dr), (S, dn), (S, dv)
        scores = (_mm(qn, kn.T, quant) + _mm(qr, k_rope.T, quant)) \
            * softmax_scale(m)
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return _mm(p, vv, quant)

    out = lax.map(head, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
                         k_nope.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return _mm(out.transpose(1, 0, 2).reshape(s, hd * m.v_dim), w["wo"],
               quant)


def mlp(h, up, gate, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def routed(m: Dims, h: jax.Array, w: dict, quant,
           first: int | None = None) -> jax.Array:
    """The routed experts' part of a MoE layer for tokens h: (T, d): the
    router's softmax over all experts in float32, greedy top-k, weights
    not renormalised (unless the file says so); the experts of ``w``
    (numbered from ``first``, default the held ones) each computed for
    every token and masked by the top-k."""
    first = m.held_first if first is None else first
    probs = jax.nn.softmax(_mm(h, w["router"], quant), axis=-1)  # (T, E)
    top_w, top_e = lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    n = w["e_up"].shape[0]
    ids = first + jnp.arange(n)
    gate = jnp.sum(jnp.where(top_e[:, :, None] == ids, top_w[:, :, None],
                             0.0), axis=1)  # (T, n)

    def one(args):
        up, g, down, wt = args
        return mlp(h, up, g, down, quant) * wt[:, None]

    return jnp.sum(lax.map(one, (w["e_up"], w["e_gate"], w["e_down"],
                                 gate.T)), axis=0)


def shared(h, w, quant):
    return mlp(h, w["s_up"], w["s_gate"], w["s_down"], quant)


def layer(m: Dims, x: jax.Array, w: dict, moe: bool, quant=None):
    """One decoder layer over a block of sequences; x: (b, S, d) f32."""
    b, s, d = x.shape
    x = x + lax.map(lambda h: attention(m, h, w, quant), rms(m, x))
    h = rms(m, x).reshape(b * s, d)
    if moe:
        y = routed(m, h, w, quant) + shared(h, w, quant)
    else:
        y = mlp(h, w["w_up"], w["w_gate"], w["w_down"], quant)
    return x + y.reshape(b, s, d)


def logits(m: Dims, x: jax.Array, unembed: jax.Array, quant=None):
    """Final norm and output projection of one sequence: (S, d) -> (S, V)."""
    return _mm(rms(m, x), unembed, quant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def layer_weights(m: Dims, key, l: int) -> dict:
    """Layer ``l``'s weights in the served dtypes (router float32)."""
    w = attn_weights(m, key, l)
    w.update(dense_weights(m, key, l) if l < m.first_dense
             else moe_weights(m, key, l))
    return w


# ---------------------------------------------------------------------------
# Serving: the gap of a chosen token below the reference's best
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _layer_w(m: Dims, key, quant, moe: bool, x, l):
    w = attn_weights(m, key, l)
    w.update(moe_weights(m, key, l) if moe else dense_weights(m, key, l))
    return layer(m, x, _f32(w), moe, quant)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_rows(m: Dims, key, tokens):
    return embed(m, key)[tokens].astype(jnp.float32)


def hidden(m: Dims, seed: int, blocks: Sequence, quant=None) -> list:
    """Hidden states before the final norm, float32, of each block of
    token rows in ``blocks`` (each (b, S)), computed layer by layer."""
    key = seed_key(seed)
    xs = [_embed_rows(m, key, t) for t in blocks]
    for l in range(m.num_layers):
        moe = l >= m.first_dense
        xs = [_layer_w(m, key, quant, moe, x, jnp.int32(l)) for x in xs]
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

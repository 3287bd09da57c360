"""Transformer building blocks: norms, RoPE, chunked attention (GQA / MLA),
gated MLP, and scatter-based MoE with capacity dropping.

All forwards are pure functions over parameter dicts built from
:class:`~repro.sharding.partitioning.ParamSpec` templates. Attention is
q-chunked (exact softmax, memory O(chunk x kv_len)) so 32k-token prefill
lowers without materialising S x S score matrices; the Pallas flash kernel
(`repro.kernels.flash_attention`) is the TPU fast path selected via
``ModelConfig.attention_impl``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.sharding.partitioning import ParamSpec, hint

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (half-rotation / llama style)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                 # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (...,S,d/2)
    cos = jnp.cos(angles)[..., None, :]                          # (...,S,1,d/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core: q-chunked exact attention, GQA aware
# ---------------------------------------------------------------------------

def _attend_chunk(q, k, v, q_pos, k_pos, causal, window):
    """q: (B,Cq,KV,G,hd)  k,v: (B,T,KV,hd)  -> (B,Cq,KV,G,hd).

    q_pos: (Cq,) shared positions, or (B,Cq) per-sequence positions
    (continuous batching decodes sequences at different depths).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqkgd,btkd->bqkgt", q, k,
                        preferred_element_type=jnp.float32) * scale
    qp = q_pos[..., :, None]                   # (Cq,1) or (B,Cq,1)
    kp = k_pos[None, :]
    mask = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        mask &= qp >= kp
    if window and window > 0:
        mask &= (qp - kp) < window
    if mask.ndim == 2:
        mask = mask[None, :, None, None, :]
    else:                                      # batched positions
        mask = mask[:, :, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqkgt,btkd->bqkgd", probs.astype(v.dtype), v)
    return out


def chunked_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                      chunk=512):
    """q: (B,S,H,hd), k/v: (B,T,KV,hd). Exact attention, scanned over q chunks.

    q_offset: absolute position of q[0] relative to k[0] (decode: T_cache).
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    vd = v.shape[-1]                      # v head dim may differ (MLA)
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    k_pos = jnp.arange(T)
    offset_arr = jnp.asarray(q_offset)
    if S <= chunk or S % chunk != 0:
        q_pos = offset_arr[..., None] + jnp.arange(S)  # (S,) or (B,S)
        out = _attend_chunk(qg, k, v, q_pos, k_pos, causal, window)
        return out.reshape(B, S, H, vd)

    n_chunks = S // chunk
    qg = qg.reshape(B, n_chunks, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def body(_, inputs):
        qc, start = inputs
        q_pos = q_offset + start + jnp.arange(chunk)
        return None, _attend_chunk(qc, k, v, q_pos, k_pos, causal, window)

    starts = jnp.arange(n_chunks) * chunk
    _, out = lax.scan(body, None, (qg, starts))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, vd)
    return out


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def gqa_template(cfg: ModelConfig, cross=False) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    t = {
        "wq": ParamSpec((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, D), ("heads", "head_dim", "embed"),
                        "scaled_normal"),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), "zeros")
        t["bk"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), "zeros")
        t["bv"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), "zeros")
    return t


def gqa_project_qkv(p, x, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def gqa_attention(p, x, cfg: ModelConfig, *, positions=None, causal=None,
                  window=None, rope=True):
    """Full-sequence (train / prefill) GQA self-attention."""
    B, S, D = x.shape
    q, k, v = gqa_project_qkv(p, x, cfg)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    causal = cfg.causal if causal is None else causal
    window = cfg.sliding_window if window is None else window
    if cfg.context_parallel_attention:
        # shard query positions over the model axis; K/V replicated there
        q = hint(q, ("batch", "qseq", None, None))
        k = hint(k, ("batch", None, None, None))
        v = hint(v, ("batch", None, None, None))
    if cfg.attention_impl == "pallas":
        from repro.kernels.ops import flash_attention_bshd
        out = flash_attention_bshd(q, k, v, causal=causal, window=window)
    else:
        out = chunked_attention(q, k, v, causal=causal, window=window)
    if cfg.context_parallel_attention:
        out = hint(out, ("batch", "qseq", None, None))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k, v)


def gqa_decode(p, x, cache_k, cache_v, cfg: ModelConfig, *, t_cache: int,
               window=None, rope=True):
    """One-token decode against a full KV cache of length t_cache."""
    q, k_new, v_new = gqa_project_qkv(p, x, cfg)       # (B,1,?,hd)
    pos = jnp.full((x.shape[0], 1), t_cache)
    if rope:
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
    k = jnp.concatenate([cache_k, k_new], axis=1)
    v = jnp.concatenate([cache_v, v_new], axis=1)
    window = cfg.sliding_window if window is None else window
    out = chunked_attention(q, k, v, causal=True, window=window,
                            q_offset=t_cache)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k_new, v_new)


def cross_attention(p, x, enc_kv, cfg: ModelConfig):
    """Decoder cross-attention over precomputed encoder K/V."""
    k, v = enc_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    out = chunked_attention(q, k, v, causal=False, window=0)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention) — MiniCPM3 / DeepSeek-V3
# ---------------------------------------------------------------------------

def mla_template(cfg: ModelConfig) -> dict:
    D, H = cfg.d_model, cfg.num_heads
    m = cfg.mla
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    t = {}
    if m.q_lora_rank:
        t["wq_a"] = ParamSpec((D, m.q_lora_rank), ("embed", "latent"))
        t["q_norm"] = ParamSpec((m.q_lora_rank,), (None,), "ones")
        t["wq_b"] = ParamSpec((m.q_lora_rank, H, qk), ("latent", "heads", None))
    else:
        t["wq"] = ParamSpec((D, H, qk), ("embed", "heads", None))
    t["wkv_a"] = ParamSpec((D, m.kv_lora_rank + m.qk_rope_head_dim),
                           ("embed", "latent"))
    t["kv_norm"] = ParamSpec((m.kv_lora_rank,), (None,), "ones")
    t["wkv_b"] = ParamSpec((m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim),
                           ("latent", "heads", None))
    t["wo"] = ParamSpec((H, m.v_head_dim, D), ("heads", None, "embed"),
                        "scaled_normal")
    return t


def _mla_q(p, x, m: MLAConfig, cfg, positions):
    if m.q_lora_rank:
        qa = rmsnorm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", qa, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_attention(p, x, cfg: ModelConfig, *, positions=None):
    """Expanded (train / prefill) MLA. Returns output and latent cache entry."""
    B, S, D = x.shape
    m = cfg.mla
    H = cfg.num_heads
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _mla_q(p, x, m, cfg, positions)

    kv_a = x @ p["wkv_a"]                                   # (B,S,latent+rope)
    c_kv = rmsnorm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., None, m.kv_lora_rank:], positions,
                        cfg.rope_theta)                     # (B,S,1,rope)
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"])
    k_nope = kv[..., :m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]

    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (B, S, H, m.qk_rope_head_dim))], axis=-1)
    out = chunked_attention(q, k, v, causal=cfg.causal)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    cache = jnp.concatenate([c_kv, kv_a[..., m.kv_lora_rank:]], axis=-1)
    return y, cache


def mla_decode(p, x, cache, cfg: ModelConfig, *, t_cache: int):
    """Absorbed one-token MLA decode against a latent cache.

    cache: (B, T, kv_lora + rope_dim) — the whole point of MLA: the per-token
    cache is the low-rank latent + shared rope key, not per-head K/V.
    """
    B = x.shape[0]
    m = cfg.mla
    H = cfg.num_heads
    pos = jnp.full((B, 1), t_cache)
    q_nope, q_rope = _mla_q(p, x, m, cfg, pos)              # (B,1,H,*)

    kv_a = x @ p["wkv_a"]
    c_new = rmsnorm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    kr_new = apply_rope(kv_a[..., None, m.kv_lora_rank:], pos, cfg.rope_theta)
    new_entry = jnp.concatenate([c_new, kr_new[:, :, 0, :]], axis=-1)
    cache = jnp.concatenate([cache, new_entry], axis=1)     # (B,T+1,...)

    c = cache[..., :m.kv_lora_rank]                         # (B,T+1,r)
    k_rope = cache[..., m.kv_lora_rank:]                    # (B,T+1,rope)

    wk = p["wkv_b"][..., :m.qk_nope_head_dim]               # (r,H,nope)
    wv = p["wkv_b"][..., m.qk_nope_head_dim:]               # (r,H,v)
    # absorb k up-projection into q: q_lat (B,1,H,r)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (jnp.einsum("bshr,btr->bsht", q_lat, c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshk,btk->bsht", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bsht,btr->bshr", probs.astype(c.dtype), c)
    o = jnp.einsum("bshr,rhk->bshk", o_lat, wv)             # (B,1,H,v)
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return y, new_entry


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------

def mlp_template(d_model: int, d_ff: int) -> dict:
    return {
        "wi": ParamSpec((d_model, d_ff), ("embed", "mlp")),
        "wg": ParamSpec((d_model, d_ff), ("embed", "mlp")),
        "wo": ParamSpec((d_ff, d_model), ("mlp", "embed"), "scaled_normal"),
    }


def mlp(p, x):
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# MoE: top-k routing with capacity dropping, scatter-based dispatch
# ---------------------------------------------------------------------------

def moe_template(cfg: ModelConfig) -> dict:
    D = cfg.d_model
    m = cfg.moe
    t = {
        "router": ParamSpec((D, m.num_experts), ("embed", None)),
        "wi": ParamSpec((m.num_experts, D, m.d_expert),
                        ("experts", "embed", None)),
        "wg": ParamSpec((m.num_experts, D, m.d_expert),
                        ("experts", "embed", None)),
        "wo": ParamSpec((m.num_experts, m.d_expert, D),
                        ("experts", None, "embed"), "scaled_normal"),
    }
    if m.num_shared_experts:
        t["shared"] = mlp_template(D, m.d_expert * m.num_shared_experts)
    return t


def _capacity(tokens: int, m: MoEConfig) -> int:
    c = math.ceil(tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(8, -(-c // 8) * 8)   # round up to a multiple of 8


def moe_ffn(p, x, cfg: ModelConfig):
    """x: (B,S,D) -> (y, aux_loss). Scatter-based dispatch: no (T,E,C) one-hot
    is ever materialised (critical at T ~ 1M tokens for deepseek-v3)."""
    B, S, D = x.shape
    m = cfg.moe
    T = B * S
    E, K = m.num_experts, m.top_k
    C = _capacity(T, m)
    xt = x.reshape(T, D)

    logits = (xt @ p["router"]).astype(jnp.float32)         # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = lax.top_k(probs, K)                         # (T,K)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-9)

    # position of each assignment within its expert (stable sort by expert id)
    flat_e = idx.reshape(-1)                                # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)                 # (E,)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * K) - starts[sorted_e]
    keep = pos_in_e < C                                     # capacity dropping
    src_tok = order // K

    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # E*C = drop slot
    buf = jnp.zeros((E * C + 1, D), xt.dtype).at[dest].set(xt[src_tok])
    # expert-parallel layout: dispatch buffer sharding must agree with the
    # expert weights' (workload-dependent, §Perf 1b/1c)
    e_ax = "experts_both" if cfg.expert_parallel == "both" else "experts"
    h = hint(buf[:-1].reshape(E, C, D), (e_ax, None, None))

    hh = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", h, p["wi"])
    y_e = hint(jnp.einsum("ecf,efd->ecd", hh, p["wo"]),
               (e_ax, None, None)).reshape(E * C, D)

    gath = jnp.where(keep[:, None], y_e[jnp.clip(dest, 0, E * C - 1)], 0.0)
    w = gate.reshape(-1)[order][:, None].astype(xt.dtype)
    y = jnp.zeros((T, D), xt.dtype).at[src_tok].add(gath * w)

    # load-balance aux loss (Switch/GShard form): E * sum_e f_e * P_e
    f = jnp.bincount(flat_e, length=E).astype(jnp.float32) / (T * K)
    pmean = jnp.mean(probs, axis=0)
    aux = m.router_aux_coef * E * jnp.sum(f * pmean)

    y = y.reshape(B, S, D)
    if m.num_shared_experts:
        y = y + mlp(p["shared"], x)
    return y, aux


def moe_ffn_shard_map(p, x, cfg: ModelConfig):
    """MoE FFN with a hand-written expert-parallel schedule (§Perf follow-up).

    shard_map manual over the 'model' axis: every shard owns E/n_shards
    experts, tokens are replicated across that axis, so dispatch is a purely
    LOCAL scatter (each shard picks the assignments routed to its experts)
    and the only collective is one activation-sized psum of the combined
    output — instead of the weight/buffer gathers GSPMD lowers the auto
    version to. Falls back to :func:`moe_ffn` off-mesh or when the expert
    count does not divide the axis.
    """
    from repro.sharding.partitioning import current_mesh
    mesh = current_mesh()
    m = cfg.moe
    E = m.num_experts
    if (mesh is None or "model" not in mesh.shape
            or E % mesh.shape["model"] != 0):
        return moe_ffn(p, x, cfg)
    n_sh = mesh.shape["model"]
    E_loc = E // n_sh
    B, S, D = x.shape
    T = B * S
    K = m.top_k
    C = _capacity(T, m)
    from jax.sharding import PartitionSpec as P_

    def local(wi, wg, wo, router, xt):
        # wi/wg/wo: (E_loc, ...) this shard's experts; xt replicated (T, D)
        sh = lax.axis_index("model")
        logits = (xt @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = lax.top_k(probs, K)
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-9)

        flat_e = idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(T * K) - starts[sorted_e]
        mine = (sorted_e >= sh * E_loc) & (sorted_e < (sh + 1) * E_loc)
        keep = (pos_in_e < C) & mine
        src_tok = order // K
        local_e = sorted_e - sh * E_loc
        dest = jnp.where(keep, local_e * C + pos_in_e, E_loc * C)
        buf = jnp.zeros((E_loc * C + 1, D), xt.dtype).at[dest].set(
            xt[src_tok])
        h = buf[:-1].reshape(E_loc, C, D)
        hh = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg)) * \
            jnp.einsum("ecd,edf->ecf", h, wi)
        y_e = jnp.einsum("ecf,efd->ecd", hh, wo).reshape(E_loc * C, D)
        gath = jnp.where(keep[:, None],
                         y_e[jnp.clip(dest, 0, E_loc * C - 1)], 0.0)
        w = gate.reshape(-1)[order][:, None].astype(xt.dtype)
        y = jnp.zeros((T, D), xt.dtype).at[src_tok].add(gath * w)
        y = lax.psum(y, "model")          # the only collective
        f = counts.astype(jnp.float32) / (T * K)
        aux = m.router_aux_coef * E * jnp.sum(f * jnp.mean(probs, axis=0))
        return y, aux

    fn = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(P_("model"), P_("model"), P_("model"), P_(), P_()),
        out_specs=(P_(), P_()))
    y, aux = fn(p["wi"], p["wg"], p["wo"], p["router"],
                x.reshape(T, D))
    y = y.reshape(B, S, D)
    if m.num_shared_experts:
        y = y + mlp(p["shared"], x)
    return y, aux

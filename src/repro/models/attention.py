"""GQA attention: projections, chunked online-softmax, KV caches, and the
sequence-sharded split-KV decode combine (FlashDecoding adapted to the mesh).

Memory discipline: prefill/train attention over long sequences uses a
lax.scan over KV chunks with running (max, denom, acc) statistics — exact
softmax with O(S * chunk) live memory instead of O(S^2), which is what lets
the 32k-prefill cells compile within a v5e's HBM without a fused kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import apply_rope, init_linear

NEG_INF = -1.0e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    rope_theta: float = 10000.0
    window: Optional[int] = None     # sliding-window size (None = global)
    causal: bool = True
    q_scale: Optional[float] = None  # default 1/sqrt(head_dim)
    kv_chunk: int = 1024             # online-softmax chunk length


def init_attention(key, cfg: AttnConfig, dtype):
    kq, kk, kv, ko = jax.random.split(key, 4)
    params, specs = {}, {}
    params["wq"], specs["wq"] = init_linear(
        kq, cfg.d_model, (cfg.n_heads, cfg.head_dim), ("embed", "heads", "head_dim"), dtype
    )
    params["wk"], specs["wk"] = init_linear(
        kk, cfg.d_model, (cfg.n_kv, cfg.head_dim), ("embed", "kv_heads", "head_dim"), dtype
    )
    params["wv"], specs["wv"] = init_linear(
        kv, cfg.d_model, (cfg.n_kv, cfg.head_dim), ("embed", "kv_heads", "head_dim"), dtype
    )
    params["wo"], specs["wo"] = init_linear(
        ko, cfg.n_heads * cfg.head_dim, (cfg.d_model,), ("heads_flat", "embed"), dtype,
        scale=(cfg.n_heads * cfg.head_dim) ** -0.5,
    )
    return params, specs


def project_qkv(cfg: AttnConfig, params, x, positions):
    """x: (B, S, D) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd), RoPE applied."""
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def output_proj(cfg: AttnConfig, params, attn_out):
    b, s = attn_out.shape[:2]
    flat = attn_out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bsf,fd->bsd", flat, params["wo"])


# ----------------------------------------------------------- full attention

def _expand_gqa(q, n_kv):
    """(B,S,Hq,hd) -> (B,S,Hkv,G,hd)."""
    b, s, hq, hd = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, hd)


def attention_full(cfg: AttnConfig, q, k, v, q_positions, kv_positions):
    """Materialized-scores attention (short sequences / reference oracle)."""
    scale = cfg.q_scale or cfg.head_dim ** -0.5
    qg = _expand_gqa(q * scale, cfg.n_kv)
    scores = jnp.einsum("bqhge,bkhe->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if cfg.causal:
        mask &= q_positions[:, None] >= kv_positions[None, :]
    if cfg.window is not None:
        mask &= (q_positions[:, None] - kv_positions[None, :]) < cfg.window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhe->bqhge", probs, v)
    b, s = q.shape[:2]
    return out.reshape(b, s, cfg.n_heads, cfg.head_dim)


def attention_chunked(cfg: AttnConfig, q, k, v, q_positions, kv_positions):
    """Exact attention with online softmax over KV chunks (O(S) memory).

    Sliding-window chunks that fall fully outside the causal/window band are
    still scanned (static shapes) but contribute exp(-inf)=0; the HLO is one
    compact scan regardless of sequence length.
    """
    scale = cfg.q_scale or cfg.head_dim ** -0.5
    b, sq, hq, hd = q.shape
    sk = k.shape[1]
    ck = min(cfg.kv_chunk, sk)
    n_chunks = (sk + ck - 1) // ck
    pad = n_chunks * ck - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-(10 ** 9))
    kc = k.reshape(b, n_chunks, ck, cfg.n_kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, ck, cfg.n_kv, hd).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(n_chunks, ck)
    qg = _expand_gqa(q * scale, cfg.n_kv)  # (b, sq, hkv, g, hd)

    def step(carry, chunk):
        m, l, acc = carry
        kb, vb, pb = chunk
        s = jnp.einsum("bqhge,bkhe->bhgqk", qg, kb,
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((sq, ck), bool)
        if cfg.causal:
            mask &= q_positions[:, None] >= pb[None, :]
        if cfg.window is not None:
            mask &= (q_positions[:, None] - pb[None, :]) < cfg.window
        mask &= pb[None, :] >= 0  # padding chunk entries
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhe->bhgqe", p.astype(q.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    g = hq // cfg.n_kv
    m0 = jnp.full((b, cfg.n_kv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, cfg.n_kv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, cfg.n_kv, g, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, cfg.n_heads, hd)
    return out.astype(q.dtype)


def attention_chunked_q(cfg: AttnConfig, q, k, v, q_positions, kv_positions,
                        q_chunk: int):
    """Doubly-chunked attention: an outer (unrolled) loop over query chunks,
    each attending only the KV range its causal/window band can reach.

    vs attention_chunked (full q x all KV chunks): (a) masked-out (q, kv)
    chunk pairs are STATICALLY skipped — for causal attention that halves
    score FLOPs and KV re-reads; for sliding windows it makes them O(S * W);
    (b) the online-softmax accumulator shrinks from O(S_q * hd) carried
    through every KV step to O(q_chunk * hd), VMEM-resident on TPU.
    """
    b, sq, hq, hd = q.shape
    nq = (sq + q_chunk - 1) // q_chunk
    outs = []
    for i in range(nq):
        lo_q = i * q_chunk
        hi_q = min(sq, (i + 1) * q_chunk)
        # the band of kv positions this q chunk can see (positions are
        # arange in train/prefill, so index == position)
        hi_k = hi_q if cfg.causal else k.shape[1]
        lo_k = 0
        if cfg.window is not None:
            lo_k = max(0, lo_q - cfg.window + 1)
        lo_k = (lo_k // cfg.kv_chunk) * cfg.kv_chunk  # align to kv chunks
        out = attention_chunked(
            cfg, q[:, lo_q:hi_q], k[:, lo_k:hi_k], v[:, lo_k:hi_k],
            q_positions[lo_q:hi_q], kv_positions[lo_k:hi_k],
        )
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


# ------------------------------------------------------------------- decode

def decode_attention(cfg: AttnConfig, q, k_cache, v_cache, pos, slot_positions):
    """Single-token attention over a cache.

    q: (B, 1, Hq, hd); caches: (B, S_cache, Hkv, hd); pos: (B,) current
    position; slot_positions: (B, S_cache) absolute position stored in each
    slot (-1 = empty). Works for both full and rolling (windowed) caches.
    """
    scale = cfg.q_scale or cfg.head_dim ** -0.5
    qg = _expand_gqa(q * scale, cfg.n_kv)[:, 0]  # (B, Hkv, G, hd)
    s = jnp.einsum("bhge,bkhe->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32)
    valid = (slot_positions >= 0) & (slot_positions <= pos[:, None])
    if cfg.window is not None:
        valid &= (pos[:, None] - slot_positions) < cfg.window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgk,bkhe->bhge", p, v_cache)
    b = q.shape[0]
    return out.reshape(b, 1, cfg.n_heads, cfg.head_dim)


def decode_append_attend_seqsharded(
    cfg: AttnConfig, mesh, axis: str,
    q, k1, v1, k_cache, v_cache, pos, slot_positions,
    batch_axis=None,
):
    """Split-KV decode with in-shard cache append.

    The cache's sequence dim is sharded over `axis`. The new token's K/V is
    written by the one shard that owns its slot (a purely local scatter — a
    global scatter over a sharded dim would make GSPMD all-gather the cache),
    then each shard computes partial (max, denom, weighted-V) statistics and
    the exact softmax is reassembled with pmax/psum — FlashDecoding across
    chips. Per-token collective volume is O(B * Hq * hd), not
    O(S_cache * Hkv * hd). Returns (attn_out, new_k, new_v, new_slot_pos).
    """
    scale = cfg.q_scale or cfg.head_dim ** -0.5
    s_total = k_cache.shape[1]

    def partial_fn(q, k1, v1, k_cache, v_cache, pos, slot_positions):
        s_local = k_cache.shape[1]
        shard = jax.lax.axis_index(axis)
        b = q.shape[0]
        bidx = jnp.arange(b)
        slot = (pos % s_total).astype(jnp.int32)
        local = slot - shard * s_local
        mine = (local >= 0) & (local < s_local)
        local_c = jnp.clip(local, 0, s_local - 1)
        old_k = k_cache[bidx, local_c]
        old_v = v_cache[bidx, local_c]
        old_sp = slot_positions[bidx, local_c]
        k_cache = k_cache.at[bidx, local_c].set(
            jnp.where(mine[:, None, None], k1[:, 0], old_k))
        v_cache = v_cache.at[bidx, local_c].set(
            jnp.where(mine[:, None, None], v1[:, 0], old_v))
        slot_positions = slot_positions.at[bidx, local_c].set(
            jnp.where(mine, pos.astype(jnp.int32), old_sp))

        qg = _expand_gqa(q * scale, cfg.n_kv)[:, 0]
        s = jnp.einsum("bhge,bkhe->bhgk", qg, k_cache,
                       preferred_element_type=jnp.float32)
        valid = (slot_positions >= 0) & (slot_positions <= pos[:, None])
        if cfg.window is not None:
            valid &= (pos[:, None] - slot_positions) < cfg.window
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)                       # (B,Hkv,G)
        m_glob = jax.lax.pmax(m_loc, axis)
        p = jnp.exp(s - m_glob[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bhgk,bkhe->bhge", p.astype(q.dtype), v_cache,
                           preferred_element_type=jnp.float32)
        l_glob = jax.lax.psum(l_loc, axis)
        o_glob = jax.lax.psum(o_loc, axis)
        out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        out = out.reshape(b, 1, cfg.n_heads, cfg.head_dim).astype(q.dtype)
        return out, k_cache, v_cache, slot_positions

    ba = batch_axis
    return jax.shard_map(
        partial_fn,
        mesh=mesh,
        in_specs=(P(ba), P(ba), P(ba), P(ba, axis), P(ba, axis), P(ba),
                  P(ba, axis)),
        out_specs=(P(ba), P(ba, axis), P(ba, axis), P(ba, axis)),
    )(q, k1, v1, k_cache, v_cache, pos, slot_positions)

"""Shared transformer layers: RMSNorm, RoPE/M-RoPE, GQA attention.

Attention uses a pure-JAX blockwise flash scan (online softmax, no SxS
materialization) so 32k prefill lowers with O(S * block) memory; the Pallas
kernel in repro/kernels/flash_attention is the drop-in TPU hot path
(cfg.use_flash_kernel).  Decode attends one query against a (possibly
sequence-sharded) KV cache; softmax reductions over the sharded axis lower
to cheap psums (flash-decode, DESIGN.md §6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.template import Leaf
from repro.sharding.partition import ShardCtx, constrain

NEG_INF = -1e30


# ------------------------------------------------------------------ norms --
def rmsnorm(x, scale, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * scale


# ------------------------------------------------------------------- rope --
def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S) int32."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                 # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> tuple[int, int, int]:
    """Qwen2-VL M-RoPE frequency split (t, h, w) in half-dim units.

    head_dim=128 -> (16, 24, 24), matching the published config.
    """
    half = head_dim // 2
    s_hw = 3 * half // 8
    return (half - 2 * s_hw, s_hw, s_hw)


def apply_mrope(x, positions_thw, theta: float):
    """M-RoPE: three position streams rotate disjoint frequency sections.

    x: (B, S, H, D); positions_thw: (B, S, 3) int32 (t, h, w ids; equal for
    text tokens, spatial for vision-patch tokens).
    """
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)
    sec = mrope_sections(x.shape[-1])
    bounds = (sec[0], sec[0] + sec[1])
    idx = jnp.arange(half)
    which = jnp.where(idx < bounds[0], 0, jnp.where(idx < bounds[1], 1, 2))
    pos = jnp.take_along_axis(
        positions_thw, which[None, None, :], axis=-1
    ).astype(jnp.float32)                                   # (B, S, half)
    ang = pos * freqs
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------- blockwise attention ----
def blockwise_attention(q, k, v, block_q: int, block_k: int,
                        causal: bool = True):
    """Flash-style causal attention without SxS materialization.

    q: (B, S, H, D); k, v: (B, S, KV, D) with H = KV * G.
    Double lax.scan (q blocks x kv blocks) with online softmax.  Future kv
    blocks are fully masked (computed then zeroed) — the §Perf log tracks
    the 2x FLOP overhead this leaves on the table vs triangle iteration.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = D ** -0.5

    qb = q.reshape(B, nq, bq, KV, G, D).astype(jnp.float32)
    kb = k.reshape(B, nk, bk, KV, D).astype(jnp.float32)
    vb = v.reshape(B, nk, bk, KV, D).astype(jnp.float32)
    # scan-major layouts
    qb = jnp.moveaxis(qb, 1, 0)  # (nq, B, bq, KV, G, D)
    kb = jnp.moveaxis(kb, 1, 0)  # (nk, B, bk, KV, D)
    vb = jnp.moveaxis(vb, 1, 0)

    q_pos_in = jnp.arange(bq)
    k_pos_in = jnp.arange(bk)

    def q_step(_, q_in):
        qi, qblk = q_in  # qblk: (B, bq, KV, G, D)

        def kv_step(carry, kv_in):
            m, l, acc = carry
            ki, kblk, vblk = kv_in
            s = jnp.einsum("bqkgd,bckd->bkgqc", qblk, kblk) * scale
            if causal:
                qp = qi * bq + q_pos_in            # (bq,)
                kp = ki * bk + k_pos_in            # (bk,)
                mask = qp[:, None] >= kp[None, :]  # (bq, bk)
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bkgqc,bckd->bkgqd", p, vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq, 1), jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, D), jnp.float32)
        # checkpoint the kv step: without it, reverse-mode saves the
        # softmax block p for EVERY (q, kv) block pair — the full SxS
        # matrix re-materialized under remat (measured: 4 GiB f32
        # (nq, nk, ..., bq, bk) buffers on kimi train_4k; §Perf log).
        # With it, backward recomputes one block at a time — the actual
        # flash-attention backward.
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (m0, l0, a0),
            (jnp.arange(nk), kb, vb))
        out = acc / jnp.where(l == 0, 1.0, l)      # (B, KV, G, bq, D)
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(q_step), None,
                           (jnp.arange(nq), qb))
    return outs  # (nq, B, KV, G, bq, D); see _assemble_blockwise


def _assemble_blockwise(outs, B, S, H, D, KV, G, nq, bq):
    """(nq, B, KV, G, bq, D) -> (B, S, H, D)."""
    x = jnp.moveaxis(outs, 0, 1)          # (B, nq, KV, G, bq, D)
    x = x.transpose(0, 1, 4, 2, 3, 5)     # (B, nq, bq, KV, G, D)
    return x.reshape(B, S, H, D)


def triangle_attention(q, k, v, block_q: int, block_k: int):
    """Causal blockwise attention, python-loop lower-triangle iteration.

    Used by the dry-run (unrolled mode): (1) cost_analysis counts every
    block (lax.scan bodies are counted once), and
    (2) upper-triangle blocks are *skipped*, not masked — removing the 2x
    masked-FLOP overhead of the scan path (a beyond-paper §Perf win that
    also exists on real hardware).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0
    nq, nk = S // bq, S // bk
    scale = D ** -0.5
    out_blocks = []
    for qi in range(nq):
        qblk = q[:, qi * bq : (qi + 1) * bq].reshape(
            B, bq, KV, G, D).astype(jnp.float32)
        m = jnp.full((B, KV, G, bq, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((B, KV, G, bq, 1), jnp.float32)
        acc = jnp.zeros((B, KV, G, bq, D), jnp.float32)
        hi = ((qi + 1) * bq + bk - 1) // bk  # kv blocks intersecting causal
        for ki in range(hi):
            kblk = k[:, ki * bk : (ki + 1) * bk].astype(jnp.float32)
            vblk = v[:, ki * bk : (ki + 1) * bk].astype(jnp.float32)
            s = jnp.einsum("bqkgd,bckd->bkgqc", qblk, kblk) * scale
            if ki * bk + bk > qi * bq:  # diagonal block: mask inside
                qp = qi * bq + jnp.arange(bq)
                kp = ki * bk + jnp.arange(bk)
                mask = qp[:, None] >= kp[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bkgqc,bckd->bkgqd", p, vblk)
            m = m_new
        o = acc / jnp.where(l == 0, 1.0, l)  # (B, KV, G, bq, D)
        out_blocks.append(o.transpose(0, 3, 1, 2, 4).reshape(B, bq, H, D))
    return jnp.concatenate(out_blocks, axis=1)


def dense_attention(q, k, v, causal: bool = True):
    """Reference O(S^2)-memory attention (tiny smoke shapes only)."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, D)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqc,bckd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, D)


def decode_attention(q, k_cache, v_cache, cache_len):
    """One-token attention over a KV cache.

    q: (B, 1, H, D); caches: (B, Smax, KV, D); cache_len: scalar/int —
    positions >= cache_len are masked.  Reductions over Smax lower to psums
    when the cache is sequence-sharded (flash-decode).
    """
    B, Smax, KV, D = k_cache.shape
    H = q.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bckd->bkgc", qg, k_cache.astype(jnp.float32))
    s = s * (D ** -0.5)
    pos = jnp.arange(Smax)
    s = jnp.where(pos[None, None, None, :] < cache_len, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgc,bckd->bkgd", p / l, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, D)


# ------------------------------------------------------------ GQA module ---
def attention_template(cfg: ModelConfig, stacked: tuple = ()) -> dict:
    """Template for one (optionally layer-stacked) GQA attention block."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    st = stacked
    sta = tuple("layers" for _ in stacked)
    t = {
        "wq": Leaf(st + (d, H * hd), sta + ("embed", "q_heads")),
        "wk": Leaf(st + (d, KV * hd), sta + ("embed", "kv_heads")),
        "wv": Leaf(st + (d, KV * hd), sta + ("embed", "kv_heads")),
        "wo": Leaf(st + (H * hd, d), sta + ("q_heads", "embed")),
    }
    if cfg.qkv_bias:
        t["bq"] = Leaf(st + (H * hd,), sta + ("q_heads",), init="zeros")
        t["bk"] = Leaf(st + (KV * hd,), sta + ("kv_heads",), init="zeros")
        t["bv"] = Leaf(st + (KV * hd,), sta + ("kv_heads",), init="zeros")
    return t


def attention_forward(p, x, cfg: ModelConfig, ctx: ShardCtx,
                      positions, cache=None, cache_len=None,
                      positions_thw=None):
    """GQA attention.  cache=None: full causal (train/prefill), returns
    (out, (k, v)); cache=(k_cache, v_cache): decode, returns (out, new_kv).
    """
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = x.dtype
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.m_rope and positions_thw is not None:
        q = apply_mrope(q, positions_thw, cfg.rope_theta)
        k = apply_mrope(k, positions_thw, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ctx, "batch", None, "q_heads", None)
    k = constrain(k, ctx, "batch", None, "kv_heads", None)

    if cache is not None:
        k_cache, v_cache = cache
        # insert at position cache_len (decode: S == 1)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), cache_len, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), cache_len, axis=1)
        out = decode_attention(q, k_cache, v_cache, cache_len + S)
        new_cache = (k_cache, v_cache)
    else:
        if cfg.attn_impl == "triangle":
            out = triangle_attention(q, k, v, cfg.attn_block_q,
                                     cfg.attn_block_k)
        elif S <= cfg.attn_block_q or S <= 128:
            out = dense_attention(q, k, v)
        elif cfg.use_flash_kernel:
            from repro.kernels.flash_attention.ops import flash_attention
            G = H // KV
            kr = jnp.repeat(k, G, axis=2)
            vr = jnp.repeat(v, G, axis=2)
            bhd = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
            o = flash_attention(bhd(q), bhd(kr), bhd(vr), causal=True)
            out = o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
        else:
            nq = S // min(cfg.attn_block_q, S)
            outs = blockwise_attention(
                q, k, v, cfg.attn_block_q, cfg.attn_block_k, causal=True)
            out = _assemble_blockwise(
                outs, B, S, H, hd, KV, H // KV,
                nq, min(cfg.attn_block_q, S))
        new_cache = (k, v)
    out = out.astype(dt).reshape(B, S, H * hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
    return constrain(out, ctx, "batch", None, None), new_cache


# -------------------------------------------------------------- SwiGLU -----
def mlp_template(cfg: ModelConfig, stacked: tuple = ()) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    st = stacked
    sta = tuple("layers" for _ in stacked)
    return {
        "w_gate": Leaf(st + (d, f), sta + ("embed", "ff")),
        "w_up": Leaf(st + (d, f), sta + ("embed", "ff")),
        "w_down": Leaf(st + (f, d), sta + ("ff", "embed")),
    }


def mlp_forward(p, x, ctx: ShardCtx):
    dt = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    h = jax.nn.silu(g) * u
    h = constrain(h, ctx, "batch", None, "ff")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))

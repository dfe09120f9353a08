"""Attention: GQA (chunked flash-style) and DeepSeek MLA, train + decode.

Memory discipline: full (S, S) score matrices are never materialized.
Training/prefill attention is a scan over query chunks with an inner
online-softmax scan over key chunks (the flash-attention recurrence in pure
XLA), so peak logits memory is (B, H, cq, ck) regardless of sequence length
— this is what lets prefill_32k lower within HBM.

MLA decode uses the "absorbed" formulation: the per-head up-projections are
folded into the query/output so scores are taken directly against the
(B, S, r) compressed KV cache — the cache stays rank-compressed end to end.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from .base import P, ShardCtx, dense, rms_norm
from .config import ModelConfig
from .rope import apply_rope, mrope_angles, rope_angles

Array = jax.Array


# ---------------------------------------------------------------------------
# Parameter declarations
# ---------------------------------------------------------------------------

def decls_gqa(cfg: ModelConfig) -> dict:
    d, hq, hkv, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                      cfg.resolved_head_dim)
    decls = {
        "wq": P((d, hq, hd), ("embed", "heads", None)),
        "wk": P((d, hkv, hd), ("embed", "kv", None)),
        "wv": P((d, hkv, hd), ("embed", "kv", None)),
        "wo": P((hq, hd, d), ("heads", None, "embed")),
    }
    if cfg.qk_norm:
        decls["q_gamma"] = P((hd,), (None,), init="zeros")
        decls["k_gamma"] = P((hd,), (None,), init="zeros")
    return decls


def decls_mla(cfg: ModelConfig) -> dict:
    assert cfg.mla is not None
    d, hq, m = cfg.d_model, cfg.n_heads, cfg.mla
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": P((d, hq, qk), ("embed", "heads", None)),
        "w_dkv": P((d, m.kv_lora_rank), ("embed", None)),
        "w_kr": P((d, m.qk_rope_head_dim), ("embed", None)),
        "kv_norm": P((m.kv_lora_rank,), (None,), init="zeros"),
        "w_uk": P((m.kv_lora_rank, hq, m.qk_nope_head_dim),
                  (None, "heads", None)),
        "w_uv": P((m.kv_lora_rank, hq, m.v_head_dim),
                  (None, "heads", None)),
        "wo": P((hq, m.v_head_dim, d), ("heads", None, "embed")),
    }


# ---------------------------------------------------------------------------
# Chunked causal attention (flash-style online softmax in XLA)
# ---------------------------------------------------------------------------

import functools

from .base import NULL_CTX


def chunked_attention(q: Array, k: Array, v: Array, *, scale: float,
                      q_chunk: int, k_chunk: int, causal: bool = True,
                      q_offset: int = 0, ctx: ShardCtx = NULL_CTX) -> Array:
    """q (B, Sq, H, D), k/v (B, Sk, H, Dk/Dv) -> (B, Sq, H, Dv).

    Flash-attention recurrence in pure XLA: scan over query chunks with an
    inner online-softmax scan over key chunks; peak logits memory is
    (B, H, cq, ck) regardless of sequence length.  The whole computation is
    a checkpoint (backward recomputes chunk internals from q/k/v).

    Callers pre-expand GQA KV heads to H == Hq: a SINGLE flat head axis is
    the only layout GSPMD shards 16-ways (perf iteration 2: the (Hkv, G)
    split layout silently replicated every chunk across the model axis —
    1.37 TB/step of all-gathers on deepseek train_4k).  Every loop-carried
    tensor is sharding-constrained so the annotation survives remat.

    When the (flattened) head count does NOT divide the model axis
    (starcoder2's 24, qwen2-vl's 12 on a 16-wide axis), head-sharded TP is
    impossible and attention would run fully replicated (16x the compute).
    Fallback: CONTEXT PARALLELISM over query chunks (perf iteration 6) —
    the q-chunk grid is sharded over the model axis and all chunks advance
    through the k-scan together (q chunks are independent), so attention
    compute scales with the full mesh again at the cost of replicating
    K/V (already needed) and a (nq/16, B, H, cq, ck) logits transient.

    Pads ragged sequence lengths up to the chunk grid; padded key rows sit
    beyond every real query position, so the causal mask kills them.
    """
    Sq, Sk = q.shape[1], k.shape[1]
    H = q.shape[2]
    model_size = ctx.mesh.shape.get("model", 1) if ctx.mesh else 1
    cp_mode = (model_size > 1 and H % model_size != 0
               and Sq >= 2 * model_size)
    if cp_mode:
        # pick a q_chunk that makes the chunk-grid divisible by the axis
        q_chunk = min(q_chunk, max(Sq // model_size, 1))
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    pad_q = (-Sq) % q_chunk
    pad_k = (-Sk) % k_chunk

    def pad1(x, p):
        return jnp.pad(x, ((0, 0), (0, p)) + ((0, 0),) * (x.ndim - 2))

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def attn(q: Array, k: Array, v: Array) -> Array:
        B, Sqp, H, D = q.shape
        Skp = k.shape[1]
        Dv = v.shape[-1]
        nq, nk = Sqp // q_chunk, Skp // k_chunk
        c_head = lambda x: ctx.constrain(x, None, "batch", None, "heads",
                                         None)
        qg = c_head(q.reshape(B, nq, q_chunk, H, D)
                     .transpose(1, 0, 2, 3, 4).astype(jnp.bfloat16))
        kg = c_head(k.reshape(B, nk, k_chunk, H, D)
                     .transpose(1, 0, 2, 3, 4).astype(jnp.bfloat16))
        vg = c_head(v.reshape(B, nk, k_chunk, H, Dv)
                     .transpose(1, 0, 2, 3, 4).astype(jnp.bfloat16))
        if cp_mode and nq % model_size == 0:
            return _attn_context_parallel(qg, kg, vg, nq, nk, B, H, D, Dv)

        def q_step(_, qi):
            qc, q_idx = qi                               # (B,cq,H,D)
            qc = ctx.constrain(qc, "batch", None, "heads", None)

            def k_step(carry, ki):
                m, l, acc = carry
                kc, vc, k_idx = ki
                kc = ctx.constrain(kc, "batch", None, "heads", None)
                logits = jnp.einsum(
                    "bqhd,bkhd->bhqk", qc, kc,
                    preferred_element_type=jnp.float32) * scale
                logits = ctx.constrain(logits, "batch", "heads", None,
                                       None)
                if causal:
                    qpos = (q_offset + q_idx * q_chunk
                            + jax.lax.broadcasted_iota(
                                jnp.int32, (q_chunk, k_chunk), 0))
                    kpos = (k_idx * k_chunk
                            + jax.lax.broadcasted_iota(
                                jnp.int32, (q_chunk, k_chunk), 1))
                    logits = jnp.where(qpos >= kpos, logits, -jnp.inf)
                m_new = jnp.maximum(m, logits.max(axis=-1))
                # Guard fully-masked rows (m_new == -inf) against NaN.
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.exp(logits - m_safe[..., None])
                corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe,
                                         -jnp.inf))
                l_new = l * corr + p.sum(axis=-1)
                acc_new = (acc * corr[..., None]
                           + jnp.einsum("bhqk,bkhd->bhqd",
                                        p.astype(jnp.bfloat16), vc,
                                        preferred_element_type=jnp.float32))
                acc_new = ctx.constrain(acc_new, "batch", "heads", None,
                                        None)
                return (m_new, l_new, acc_new), None

            shape = (B, H, q_chunk)
            init = (jnp.full(shape, -jnp.inf, jnp.float32),
                    jnp.zeros(shape, jnp.float32),
                    ctx.constrain(jnp.zeros(shape + (Dv,), jnp.float32),
                                  "batch", "heads", None, None))
            (m, l, acc), _ = jax.lax.scan(
                k_step, init, (kg, vg, jnp.arange(nk)))
            out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,H,cq,Dv)
            return None, out.transpose(0, 2, 1, 3)        # (B,cq,H,Dv)

        _, out = jax.lax.scan(q_step, None, (qg, jnp.arange(nq)))
        # out (nq, B, cq, H, Dv) -> (B, Sqp, H, Dv)
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, Sqp, H, Dv)
        return out.astype(q.dtype)

    def _attn_context_parallel(qg, kg, vg, nq, nk, B, H, D, Dv):
        """All q chunks advance together; the nq grid is model-sharded
        (and the batch dim keeps its data sharding)."""
        c_cp = lambda x: ctx.constrain(
            x, *(("seq", "batch") + (None,) * (x.ndim - 2)))
        qg = c_cp(qg)                                     # (nq,B,cq,H,D)

        def k_step(carry, ki):
            m, l, acc = carry
            kc, vc, k_idx = ki
            logits = jnp.einsum(
                "nbqhd,bkhd->nbhqk", qg, kc,
                preferred_element_type=jnp.float32) * scale
            logits = c_cp(logits)
            if causal:
                qpos = (q_offset
                        + jax.lax.broadcasted_iota(
                            jnp.int32, (nq, q_chunk, k_chunk), 0) * q_chunk
                        + jax.lax.broadcasted_iota(
                            jnp.int32, (nq, q_chunk, k_chunk), 1))
                kpos = (k_idx * k_chunk
                        + jax.lax.broadcasted_iota(
                            jnp.int32, (nq, q_chunk, k_chunk), 2))
                mask = (qpos >= kpos)[:, None, None, :, :]
                logits = jnp.where(mask, logits, -jnp.inf)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(logits - m_safe[..., None])
            corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
            l_new = l * corr + p.sum(axis=-1)
            acc_new = (acc * corr[..., None]
                       + jnp.einsum("nbhqk,bkhd->nbhqd",
                                    p.astype(jnp.bfloat16), vc,
                                    preferred_element_type=jnp.float32))
            return (m_new, c_cp(l_new), c_cp(acc_new)), None

        shape = (nq, B, H, q_chunk)
        init = (jnp.full(shape, -jnp.inf, jnp.float32),
                jnp.zeros(shape, jnp.float32),
                c_cp(jnp.zeros(shape + (Dv,), jnp.float32)))
        (m, l, acc), _ = jax.lax.scan(k_step, init,
                                      (kg, vg, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (nq,B,H,cq,Dv)
        out = out.transpose(1, 0, 3, 2, 4).reshape(B, nq * q_chunk, H, Dv)
        return out.astype(qg.dtype)

    out = attn(pad1(q, pad_q), pad1(k, pad_k), pad1(v, pad_k))
    return out[:, :Sq]


def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     cache_len: Array, *, scale: float,
                     ctx: ShardCtx = None) -> Array:
    """One-token attention against a KV cache.

    q (B, 1, Hq, D); caches (B, Smax, Hkv, D); cache_len () or (B,) —
    number of valid cache entries INCLUDING the current token.

    When the KV heads cannot shard over the model axis but the head_dim
    can (llama/qwen3/grok GQA on a 16-wide axis), the cache is hd-sharded
    and GSPMD's dot handling degrades to replicate-then-repartition of
    every per-step chunk (the "involuntary full rematerialization"
    warning; ~60 GiB/step on llama3 decode_32k).  The shard_map path makes
    the math explicit: partial logits over local head_dim slices + one
    psum of (B, H, S) — perf iteration 5.
    """
    B, _, Hq, D = q.shape
    Smax, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv

    mesh = ctx.mesh if ctx is not None else None
    model_size = mesh.shape.get("model", 1) if mesh is not None else 1
    use_shard_map = (mesh is not None and model_size > 1
                     and Hkv % model_size != 0 and D % model_size == 0)

    def _attn(qg, kc, vc, length, axis=None):
        contract = (jnp.einsum("bhgd,bkhd->bhgk", qg, kc,
                               preferred_element_type=jnp.float32) * scale)
        if axis is not None:
            contract = jax.lax.psum(contract, axis)
        pos = jax.lax.broadcasted_iota(jnp.int32, (qg.shape[0], Smax), 1)
        valid = pos < jnp.reshape(length, (-1, 1))
        logits = jnp.where(valid[:, None, None, :], contract, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhgk,bkhd->bhgd", p.astype(jnp.bfloat16), vc,
                          preferred_element_type=jnp.float32)

    qg = q.reshape(B, Hkv, G, D).astype(jnp.bfloat16)
    if use_shard_map:
        from jax.sharding import PartitionSpec as PS
        dp = tuple(n for n in ("pod", "data") if n in mesh.shape)
        dp_size = 1
        for n in dp:
            dp_size *= mesh.shape[n]
        bspec = dp if (dp and B % dp_size == 0) else None
        out = jax.shard_map(
            lambda qq, kk, vv, ln: _attn(qq, kk, vv, ln, axis="model"),
            mesh=mesh,
            in_specs=(PS(bspec, None, None, "model"),
                      PS(bspec, None, None, "model"),
                      PS(bspec, None, None, "model"),
                      PS(bspec)),
            out_specs=PS(bspec, None, None, "model"),
            check_vma=False,
        )(qg, k_cache.astype(jnp.bfloat16), v_cache.astype(jnp.bfloat16),
          jnp.broadcast_to(jnp.reshape(cache_len, (-1,)), (B,)))
    else:
        out = _attn(qg, k_cache.astype(jnp.bfloat16),
                    v_cache.astype(jnp.bfloat16), cache_len)
    return out.reshape(B, 1, Hq, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def _angles(cfg: ModelConfig, positions: Array, head_dim: int) -> Array:
    if cfg.rope_style == "mrope":
        return mrope_angles(positions, head_dim, cfg.rope_theta,
                            cfg.mrope_sections)
    return rope_angles(positions, head_dim, cfg.rope_theta)


def _pad_seq(x: Array, max_len: int) -> Array:
    pad = max_len - x.shape[1]
    if pad <= 0:
        return x[:, :max_len]
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, pad)
    return jnp.pad(x, widths)


def gqa_forward(p: dict, x: Array, positions: Array, cfg: ModelConfig,
                ctx: ShardCtx, *, cache: dict | None = None,
                fill_len: int | None = None) -> tuple:
    """x (B, S, d) -> (out (B, S, d), updated cache or None).

    ``positions`` is (B, S) int32, or (3, B, S) for M-RoPE.
    With ``cache`` set, S must be 1 (decode) and the cache dict holds
    {"k": (B, Smax, Hkv, D), "v": ..., "len": (B,)} — "len" counts tokens
    already in the cache BEFORE this call.  With ``fill_len`` set (prefill),
    the full-sequence K/V are padded to that length and returned as a fresh
    cache.
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    q = ctx.constrain(q, "batch", None, "heads", None)
    k = ctx.constrain(k, "batch", None, "kv", None)
    v = ctx.constrain(v, "batch", None, "kv", None)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_gamma"])
        k = rms_norm(k, p["k_gamma"])

    if cfg.rope_style != "none":
        ang = _angles(cfg, positions, hd)
        q = apply_rope(q, ang)
        k = apply_rope(k, ang)

    if cache is None:
        g = cfg.n_heads // cfg.n_kv_heads
        k_full = jnp.repeat(k, g, axis=2) if g > 1 else k
        v_full = jnp.repeat(v, g, axis=2) if g > 1 else v
        out = chunked_attention(q, k_full, v_full, scale=scale,
                                q_chunk=min(cfg.attn_chunk_q, S),
                                k_chunk=min(cfg.attn_chunk_k, S), ctx=ctx)
        new_cache = None
        if fill_len is not None:
            new_cache = dict(
                k=_pad_seq(k.astype(jnp.bfloat16), fill_len),
                v=_pad_seq(v.astype(jnp.bfloat16), fill_len),
                len=jnp.full((B,), S, jnp.int32))
    else:
        idx = cache["len"]                                # (B,) int32
        k_cache = jax.vmap(
            lambda c, upd, i: jax.lax.dynamic_update_slice(c, upd, (i, 0, 0))
        )(cache["k"], k.astype(cache["k"].dtype), idx)
        v_cache = jax.vmap(
            lambda c, upd, i: jax.lax.dynamic_update_slice(c, upd, (i, 0, 0))
        )(cache["v"], v.astype(cache["v"].dtype), idx)
        out = decode_attention(q, k_cache, v_cache, idx + 1, scale=scale,
                               ctx=ctx)
        new_cache = dict(k=k_cache, v=v_cache, len=idx + 1)

    out = ctx.constrain(out, "batch", None, "heads", None)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return ctx.constrain(out, "batch", "seq", None), new_cache


# ---------------------------------------------------------------------------
# MLA block (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_forward(p: dict, x: Array, positions: Array, cfg: ModelConfig,
                ctx: ShardCtx, *, cache: dict | None = None,
                fill_len: int | None = None) -> tuple:
    """Multi-head latent attention; cache holds the COMPRESSED kv stream:
    {"ckv": (B, Smax, r), "kr": (B, Smax, rope_dim), "len": (B,)}."""
    m = cfg.mla
    B, S, _ = x.shape
    hq = cfg.n_heads
    nope, rdim = m.qk_nope_head_dim, m.qk_rope_head_dim
    scale = 1.0 / math.sqrt(nope + rdim)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q = ctx.constrain(q, "batch", None, "heads", None)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    ckv = rms_norm(dense(x, p["w_dkv"]), p["kv_norm"])    # (B, S, r)
    kr = dense(x, p["w_kr"])                              # (B, S, rdim)

    ang = rope_angles(positions, rdim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, ang)
    kr = apply_rope(kr[:, :, None, :], ang)[:, :, 0, :]   # single shared head

    if cache is None:
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"].astype(x.dtype))
        v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"].astype(x.dtype))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                      (B, S, hq, rdim))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = chunked_attention(qf, k, v, scale=scale,
                                q_chunk=min(cfg.attn_chunk_q, S),
                                k_chunk=min(cfg.attn_chunk_k, S), ctx=ctx)
        new_cache = None
        if fill_len is not None:
            new_cache = dict(
                ckv=_pad_seq(ckv.astype(jnp.bfloat16), fill_len),
                kr=_pad_seq(kr.astype(jnp.bfloat16), fill_len),
                len=jnp.full((B,), S, jnp.int32))
    else:
        # Absorbed decode: fold w_uk into q, w_uv into the output.
        idx = cache["len"]
        ckv_cache = jax.vmap(
            lambda c, upd, i: jax.lax.dynamic_update_slice(c, upd, (i, 0))
        )(cache["ckv"], ckv.astype(cache["ckv"].dtype), idx)
        kr_cache = jax.vmap(
            lambda c, upd, i: jax.lax.dynamic_update_slice(c, upd, (i, 0))
        )(cache["kr"], kr.astype(cache["kr"].dtype), idx)
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope,
                           p["w_uk"].astype(x.dtype))     # (B,1,H,r)
        logits = (jnp.einsum("bshr,btr->bhst", q_abs,
                             ckv_cache.astype(x.dtype),
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshk,btk->bhst", q_rope,
                               kr_cache.astype(x.dtype),
                               preferred_element_type=jnp.float32)) * scale
        Smax = ckv_cache.shape[1]
        pos = jax.lax.broadcasted_iota(jnp.int32, (B, Smax), 1)
        valid = pos < (idx + 1)[:, None]
        logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
        pr = jax.nn.softmax(logits, axis=-1)
        o_r = jnp.einsum("bhst,btr->bshr", pr.astype(x.dtype),
                         ckv_cache.astype(x.dtype))       # (B,1,H,r)
        out = jnp.einsum("bshr,rhk->bshk", o_r, p["w_uv"].astype(x.dtype))
        new_cache = dict(ckv=ckv_cache, kr=kr_cache, len=idx + 1)

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return ctx.constrain(out, "batch", "seq", None), new_cache


def attn_decls(cfg: ModelConfig) -> dict:
    return decls_mla(cfg) if cfg.mla is not None else decls_gqa(cfg)


def attn_forward(p: dict, x: Array, positions: Array, cfg: ModelConfig,
                 ctx: ShardCtx, *, cache: dict | None = None,
                 fill_len: int | None = None) -> tuple:
    fn = mla_forward if cfg.mla is not None else gqa_forward
    return fn(p, x, positions, cfg, ctx, cache=cache, fill_len=fill_len)


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16) -> dict:
    """Abstract per-layer cache structure (shapes only via eval_shape)."""
    if cfg.mla is not None:
        m = cfg.mla
        return dict(
            ckv=jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            kr=jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
            len=jnp.zeros((batch,), jnp.int32))
    hd = cfg.resolved_head_dim
    return dict(
        k=jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        v=jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        len=jnp.zeros((batch,), jnp.int32))

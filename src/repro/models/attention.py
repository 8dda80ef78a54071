"""Grouped-query attention with a memory-efficient (flash-style) chunked core.

Pure-JAX XLA path used by training / prefill / the multi-pod dry-run. The
double-chunked online-softmax scan bounds the materialized score block to
[B, H, cq, ck] regardless of GSPMD propagation, which is what lets the 32k
prefill cells fit HBM. (On real TPU a Pallas flash kernel would replace the
inner loop; the XLA path is what the dry-run compiles.)

Supports: GQA (num_kv_heads < num_heads), RoPE, causal / sliding-window /
bidirectional / cross masks, QKV bias (qwen2), logit softcap, single-token
decode against a KV cache.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import quant as Q
from repro.models.layers import (apply_rope, dense_init, dp_spec, mesh_axis,
                                 rmsnorm, rmsnorm_init, rope_angles,
                                 shard_hint, split)

NEG_INF = -1e30


# ----------------------------------------------------------------------- init

def attn_init(key, cfg, *, d_model: int = 0, cross: bool = False) -> dict:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = split(key, 4)
    p = {
        "wq": dense_init(k1, d, nq * hd, dt),
        "wk": dense_init(k2, d, nkv * hd, dt),
        "wv": dense_init(k3, d, nkv * hd, dt),
        "wo": dense_init(k4, nq * hd, d, dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


# ----------------------------------------------------------- chunked SDPA core

@partial(
    jax.jit,
    static_argnames=("causal", "softcap", "ck", "sp_attn"),
)
def sdpa_chunked(
    q: jax.Array,            # [B, Sq, Hq, D]
    k: jax.Array,            # [B, Sk, Hkv, D]
    v: jax.Array,            # [B, Sk, Hkv, D]
    q_pos: jax.Array,        # [Sq] int32 absolute positions of queries
    k_pos: jax.Array,        # [Sk] int32 absolute positions of keys
    window,                  # traced int32 scalar: 0 => global, >0 => local span
    kv_len,                  # traced int32 scalar: keys with k_pos >= kv_len masked
    *,
    causal: bool,
    softcap: float = 0.0,
    ck: int = 1024,
    sp_attn: bool = False,
) -> jax.Array:
    """Flash-style online-softmax attention, scanned over KV chunks only.

    Queries keep their natural [B, Sq, ...] layout so GSPMD shards the score
    blocks natively: head-parallel when Hkv divides the model axis (Megatron),
    else sequence-parallel on Sq (SP attention for odd head counts). The KV
    chunk axis is the scan axis and is never sharded; materialized score block
    is [B, Sq_local, Hkv, G, ck].
    """
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    ck = min(ck, Sk)
    while Sk % ck:
        ck -= 1
    nk = Sk // ck
    scale = 1.0 / (D ** 0.5)

    # --- model-axis work split for the score/PV blocks -------------------
    # Hkv | M : Megatron head-parallel attention on the kv-head dim.
    # Hq  | M : GQA with too few kv heads — expand K/V to Hq ("repeat_kv")
    #           and shard the query-head dim; kv replication cost is tiny.
    # otherwise: attention replicated on the model axis (odd head counts,
    #           e.g. 24/28 heads over 16); everything else stays TP.
    M = mesh_axis("model")
    dp = dp_spec()
    expand = M > 1 and Hkv % M != 0 and Hq % M == 0
    if expand:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        Hkv, G = Hq, 1

    qg = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, D).astype(q.dtype)

    if M > 1 and Hkv % M == 0:
        qg = shard_hint(qg, dp, None, "model", None, None)
        k = shard_hint(k, dp, None, "model", None)
        v = shard_hint(v, dp, None, "model", None)
    elif sp_attn and M > 1 and Sq % M == 0:
        # sequence-parallel score blocks (forward-only paths; §Perf knob for
        # odd head counts — kv replicated, q rows sharded)
        qg = shard_hint(qg, dp, "model", None, None, None)

    kc = k.reshape(B, nk, ck, Hkv, D).transpose(1, 0, 2, 3, 4)  # [nk,B,ck,Hkv,D]
    vc = v.reshape(B, nk, ck, Hkv, D).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(nk, ck)

    def kv_step(carry, kv):
        m, l, acc = carry                              # [B,Sq,Hkv,G](,D)
        kb, vb, kpb = kv                               # [B,ck,Hkv,D], ..., [ck]
        s = jnp.einsum(
            "bqhgd,bkhd->bqhgk", qg, kb, preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpb[None, :] < kv_len
        if causal:
            mask &= kpb[None, :] <= q_pos[:, None]
        mask &= jnp.where(
            window > 0, kpb[None, :] > q_pos[:, None] - window, True)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kc, vc, kp))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


# ------------------------------------------------- flash backward (custom VJP)

def _mask_block(kpb, q_pos, kv_len, window, causal: bool):
    mask = kpb[None, :] < kv_len
    if causal:
        mask &= kpb[None, :] <= q_pos[:, None]
    mask &= jnp.where(window > 0, kpb[None, :] > q_pos[:, None] - window, True)
    return mask                                            # [Sq, ck]


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def sdpa_flash(q, k, v, q_pos, k_pos, window, kv_len, causal, softcap, ck):
    return _flash_fwd(q, k, v, q_pos, k_pos, window, kv_len,
                      causal, softcap, ck)[0]


def _flash_fwd(q, k, v, q_pos, k_pos, window, kv_len, causal, softcap, ck):
    """Online-softmax forward that also returns the row statistics (m, l) —
    the only residuals the backward needs besides (q, k, v, out)."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    ckk = min(ck, Sk)
    while Sk % ckk:
        ckk -= 1
    nk = Sk // ckk
    scale = 1.0 / (D ** 0.5)
    M = mesh_axis("model")
    dp = dp_spec()
    expand = M > 1 and Hkv % M != 0 and Hq % M == 0
    if expand:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        Hkv, G = Hq, 1
    qg = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, D).astype(q.dtype)
    if M > 1 and Hkv % M == 0:
        qg = shard_hint(qg, dp, None, "model", None, None)
        k = shard_hint(k, dp, None, "model", None)
        v = shard_hint(v, dp, None, "model", None)
    kc = k.reshape(B, nk, ckk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ckk, Hkv, D).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(nk, ckk)

    def kv_step(carry, kv):
        m, l, acc = carry
        kb, vb, kpb = kv
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb,
                       preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = _mask_block(kpb, q_pos, kv_len, window, causal)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        kv_step, (m0, l0, a0), (kc, vc, kp), unroll=1)
    l = jnp.maximum(l, 1e-20)
    out = (acc / l[..., None]).reshape(B, Sq, Hq, D).astype(q.dtype)
    lse = m + jnp.log(l)                                    # [B,Sq,Hkv,G]
    return out, lse


def _flash_fwd_vjp(q, k, v, q_pos, k_pos, window, kv_len, causal, softcap, ck):
    out, lse = _flash_fwd(q, k, v, q_pos, k_pos, window, kv_len,
                          causal, softcap, ck)
    return out, (q, k, v, out, lse, q_pos, k_pos, window, kv_len)


def _flash_bwd(causal, softcap, ck, res, dout):
    """Chunk-streamed backward: recompute p per kv chunk from (q, k, lse);
    never materializes the [Sq, Sk] score matrix nor stacks per-chunk
    intermediates (scan carries are only dq)."""
    q, k, v, out, lse, q_pos, k_pos, window, kv_len = res
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    ckk = min(ck, Sk)
    while Sk % ckk:
        ckk -= 1
    nk = Sk // ckk
    scale = 1.0 / (D ** 0.5)
    M = mesh_axis("model")
    dp = dp_spec()
    expand = M > 1 and Hkv % M != 0 and Hq % M == 0
    if expand:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        Hkv_e, G_e = Hq, 1
    else:
        Hkv_e, G_e = Hkv, G
    qg = q.astype(jnp.float32).reshape(B, Sq, Hkv_e, G_e, D) * scale
    dog = dout.astype(jnp.float32).reshape(B, Sq, Hkv_e, G_e, D)
    og = out.astype(jnp.float32).reshape(B, Sq, Hkv_e, G_e, D)
    Drow = (dog * og).sum(-1)                               # [B,Sq,Hkv_e,G_e]
    if M > 1 and Hkv_e % M == 0:
        qg = shard_hint(qg.astype(q.dtype), dp, None, "model", None, None)
        dog = shard_hint(dog, dp, None, "model", None, None)
        k = shard_hint(k, dp, None, "model", None)
        v = shard_hint(v, dp, None, "model", None)
    kc = k.reshape(B, nk, ckk, Hkv_e, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ckk, Hkv_e, D).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(nk, ckk)

    def kv_step(dq, kv):
        kb, vb, kpb = kv
        sraw = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(q.dtype), kb,
                          preferred_element_type=jnp.float32)
        if softcap > 0:
            t = jnp.tanh(sraw / softcap)
            s = softcap * t
            dsoft = 1.0 - t * t                             # d softcap / d sraw
        else:
            s = sraw
            dsoft = None
        mask = _mask_block(kpb, q_pos, kv_len, window, causal)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                     # [B,Sq,Hkv,G,ck]
        dv = jnp.einsum("bqhgk,bqhgd->bkhd", p, dog,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", dog.astype(v.dtype), vb,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - Drow[..., None])
        if dsoft is not None:
            ds = ds * dsoft
        ds = jnp.where(mask[None, :, None, None, :], ds, 0.0)
        dq = dq + jnp.einsum("bqhgk,bkhd->bqhgd", ds.astype(k.dtype), kb,
                             preferred_element_type=jnp.float32)
        dk = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qg,
                        preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Sq, Hkv_e, G_e, D), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(kv_step, dq0, (kc, vc, kp))
    dq = (dq * scale).reshape(B, Sq, Hq, D).astype(q.dtype)
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, Sk, Hkv_e, D)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, Sk, Hkv_e, D)
    if expand:
        dk = dk.reshape(B, Sk, Hkv, G, D).sum(3)
        dv = dv.reshape(B, Sk, Hkv, G, D).sum(3)
    dk = dk.astype(k.dtype)
    dv = dv.astype(v.dtype)
    zero_i = jnp.zeros_like(q_pos)
    return (dq, dk, dv, zero_i, jnp.zeros_like(k_pos),
            jnp.zeros_like(jnp.asarray(0, jnp.int32)),
            jnp.zeros_like(jnp.asarray(0, jnp.int32)))


sdpa_flash.defvjp(_flash_fwd_vjp, _flash_bwd)


# ------------------------------------------------------------------- full pass

def attn_forward(
    params: dict,
    x: jax.Array,                 # [B, S, D]
    *,
    cfg,
    positions: jax.Array,         # [S]
    window=0,                     # traced scalar ok (scan-over-layers)
    causal: bool = True,
    kv_source: jax.Array = None,  # cross-attention memory [B, Sk, D]
    use_rope: bool = True,
    return_kv: bool = False,
) -> jax.Array:
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads

    q = x @ params["wq"]
    src = x if kv_source is None else kv_source
    k = src @ params["wk"]
    v = src @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    Sk = src.shape[1]
    q = q.reshape(B, S, nq, hd)
    k = k.reshape(B, Sk, nkv, hd)
    v = v.reshape(B, Sk, nkv, hd)

    if use_rope and kv_source is None:
        from repro.models.layers import rope_angles
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos[:, None, :], sin[:, None, :])
        k = apply_rope(k, cos[:, None, :], sin[:, None, :])

    k_pos = positions if kv_source is None else jnp.arange(Sk, dtype=jnp.int32)
    if getattr(cfg, "sp_attn", False):
        # sequence-parallel score blocks (forward-only serving paths)
        out = sdpa_chunked(
            q, k, v, positions.astype(jnp.int32), k_pos.astype(jnp.int32),
            jnp.asarray(window, jnp.int32), jnp.asarray(Sk + 10**9, jnp.int32),
            causal=causal and kv_source is None, softcap=cfg.logit_softcap,
            sp_attn=True,
        )
    else:
        # flash custom-VJP core: backward recomputes score blocks chunk-wise
        # instead of letting scan-AD stack fp32 intermediates (§Perf H3b)
        out = sdpa_flash(
            q, k, v, positions.astype(jnp.int32), k_pos.astype(jnp.int32),
            jnp.asarray(window, jnp.int32), jnp.asarray(Sk + 10**9, jnp.int32),
            causal and kv_source is None, cfg.logit_softcap, 1024,
        )
    out = out.reshape(B, S, nq * hd) @ params["wo"]
    # pin the residual back to the Megatron layout (batch-sharded, replicated
    # on the model axis) so sequence-parallel attention for odd head counts
    # does not flip the MLP/MoE strategy to replicated-weight SP
    out = shard_hint(out, dp_spec(), None, None)
    if return_kv:
        return out, k, v
    return out


# --------------------------------------------------------------------- decode

def _decode_sdpa(q, k, v, mask, softcap_val: float):
    """Direct single-query SDPA — no scan, so GSPMD can shard the KV-cache
    sequence dim (scores get partitioned; softmax max/sum become all-reduces).
    q [B,1,Hq,D]; k/v [B,S,Hkv,D]; mask [B?,S] or [S] bool."""
    B, _, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, 1, Hkv, G, D)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k,
                   preferred_element_type=jnp.float32) / (D ** 0.5)
    if softcap_val > 0:
        s = softcap_val * jnp.tanh(s / softcap_val)
    m = mask if mask.ndim == 2 else mask[None]
    s = jnp.where(m[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D)


def attn_decode(
    params: dict,
    x_t: jax.Array,               # [B, 1, D] current token
    cache_k: jax.Array,           # [B, Smax, Hkv, hd] — or, with block_table,
    cache_v: jax.Array,           #   a shared page pool [NP, ps, Hkv, hd]
    t,                            # traced int32 position: scalar or [B] per-slot
    *,
    cfg,
    window=0,
    use_rope: bool = True,
    block_table=None,             # [B, P] int32 page ids (paged KV pool)
) -> tuple:
    """Single-token decode against the KV cache.

    With `block_table`, the cache arrays are a PAGED pool shared by every
    slot: `cache_k[NP, ps, Hkv, hd]` holds fixed-size token pages and
    `block_table[b, j]` names the physical page backing slot b's j-th
    logical page (0 = the reserved null page — unallocated, and the write
    target of retired rows, so its contents are trash by design). The new
    token is scattered into page `bt[b, t // ps]` at offset `t % ps`, and
    attention gathers the slot's pages back into the dense [B, P*ps]
    logical layout — positions beyond `t` (including anything routed to the
    null page) are masked before the softmax, so the paged step is
    bit-identical to the dense one.

    Which paged realization runs is cfg.paged_attn (resolved by
    kernels/paged_attn.py::resolve_mode): "kernel" walks the block table
    inside a Pallas grid — per-tick HBM traffic scales with each row's LIVE
    pages instead of max_tokens — while "gather" keeps the dense
    re-materialization below (the bit-exact escape hatch)."""
    B = x_t.shape[0]
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    # Per-slot positions: every slot of a continuous-batching pool sits at its
    # own decode offset. A scalar t (static batch, all rows in lock-step) is
    # broadcast so both paths share one compiled graph.
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (B,))

    q = x_t @ params["wq"]
    k = x_t @ params["wk"]
    v = x_t @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, 1, nq, hd)
    k = k.reshape(B, 1, nkv, hd)
    v = v.reshape(B, 1, nkv, hd)

    if use_rope:
        from repro.models.layers import rope_angles
        cos, sin = rope_angles(t_vec[:, None], hd, cfg.rope_theta)  # [B,1,hd/2]
        q = apply_rope(q, cos[:, :, None, :], sin[:, :, None, :])
        k = apply_rope(k, cos[:, :, None, :], sin[:, :, None, :])

    rows = jnp.arange(B)
    # Quantized pools arrive as (int8 pages, f32 per-page scales) tuples —
    # unpack here, repack on return so the caller's carry stays bundled.
    k_scales = v_scales = None
    if isinstance(cache_k, tuple):
        cache_k, k_scales = cache_k
        cache_v, v_scales = cache_v
    if block_table is None:
        cache_k = cache_k.at[rows, t_vec].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, t_vec].set(v[:, 0].astype(cache_v.dtype))
        att_k, att_v = cache_k, cache_v
        Smax = cache_k.shape[1]
        ret_k, ret_v = cache_k, cache_v
    else:
        ps = cache_k.shape[1]
        page = block_table[rows, t_vec // ps]                       # [B]
        if k_scales is not None:
            cache_k, k_scales = Q.scatter_token(
                cache_k, k_scales, page, t_vec % ps, k[:, 0])
            cache_v, v_scales = Q.scatter_token(
                cache_v, v_scales, page, t_vec % ps, v[:, 0])
            ret_k, ret_v = (cache_k, k_scales), (cache_v, v_scales)
        else:
            cache_k = cache_k.at[page, t_vec % ps].set(
                k[:, 0].astype(cache_k.dtype))
            cache_v = cache_v.at[page, t_vec % ps].set(
                v[:, 0].astype(cache_v.dtype))
            ret_k, ret_v = cache_k, cache_v
        from repro.kernels import paged_attn as PAGED
        if PAGED.resolve_mode(cfg) == "kernel":
            out = PAGED.paged_attn_decode(
                q[:, 0], cache_k, cache_v, block_table, t_vec,
                window=jnp.asarray(window, jnp.int32),
                softcap=cfg.logit_softcap,
                k_scales=k_scales, v_scales=v_scales)[:, None]  # [B,1,Hq,hd]
            out = out.astype(x_t.dtype).reshape(B, 1, nq * hd) @ params["wo"]
            return out, ret_k, ret_v
        P = block_table.shape[1]
        Smax = P * ps
        if k_scales is not None:
            att_k = (cache_k[block_table].astype(jnp.float32)
                     * k_scales[block_table][:, :, None, :, None]
                     ).reshape(B, Smax, nkv, hd)
            att_v = (cache_v[block_table].astype(jnp.float32)
                     * v_scales[block_table][:, :, None, :, None]
                     ).reshape(B, Smax, nkv, hd)
        else:
            att_k = cache_k[block_table].reshape(B, Smax, nkv, hd)
            att_v = cache_v[block_table].reshape(B, Smax, nkv, hd)

    k_pos = jnp.arange(Smax, dtype=jnp.int32)
    mask = k_pos[None, :] <= t_vec[:, None]                         # [B, Smax]
    w = jnp.asarray(window, jnp.int32)
    mask &= jnp.where(w > 0, k_pos[None, :] > t_vec[:, None] - w, True)
    out = _decode_sdpa(q, att_k, att_v, mask, cfg.logit_softcap)
    out = out.astype(x_t.dtype).reshape(B, 1, nq * hd) @ params["wo"]
    return out, ret_k, ret_v


def attn_chunk(
    params: dict,
    x: jax.Array,                 # [B, Cs, D] one prompt chunk
    cache_k: jax.Array,           # [B, Smax, Hkv, hd] dense KV cache — or,
    cache_v: jax.Array,           #   with block_table, a pool [NP,ps,Hkv,hd]
    start,                        # traced int32: absolute position of chunk[0]
    *,
    cfg,
    window=0,
    kv_len=None,                  # traced int32: keys >= kv_len masked
    block_table=None,             # [B, P] int32 page ids (paged KV pool)
) -> tuple:
    """Chunked-prefill attention: append one prompt chunk to a dense KV
    cache and attend its queries over everything cached so far (earlier
    chunks + the causal prefix of this one). `start` is traced, so one
    compile serves every chunk of every prompt; the last (right-padded)
    chunk rides in with `kv_len = start + valid` so pad keys never score.

    With `block_table` the caches are a shared page pool: the chunk's K/V
    scatter into the pages backing positions start..start+Cs-1 (pad
    positions past the row's allocation map to the null page 0 — their
    writes are unreachable and their keys sit past kv_len anyway), and
    attention runs over the prefix's pages — in-kernel (cfg.paged_attn
    "kernel") or via a transient dense gather (the fallback, bit-exact vs
    the dense chunk path since masked stale pages contribute exactly 0)."""
    B, Cs, _ = x.shape
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    positions = (jnp.asarray(start, jnp.int32) +
                 jnp.arange(Cs, dtype=jnp.int32))                  # [Cs]

    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, Cs, nq, hd)
    k = k.reshape(B, Cs, nkv, hd)
    v = v.reshape(B, Cs, nkv, hd)

    from repro.models.layers import rope_angles
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)          # [Cs, hd/2]
    q = apply_rope(q, cos[:, None, :], sin[:, None, :])
    k = apply_rope(k, cos[:, None, :], sin[:, None, :])

    k_scales = v_scales = None
    if isinstance(cache_k, tuple):
        cache_k, k_scales = cache_k
        cache_v, v_scales = cache_v
    if block_table is None:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, positions[0], 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, positions[0], 0, 0))
        att_k, att_v = cache_k, cache_v
        Smax = cache_k.shape[1]
        ret_k, ret_v = cache_k, cache_v
    else:
        ps = cache_k.shape[1]
        rows = jnp.arange(B)
        pages = block_table[rows[:, None], positions[None, :] // ps]  # [B,Cs]
        if k_scales is not None:
            cache_k, k_scales = Q.scatter_chunk(
                cache_k, k_scales, pages, positions[None, :] % ps, k)
            cache_v, v_scales = Q.scatter_chunk(
                cache_v, v_scales, pages, positions[None, :] % ps, v)
            ret_k, ret_v = (cache_k, k_scales), (cache_v, v_scales)
        else:
            cache_k = cache_k.at[pages, positions[None, :] % ps].set(
                k.astype(cache_k.dtype))
            cache_v = cache_v.at[pages, positions[None, :] % ps].set(
                v.astype(cache_v.dtype))
            ret_k, ret_v = cache_k, cache_v
        P = block_table.shape[1]
        Smax = P * ps
        from repro.kernels import paged_attn as PAGED
        if PAGED.resolve_mode(cfg) == "kernel":
            kvl = jnp.asarray(Smax if kv_len is None else kv_len, jnp.int32)
            out = PAGED.paged_attn_chunk(
                q, cache_k, cache_v, block_table, positions[0], kvl,
                window=jnp.asarray(window, jnp.int32),
                softcap=cfg.logit_softcap,
                k_scales=k_scales, v_scales=v_scales)      # [B,Cs,Hq,hd] f32
            out = out.astype(x.dtype).reshape(B, Cs, nq * hd) @ params["wo"]
            return out, ret_k, ret_v
        if k_scales is not None:
            att_k = (cache_k[block_table].astype(jnp.float32)
                     * k_scales[block_table][:, :, None, :, None]
                     ).reshape(B, Smax, nkv, hd)
            att_v = (cache_v[block_table].astype(jnp.float32)
                     * v_scales[block_table][:, :, None, :, None]
                     ).reshape(B, Smax, nkv, hd)
        else:
            att_k = cache_k[block_table].reshape(B, Smax, nkv, hd)
            att_v = cache_v[block_table].reshape(B, Smax, nkv, hd)

    k_pos = jnp.arange(Smax, dtype=jnp.int32)
    kvl = jnp.asarray(Smax if kv_len is None else kv_len, jnp.int32)
    out = sdpa_chunked(
        q, att_k, att_v, positions, k_pos,
        jnp.asarray(window, jnp.int32), kvl,
        causal=True, softcap=cfg.logit_softcap)
    out = out.reshape(B, Cs, nq * hd) @ params["wo"]
    return out, ret_k, ret_v


def cross_attn_decode(params: dict, x_t: jax.Array, memory: jax.Array, *, cfg):
    """Single-token cross attention over a fixed encoder/image memory."""
    B = x_t.shape[0]
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    Sk = memory.shape[1]
    q = (x_t @ params["wq"]).reshape(B, 1, nq, hd)
    k = (memory @ params["wk"]).reshape(B, Sk, nkv, hd)
    v = (memory @ params["wv"]).reshape(B, Sk, nkv, hd)
    mask = jnp.ones((Sk,), bool)
    out = _decode_sdpa(q, k, v, mask, cfg.logit_softcap)
    return out.astype(x_t.dtype).reshape(B, 1, nq * hd) @ params["wo"]


# ------------------------------------------- multi-head latent attention

def mla_init(key, cfg) -> dict:
    """Latent-attention weights (DeepSeek-V2/V3, no query LoRA). `wq`
    [d, H*hd]: each head's `hd - rope` plain then `rope` rotary query
    columns; `wkva` [d, rank + rope]: the latent, then the shared rotary
    key; `kv_norm` the latent's RMSNorm; `wkv_b` [rank, H, nope + v]: each
    head's key (first `nope`) and value up-projections of the latent; `wo`
    [H*v, d]. The rotary columns are in this module's half-split layout
    (`layers.apply_rope`): a checkpoint in the interleaved-pair layout of
    the published model has its rotary columns of `wq` and `wkva` permuted
    to [0, 2, .., rope-2, 1, 3, .., rope-1] on loading."""
    d, H, hd = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim()
    r, vd = cfg.kv_lora_rank, cfg.v_head_dim
    nope = hd - cfg.qk_rope_dim
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = split(key, 4)
    return {
        "wq": dense_init(k1, d, H * hd, dt),
        "wkva": dense_init(k2, d, cfg.latent_width(), dt),
        "kv_norm": rmsnorm_init(r),
        "wkv_b": dense_init(k3, r, H * (nope + vd), dt).reshape(
            r, H, nope + vd),
        "wo": dense_init(k4, H * vd, d, dt),
    }


def _mla_project(params, x, positions, cfg):
    """x [B, S, d] at `positions` ([S], or [B, S]) -> (plain query part
    [B, S, H, nope], rotated query part [B, S, H, rope], latent rows
    [B, S, cfg.latent_row_width()]: the normalised latent, the rotated
    shared key, zeros — what the cache holds)."""
    B, S, _ = x.shape
    H, hd, rd = cfg.num_heads, cfg.resolved_head_dim(), cfg.qk_rope_dim
    r = cfg.kv_lora_rank
    cos, sin = rope_angles(positions, rd, cfg.rope_theta)
    cos, sin = cos[..., None, :], sin[..., None, :]
    with jax.named_scope("mla.q"):
        q = (x @ params["wq"]).reshape(B, S, H, hd)
        q_rope = apply_rope(q[..., hd - rd:], cos, sin)
    with jax.named_scope("mla.kv_a"):
        kv = x @ params["wkva"]
        c = rmsnorm(params["kv_norm"], kv[..., :r], cfg.norm_eps)
        k_rope = apply_rope(kv[..., None, r:], cos, sin)[..., 0, :]
        pad = jnp.zeros((*c.shape[:-1], cfg.latent_row_width()
                         - cfg.latent_width()), c.dtype)
        row = jnp.concatenate([c, k_rope.astype(c.dtype), pad], axis=-1)
    return q[..., :hd - rd], q_rope, row


def _mla_plain(params, q_nope, q_rope, lat, q_pos, k_pos, kv_len, cfg, *,
               flash: bool):
    """The published (non-absorbed) form over latent rows `lat` [B, Sk, W]:
    per head k = [c W_UK | k_rope], v = c W_UV, scores (q . k) / sqrt(hd),
    causal softmax. Returns [B, Sq, H*v]. The values ride the blocked
    attention padded to the query width and are cut back after it."""
    B, Sk, _ = lat.shape
    H, hd, r, vd = (cfg.num_heads, cfg.resolved_head_dim(), cfg.kv_lora_rank,
                    cfg.v_head_dim)
    nope = hd - cfg.qk_rope_dim
    if vd > hd:
        raise ValueError(f"v_head_dim={vd} > head_dim={hd}")
    kvb = jnp.einsum("bsc,chn->bshn", lat[..., :r], params["wkv_b"])
    k_rope = jnp.broadcast_to(lat[:, :, None, r:r + hd - nope],
                              (B, Sk, H, hd - nope))
    k = jnp.concatenate([kvb[..., :nope], k_rope.astype(kvb.dtype)], -1)
    v = jnp.pad(kvb[..., nope:], ((0, 0), (0, 0), (0, 0), (0, hd - vd)))
    q = jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)], -1)
    if flash:
        out = sdpa_flash(q, k, v, q_pos, k_pos, jnp.asarray(0, jnp.int32),
                         kv_len, True, 0.0, 1024)
    else:
        out = sdpa_chunked(q, k, v, q_pos, k_pos, jnp.asarray(0, jnp.int32),
                           kv_len, causal=True)
    Sq = q.shape[1]
    return out[..., :vd].reshape(B, Sq, H * vd)


def mla_forward(params: dict, x: jax.Array, *, cfg,
                positions: jax.Array) -> tuple:
    """Full-sequence causal latent attention, x [B, S, d] -> (out
    [B, S, d], latent rows [B, S, cfg.latent_row_width()] for the
    cache)."""
    S = x.shape[1]
    q_nope, q_rope, row = _mla_project(params, x, positions, cfg)
    pos = positions.astype(jnp.int32)
    with jax.named_scope("mla.attend"):
        out = _mla_plain(params, q_nope, q_rope, row, pos, pos,
                         jnp.asarray(S + 10**9, jnp.int32), cfg, flash=True)
    with jax.named_scope("mla.out"):
        out = out @ params["wo"]
    return out, row


def _gather_latent(cache, l, block_table):
    """Layer `l`'s latent rows of each batch row [B, Smax, W]: a dense
    [L, B, Smax, W] cache's slice, or a paged [L, NP, ps, W] pool's pages
    gathered through the block table (no slice of the layer's pool)."""
    if block_table is None:
        return jax.lax.dynamic_index_in_dim(cache, l, 0, keepdims=False)
    B, P = block_table.shape
    return cache[l, block_table].reshape(B, P * cache.shape[2], -1)


def mla_chunk(params: dict, x: jax.Array, cache, l, start, *, cfg, kv_len,
              block_table=None) -> tuple:
    """Chunked-prefill latent attention: append the chunk's latent rows (x
    [B, Cs, d] at start..start+Cs-1) to layer `l` of the stacked cache —
    dense [L, B, Smax, W], or with `block_table` the page pool
    [L, NP, ps, W] — and attend over everything cached so far in the
    published form. `kv_len` masks the last chunk's pads. Returns (out,
    cache)."""
    B, Cs, _ = x.shape
    positions = jnp.asarray(start, jnp.int32) + jnp.arange(Cs, dtype=jnp.int32)
    q_nope, q_rope, row = _mla_project(params, x, positions, cfg)
    with jax.named_scope("kv_write"):
        row = row.astype(cache.dtype)
        if block_table is None:
            cache = jax.lax.dynamic_update_slice(
                cache, row[None], (l, 0, positions[0], 0))
        else:
            ps = cache.shape[2]
            rows = jnp.arange(B)
            pages = block_table[rows[:, None], positions[None, :] // ps]
            cache = cache.at[l, pages, positions[None, :] % ps].set(row)
    with jax.named_scope("mla.attend"):
        lat = _gather_latent(cache, l, block_table)
        k_pos = jnp.arange(lat.shape[1], dtype=jnp.int32)
        out = _mla_plain(params, q_nope, q_rope, lat.astype(x.dtype),
                         positions, k_pos, jnp.asarray(kv_len, jnp.int32),
                         cfg, flash=False)
    with jax.named_scope("mla.out"):
        out = out.astype(x.dtype) @ params["wo"]
    return out, cache


def mla_decode(params: dict, x_t: jax.Array, cache, l, t, *, cfg,
               block_table=None) -> tuple:
    """Single-token latent attention in the absorbed form. x_t [B, 1, d] at
    positions t (scalar or [B]); `cache` is the stacked latent cache (dense
    [L, B, Smax, W], or with `block_table` the page pool [L, NP, ps, W]) and
    `l` the layer. Only the new token's latent row is written. The query's
    plain part is taken into the latent space (q W_UK^T), so every head
    scores the cached rows directly — keys are all W columns of a row,
    values its first `rank` — and the attended latent leaves through W_UV:
    the same sums as the published form, in another order. The query is
    padded with zeros to the stored row width, so the rows' zero tail adds
    nothing to a score. Returns (out [B, 1, d], cache)."""
    B = x_t.shape[0]
    H, hd, r, vd = (cfg.num_heads, cfg.resolved_head_dim(), cfg.kv_lora_rank,
                    cfg.v_head_dim)
    nope = hd - cfg.qk_rope_dim
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (B,))
    q_nope, q_rope, row = _mla_project(params, x_t, t_vec[:, None], cfg)
    rows = jnp.arange(B)
    with jax.named_scope("kv_write"):
        new = row[:, 0].astype(cache.dtype)
        if block_table is None:
            cache = cache.at[l, rows, t_vec].set(new)
        else:
            ps = cache.shape[2]
            cache = cache.at[l, block_table[rows, t_vec // ps],
                             t_vec % ps].set(new)
    w = params["wkv_b"]
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w[..., :nope],
                           preferred_element_type=jnp.float32)
        pad = jnp.zeros((B, H, cfg.latent_row_width() - cfg.latent_width()),
                        x_t.dtype)
        q = jnp.concatenate([q_lat.astype(x_t.dtype),
                             q_rope[:, 0].astype(x_t.dtype), pad], -1)
    scale = hd ** -0.5
    with jax.named_scope("mla.attend"):
        from repro.kernels import paged_attn as PAGED
        if block_table is not None and PAGED.resolve_mode(cfg) == "kernel":
            o = PAGED.mla_paged_decode(q, cache, l, block_table, t_vec,
                                       rank=r, scale=scale)
        else:
            lat = _gather_latent(cache, l, block_table)        # [B, S, W]
            s = jnp.einsum("bhw,bsw->bhs", q, lat.astype(q.dtype),
                           preferred_element_type=jnp.float32) * scale
            k_pos = jnp.arange(lat.shape[1], dtype=jnp.int32)
            s = jnp.where((k_pos[None, :] <= t_vec[:, None])[:, None, :], s,
                          NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhs,bsc->bhc", p.astype(lat.dtype), lat[..., :r],
                           preferred_element_type=jnp.float32)
    with jax.named_scope("mla.out"):
        o = jnp.einsum("bhc,chv->bhv", o.astype(x_t.dtype), w[..., nope:])
        out = o.reshape(B, 1, H * vd) @ params["wo"]
    return out, cache

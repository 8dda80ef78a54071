"""Fused paged-attention — decode (and chunked-prefill) attention that walks
the block table in-kernel instead of gathering pages back into the dense
[B, max_tokens] layout per layer per tick.

PIM mapping: the paper caches decode state near the compute instead of
re-materializing it (the GO cache's "cache, don't recompute" discipline);
this kernel is the attention-side sibling. The dense gather reads EVERY
page slot of every block table each tick — bandwidth scales with
`max_tokens`. Here the grid walks (batch row, logical page) with the block
table scalar-prefetched, so each step stages exactly ONE physical page into
VMEM; pages past the row's position `t` skip their FLOPs via `pl.when` AND
resolve to the block index of the row's last live page, so the pipeline
re-uses the staged buffer instead of issuing fresh HBM copies — per-tick
traffic scales with LIVE tokens.

Kernels:
  paged_attn_decode(q, k_pages, v_pages, block_table, t)   -> [B, Hq, hd]
      one query per row, online softmax over the row's pages; reproduces
      models/attention.py::_decode_sdpa over the gathered layout (masking
      `k_pos <= t` + sliding window, GQA head broadcast, logit softcap) to
      within fp accumulation-order differences (online vs one-shot softmax).
  paged_attn_chunk(q, k_pages, v_pages, block_table, start, kv_len)
      chunked prefill: a [B, Cs] query chunk attends over the prefix's
      pages (causal within the chunk) without re-materializing the dense
      layout per chunk.

Masking rules (matching the gather path exactly):
  decode   k_pos <= t,              and k_pos > t - window     (window > 0)
  chunk    k_pos <  kv_len,  k_pos <= q_pos,  k_pos > q_pos - window

Null pages need no special-casing for CORRECTNESS — every position they
back is already masked by the rules above (block tables only map live
positions to real pages) — but they are where the bandwidth win comes
from: a dead page's block index repeats the last live one, constant across
the tail of the row, so only compute-live pages cost HBM traffic.

`interpret=None` auto-selects from the lowering context exactly like
kernels/moe_gmm.py (pallas lowers via Mosaic only on TPU; CPU CI runs the
same kernel body in interpret mode), and the resolved value is part of the
jit cache key. Under a GSPMD mesh the kernel runs replicated on every
device (`moe_gmm.py::mosaic_call`) — a Mosaic kernel has no partitioning
rule; a page-parallel shard_map variant is the ROADMAP follow-up for real
multi-chip TPU.

`resolve_mode(cfg)` is the path selector consumed by models/attention.py
and launch/sharding.py: cfg.paged_attn "kernel" / "gather" are explicit,
"auto" picks the kernel wherever Mosaic can lower it (TPU) and the gather
fallback elsewhere — CPU CI opts into the kernel explicitly (the
REPRO_FORCE_PAGED_KERNEL lane) or per-test via cfg overrides.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_gmm import (default_interpret, lowering_platform,
                                   mosaic_call)

NEG_INF = -1e30


def resolve_mode(cfg) -> str:
    """The paged-attention realization for `cfg`: "kernel" (this module) or
    "gather" (attention.py's dense re-materialization). cfg.paged_attn
    "auto" resolves per lowering platform, like the MoE backend."""
    mode = getattr(cfg, "paged_attn", "auto")
    if mode == "auto":
        return "kernel" if lowering_platform() == "tpu" else "gather"
    if mode not in ("kernel", "gather"):
        raise ValueError(
            f"cfg.paged_attn={mode!r} (want 'auto', 'kernel' or 'gather')")
    return mode


# ------------------------------------------------------------- shared step
#
# Mosaic-friendly layout: the kernels never reshape, transpose or slice a
# head out of a VMEM tile. A page [ps, Hkv, hd] is viewed (free, in HBM) as
# [ps*Hkv, hd] — row c holds position c // Hkv, kv head c % Hkv — and the
# queries as [R, hd], one row per (query position, q head). One 2-D matmul
# scores every query row against every page row; `ok` keeps only the rows of
# the query's own kv head (GQA grouping) at attendable positions, so masked
# rows leave exactly 0 in the softmax. The matmuls, exp and masking do Hkv x
# the useful work (32x for an MHA model like llama_moe_4_16). Decode absorbs
# it, being bound by page bytes; chunked prefill is compute-bound and pays
# it in full.

NO_WINDOW = 1 << 30     # a window span no position reaches


def _span(w):
    """Keys must sit at k_pos > q_pos - span: `w` inside a sliding window,
    an unreachable span for global attention (w <= 0). A scalar i32 select,
    so no boolean select reaches a vector mask."""
    return jnp.where(w > 0, w, NO_WINDOW)


def _reset(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finish(o_ref, l_ref, acc_ref):
    o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)


def _row_scales(s_ref, head):
    """Per-query-row scale [R, 1] from a page's per-kv-head scales [1, Hkv]:
    a masked lane sum with one nonzero term, so exact in f32."""
    hkv = s_ref.shape[-1]
    pick = head == jax.lax.broadcasted_iota(jnp.int32, (1, hkv), 1)
    return jnp.sum(jnp.where(pick, s_ref[...], 0.0), axis=-1, keepdims=True)


def _attend_page(q, k_ref, v_ref, ks_ref, vs_ref, head, ok, m_ref, l_ref,
                 acc_ref, *, scale: float, softcap: float):
    """One online-softmax step of R query rows [R, hd] against one page
    [ps*Hkv, hd]. `head` [R, 1] is each row's kv head, `ok` [R, ps*Hkv] the
    mask. int8 pages (ks_ref/vs_ref given) run in f32 and fold their scales
    into the scores and the weighted values per row."""
    k, v = k_ref[...], v_ref[...]
    if ks_ref is not None:
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if ks_ref is not None:
        s = s * _row_scales(ks_ref, head)
    s = s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    if vs_ref is not None:
        pv = pv * _row_scales(vs_ref, head)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new


def _page_specs(ps: int, hkv: int, hd: int, quant: bool, page_index):
    """BlockSpecs for the K and V page views [NP, ps*Hkv, hd] (plus their
    [NP, 1, Hkv] scale views when quantized), all gathered by `page_index`
    (grid indices + scalar-prefetch refs -> physical page)."""
    page = pl.BlockSpec((pl.squeezed, ps * hkv, hd),
                        lambda *a: (page_index(*a), 0, 0))
    specs = [page, page]
    if quant:
        sc = pl.BlockSpec((pl.squeezed, 1, hkv),
                          lambda *a: (page_index(*a), 0, 0))
        specs += [sc, sc]
    return specs


def _page_operands(k_pages, v_pages, k_scales, v_scales):
    NP, ps, hkv, hd = k_pages.shape
    ops = [k_pages.reshape(NP, ps * hkv, hd), v_pages.reshape(NP, ps * hkv, hd)]
    if k_scales is not None:
        ops += [s.astype(jnp.float32).reshape(NP, 1, hkv)
                for s in (k_scales, v_scales)]
    return ops


# --------------------------------------------------------------------- decode

def _decode_kernel(bt_ref, tv_ref, wv_ref, q_ref, k_ref, v_ref, *rest,
                   ps: int, hkv: int, group: int, num_pages: int,
                   softcap: float, scale: float, quant: bool = False):
    """Grid (b, j): batch row b, logical page j (j innermost — the online-
    softmax reduction axis). Scalar-prefetched refs: block table [B, P],
    positions [B], window [1]. With `quant`, two extra operands carry the
    page's per-kv-head scales ([1, Hkv] blocks gathered by the same
    block-table index map) and the int8 page dequantizes in-kernel — HBM
    traffic drops with the storage dtype while compute stays fp32."""
    ks_ref, vs_ref = rest[:2] if quant else (None, None)
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    b, j = pl.program_id(0), pl.program_id(1)
    t = tv_ref[b]
    span = _span(wv_ref[0])

    @pl.when(j == 0)
    def _init():
        _reset(m_ref, l_ref, acc_ref)

    base = j * ps
    # live <=> the page holds at least one attendable position: some
    # k_pos in [base, base+ps-1] with k_pos <= t (and inside the window).
    # Dead pages skip ALL work.
    @pl.when((base <= t) & (base + ps - 1 > t - span))
    def _attend():
        R, C = q_ref.shape[0], ps * hkv
        head = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // group
        col = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        k_pos = base + col // hkv
        ok = (col % hkv == head) & (k_pos <= t) & (k_pos > t - span)
        _attend_page(q_ref[...], k_ref, v_ref, ks_ref, vs_ref, head, ok,
                     m_ref, l_ref, acc_ref, scale=scale, softcap=softcap)

    @pl.when(j == num_pages - 1)
    def _done():
        _finish(o_ref, l_ref, acc_ref)


def paged_attn_decode(q, k_pages, v_pages, block_table, t, *, window=0,
                      softcap: float = 0.0, k_scales=None, v_scales=None,
                      interpret: bool | None = None) -> jax.Array:
    """Single-token paged decode attention.

    q [B, Hq, hd] (post-RoPE); k_pages/v_pages [NP, ps, Hkv, hd] (one
    layer's pool, the new token already scattered in); block_table [B, P]
    int32; t scalar or [B] int32 (current position per row); window a
    traced int32 scalar (0 = global). `k_scales`/`v_scales` [NP, Hkv] f32
    mark a QUANTIZED pool (int8 pages): the kernel gathers each page's
    scales alongside it and dequantizes in-kernel. Returns fp32 [B, Hq, hd]
    — the pre-`wo` attention output, matching _decode_sdpa's epilogue
    dtype."""
    if interpret is None:
        interpret = default_interpret()
    B, Hq, hd = q.shape
    Hkv = k_pages.shape[2]
    if Hq % Hkv:
        raise ValueError(f"num_heads={Hq} must be a multiple of "
                         f"num_kv_heads={Hkv}")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (B,))
    return _paged_attn_decode(q, k_pages, v_pages, block_table, t_vec,
                              jnp.asarray(window, jnp.int32),
                              k_scales, v_scales,
                              softcap=float(softcap), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def _paged_attn_decode(q, k_pages, v_pages, block_table, t_vec, window,
                       k_scales, v_scales, *, softcap, interpret):
    B, Hq, hd = q.shape
    NP, ps, Hkv, _ = k_pages.shape
    P = block_table.shape[1]
    quant = k_scales is not None
    bt = block_table.astype(jnp.int32)
    tv = t_vec.astype(jnp.int32)
    wv = window.astype(jnp.int32).reshape(1)

    def page_index(b, j, bt, tv, wv):
        # pages past the row's position repeat its last live page, so the
        # pipeline skips their copies (their compute is skipped in-kernel)
        return bt[b, jnp.minimum(j, tv[b] // ps)]

    row_spec = pl.BlockSpec((pl.squeezed, Hq, hd),
                            lambda b, j, bt, tv, wv: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=[row_spec] + _page_specs(ps, Hkv, hd, quant, page_index),
        out_specs=row_spec,
        scratch_shapes=[pltpu.VMEM((Hq, 1), jnp.float32),
                        pltpu.VMEM((Hq, 1), jnp.float32),
                        pltpu.VMEM((Hq, hd), jnp.float32)],
    )
    return mosaic_call(pl.pallas_call(
        functools.partial(_decode_kernel, ps=ps, hkv=Hkv, group=Hq // Hkv,
                          num_pages=P, softcap=softcap,
                          scale=1.0 / (hd ** 0.5), quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), jnp.float32),
        interpret=interpret,
    ), bt, tv, wv, q, *_page_operands(k_pages, v_pages, k_scales, v_scales))


# -------------------------------------------------------------- chunk prefill

SCORE_BUDGET = 1 << 18  # f32 elements of one chunk step's [rows, ps*Hkv] scores


def query_tile(cs: int, heads: int, cols: int) -> int:
    """Query positions per chunk-kernel step: the largest divisor of the
    chunk `cs` whose [tq*heads, cols] score block fits SCORE_BUDGET and whose
    row count is a multiple of 8 (the TPU block tiling) or the whole chunk."""
    fits = [tq for tq in range(cs, 0, -1)
            if cs % tq == 0 and (tq == cs or tq * heads % 8 == 0)]
    return next((tq for tq in fits if tq * heads * cols <= SCORE_BUDGET),
                fits[-1])


def _chunk_kernel(bt_ref, sv_ref, kl_ref, wv_ref, q_ref, k_ref, v_ref, *rest,
                  ps: int, hkv: int, heads: int, group: int, tq: int,
                  num_pages: int, softcap: float, scale: float,
                  quant: bool = False):
    """Grid (b, i, j): query tile i (tq chunk positions x Hq heads) of batch
    row b against the row's page j. Scalar-prefetched: block table [B, P],
    start [1], kv_len [1], window [1]. `quant` adds per-page scale operands
    exactly as in _decode_kernel."""
    ks_ref, vs_ref = rest[:2] if quant else (None, None)
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    i, j = pl.program_id(1), pl.program_id(2)
    q0 = sv_ref[0] + i * tq                 # the tile's first query position
    kvl = kl_ref[0]
    span = _span(wv_ref[0])

    @pl.when(j == 0)
    def _init():
        _reset(m_ref, l_ref, acc_ref)

    base = j * ps
    # a page is live iff it can hold a key some query of the tile attends:
    # k_pos < kv_len, k_pos <= the LAST query (causal) and (window) k_pos
    # reaching past the EARLIEST query's window start.
    @pl.when((base < kvl) & (base <= q0 + tq - 1)
             & (base + ps - 1 > q0 - span))
    def _attend():
        R, C = q_ref.shape[0], ps * hkv
        row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        head = row % heads // group
        q_pos = q0 + row // heads
        col = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        k_pos = base + col // hkv
        ok = ((col % hkv == head) & (k_pos < kvl) & (k_pos <= q_pos)
              & (k_pos > q_pos - span))
        _attend_page(q_ref[...], k_ref, v_ref, ks_ref, vs_ref, head, ok,
                     m_ref, l_ref, acc_ref, scale=scale, softcap=softcap)

    @pl.when(j == num_pages - 1)
    def _done():
        _finish(o_ref, l_ref, acc_ref)


def paged_attn_chunk(q, k_pages, v_pages, block_table, start, kv_len, *,
                     window=0, softcap: float = 0.0,
                     k_scales=None, v_scales=None,
                     interpret: bool | None = None) -> jax.Array:
    """Chunked-prefill attention over a paged pool.

    q [B, Cs, Hq, hd] (post-RoPE, the chunk's K/V already scattered into
    the pool's pages); block_table [B, P]; start / kv_len traced int32
    scalars (chunk-absolute start, total valid key count — pads in the
    last chunk carry q_pos >= kv_len and are discarded by the caller).
    `k_scales`/`v_scales` [NP, Hkv] f32 mark a quantized (int8) pool —
    see paged_attn_decode. Returns fp32 [B, Cs, Hq, hd]."""
    if interpret is None:
        interpret = default_interpret()
    B, Cs, Hq, hd = q.shape
    Hkv = k_pages.shape[2]
    if Hq % Hkv:
        raise ValueError(f"num_heads={Hq} must be a multiple of "
                         f"num_kv_heads={Hkv}")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    return _paged_attn_chunk(q, k_pages, v_pages, block_table,
                             jnp.asarray(start, jnp.int32),
                             jnp.asarray(kv_len, jnp.int32),
                             jnp.asarray(window, jnp.int32),
                             k_scales, v_scales,
                             softcap=float(softcap), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def _paged_attn_chunk(q, k_pages, v_pages, block_table, start, kv_len,
                      window, k_scales, v_scales, *, softcap, interpret):
    B, Cs, Hq, hd = q.shape
    NP, ps, Hkv, _ = k_pages.shape
    P = block_table.shape[1]
    quant = k_scales is not None
    tq = query_tile(Cs, Hq, ps * Hkv)
    bt = block_table.astype(jnp.int32)
    sv = start.astype(jnp.int32).reshape(1)
    kl = kv_len.astype(jnp.int32).reshape(1)
    wv = window.astype(jnp.int32).reshape(1)

    def page_index(b, i, j, bt, sv, kl, wv):
        # pages past the tile's last attendable key repeat that key's page
        last = jnp.minimum(kl[0], sv[0] + (i + 1) * tq) - 1
        return bt[b, jnp.clip(j, 0, jnp.maximum(last, 0) // ps)]

    rows_spec = pl.BlockSpec((pl.squeezed, tq * Hq, hd),
                             lambda b, i, j, bt, sv, kl, wv: (b, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Cs // tq, P),
        in_specs=[rows_spec] + _page_specs(ps, Hkv, hd, quant, page_index),
        out_specs=rows_spec,
        scratch_shapes=[pltpu.VMEM((tq * Hq, 1), jnp.float32),
                        pltpu.VMEM((tq * Hq, 1), jnp.float32),
                        pltpu.VMEM((tq * Hq, hd), jnp.float32)],
    )
    out = mosaic_call(pl.pallas_call(
        functools.partial(_chunk_kernel, ps=ps, hkv=Hkv, heads=Hq,
                          group=Hq // Hkv, tq=tq, num_pages=P,
                          softcap=softcap, scale=1.0 / (hd ** 0.5),
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Cs * Hq, hd), jnp.float32),
        interpret=interpret,
    ), bt, sv, kl, wv, q.reshape(B, Cs * Hq, hd),
        *_page_operands(k_pages, v_pages, k_scales, v_scales))
    return out.reshape(B, Cs, Hq, hd)


# ------------------------------------------------------------ traffic model

def page_bytes(cfg, page_size: int) -> int:
    """HBM bytes one physical page costs to stage (K + V), per layer.
    Quantized pools pay int8 values plus one f32 scale per (page, kv
    head) — the scale operand the kernel gathers alongside the page."""
    hd = cfg.resolved_head_dim()
    if getattr(cfg, "kv_quant", "none") == "int8":
        return 2 * (page_size * cfg.num_kv_heads * hd
                    + cfg.num_kv_heads * 4)
    item = jnp.dtype(cfg.dtype).itemsize
    return 2 * page_size * cfg.num_kv_heads * hd * item


def decode_tick_pages(t_host, active, page_size: int, num_slots: int,
                      pages_per_slot: int) -> tuple[int, int]:
    """Deterministic per-layer page model for one decode tick:
    (live_pages, grid_pages). The kernel stages each active row's live
    pages — floor(t/ps)+1 — out of its grid of num_slots x pages_per_slot
    steps, which walks retired rows and pages past each row's position
    too; the gather re-materializes the same num_slots x pages_per_slot
    block-table entries. Pure host arithmetic; what the serve_throughput
    `paged_attn` section (and its regression gate) and the engine's tick
    counters (`ServingEngine.last_tick`) use."""
    live = sum(int(t_host[i]) // page_size + 1
               for i in range(num_slots) if active[i])
    return live, num_slots * pages_per_slot

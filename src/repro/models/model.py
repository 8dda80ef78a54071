"""Language-model assembly for every assigned architecture family.

One schema (`ModelConfig`) drives five structural families:

  attn      dense / MoE decoder-only transformers (starcoder2, granite-8b,
            qwen2, gemma3, deepseek-moe, granite-moe, llama_moe_4_16)
  attn+enc  whisper-style encoder-decoder (encoder_layers > 0)
  attn+x    llama-3.2-vision: cross-attention image layers every Nth layer
  xlstm     mLSTM stacks with interleaved sLSTM blocks
  mamba2    zamba2: Mamba2 stack with a weight-shared attention block

Public API:
  model_init(key, cfg)                                   -> params
  model_forward(params, tokens, cfg, extras)             -> (x_final, aux_loss)
  logits_from_hidden(params, x, cfg)                     -> [.., V]
  loss_fn(params, batch, cfg)                            -> (loss, metrics)
  init_decode_state(cfg, batch, max_len, extras)         -> state pytree
  prefill(params, tokens, cfg, extras)                   -> (state, last_logits)
  serve_step(params, state, tokens_t, cfg)               -> (logits, state)
  init_decode_slot(state, slot)                          -> state (slot reset)
  write_decode_slot(state, slot, src_state[, page_ids])  -> state (slot filled)
  prefill_chunk(params, state, tokens, cfg, start, vl)   -> (state, logits)

Decode state comes in two layouts: DENSE (per-slot KV rows
[L, B, max_len, h, hd]) and PAGED (`init_decode_state(paged=(num_pages,
page_size))` — a shared page pool [L, NP, ps, h, hd] plus a per-slot
block_table of physical page ids; serving/pool.py owns the host-side page
allocator). serve_step picks the attention path from the state's keys, so
both layouts run through the same engine.

Decode positions: `state["t"]` is either a scalar (static batch — every row in
lock-step, the classic generate() path) or an int32 vector [B] (per-slot —
the continuous-batching pool in repro/serving, where each slot sits at its
own offset). All decode kernels broadcast the scalar form to the vector form
internally, so both run the same compiled graph.

All layer stacks are scanned (jax.lax.scan over stacked params) so the HLO
stays compact at 62-100 layers; heterogeneous families scan homogeneous
segments. `cfg.remat` wraps scan bodies in jax.checkpoint.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import moe as MOE
from repro.core import quant as Q
from repro.core.go_cache import (GOCache, go_cache_init, go_cache_init_slot,
                                 go_cache_prefill, go_cache_write_slot)
from repro.core.grouping import default_groups, group_of_expert_from_groups
from repro.kernels.ops import ExpertStack
from repro.models import attention as ATT
from repro.models import blocks as B
from repro.models.layers import (dense_init, dtype_of, embed_init, rmsnorm,
                                 rmsnorm_init, split, stack_init)
from repro.models.ssm import mamba2_init_state
from repro.models.xlstm import mlstm_init_state, slstm_init_state


# ----------------------------------------------------------------- structure

def layer_windows(cfg) -> np.ndarray:
    """Per-layer sliding-window spans (0 = global attention)."""
    L = cfg.num_layers
    if cfg.local_global_ratio > 0:
        r = cfg.local_global_ratio
        return np.array(
            [cfg.sliding_window if (l % (r + 1)) != r else 0 for l in range(L)],
            np.int32)
    if cfg.sliding_window > 0:
        return np.full(L, cfg.sliding_window, np.int32)
    return np.zeros(L, np.int32)


@functools.lru_cache(maxsize=None)
def _moe_deployment(moe_cfg):
    """Deployment-time C2 artifacts, computed ONCE per MoE config (host-side
    numpy) instead of inside every traced forward: the [E] group-id map and
    the [G, g] member matrix the group-multiplexed paths consume. Cached as
    numpy: a jax array made while a jit traces is that trace's tracer."""
    groups = default_groups(moe_cfg)
    return (np.asarray(group_of_expert_from_groups(groups), np.int32),
            np.asarray(groups, np.int32))


def expert_groups(cfg) -> jax.Array | None:
    """C2 grouping -> [E] group id per expert (None for non-MoE)."""
    if cfg.moe is None:
        return None
    return jnp.asarray(_moe_deployment(cfg.moe)[0])


def expert_group_members(cfg) -> jax.Array | None:
    """C2 grouping -> [G, g] expert ids per group (None for non-MoE)."""
    if cfg.moe is None:
        return None
    return jnp.asarray(_moe_deployment(cfg.moe)[1])


def _split_experts(layers: dict):
    """A layer stack without its routed-expert banks, for a serving scan's
    `xs`, and the [L, E, K, F] banks, which the scan body closes over (None
    without MoE). Scanned, each step's banks would be a slice of the stacks,
    copied whole for the grouped GEMM, which reads them in place instead."""
    moe = layers.get("moe")
    if moe is None or "experts" not in moe:
        return layers, None
    rest = {k: v for k, v in moe.items() if k != "experts"}
    return {**layers, "moe": rest}, moe["experts"]


def _with_experts(lp: dict, banks, l) -> dict:
    """Layer `l`'s params with its routed experts as an ExpertStack."""
    if banks is None:
        return lp
    return {**lp, "moe": {**lp["moe"], "experts": ExpertStack(
        banks["wg"], banks["wi"], banks["wo"], l)}}


def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def _xlstm_segments(cfg):
    """(num_segments, mlstm_per_segment); sLSTM closes each segment."""
    if cfg.slstm_every <= 0:
        return 1, cfg.num_layers
    assert cfg.num_layers % cfg.slstm_every == 0
    return cfg.num_layers // cfg.slstm_every, cfg.slstm_every - 1


def _zamba_segments(cfg):
    if cfg.attn_every <= 0:
        return 0, cfg.num_layers
    return cfg.num_layers // cfg.attn_every, cfg.attn_every


# ----------------------------------------------------------------------- init

def model_init(key, cfg) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ks = split(key, 10)
    p = {
        "embed": embed_init(ks[0], cfg.vocab_size, d, dt),
        "final_norm": rmsnorm_init(d),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], d, cfg.vocab_size, dt)

    if cfg.block == "attn":
        use_moe = cfg.moe is not None
        if cfg.encoder_layers > 0:
            # whisper-style enc-dec (no RoPE: learned decoder positions);
            # table sized for the assigned decode_32k cell
            p["pos_embed"] = (jax.random.normal(
                ks[2], (40960, d), jnp.float32) * 0.02).astype(dt)
            p["encoder"] = stack_init(
                ks[3], cfg.encoder_layers,
                lambda k: B.attn_block_init(k, cfg, gelu=True))
            p["dec_self"] = stack_init(
                ks[4], cfg.num_layers, _dec_self_init_fn(cfg))
            p["dec_cross"] = stack_init(
                ks[5], cfg.num_layers,
                lambda k: B.attn_block_init(k, cfg, cross=True, gelu=True))
            p["enc_norm"] = rmsnorm_init(d)
        elif cfg.cross_attn_every > 0:
            every = cfg.cross_attn_every
            assert cfg.num_layers % every == 0
            n_sup = cfg.num_layers // every
            n_self = every - 1
            p["layers"] = stack_init(
                ks[3], n_sup,
                lambda k: stack_init(k, n_self,
                                     lambda k2: B.attn_block_init(k2, cfg)))
            p["cross_layers"] = stack_init(
                ks[4], n_sup,
                lambda k: B.attn_block_init(k, cfg, cross=True))
        else:
            p["layers"] = stack_init(
                ks[3], cfg.num_layers,
                lambda k: B.attn_block_init(k, cfg, use_moe=use_moe))
    elif cfg.block == "xlstm":
        n_seg, n_m = _xlstm_segments(cfg)
        p["mlayers"] = stack_init(
            ks[3], n_seg,
            lambda k: stack_init(k, n_m, lambda k2: B.mlstm_block_init(k2, cfg)))
        p["slayers"] = stack_init(
            ks[4], n_seg, lambda k: B.slstm_block_init(k, cfg))
    elif cfg.block == "mamba2":
        p["layers"] = stack_init(
            ks[3], cfg.num_layers, lambda k: B.mamba2_block_init(k, cfg))
        if cfg.attn_every > 0:
            p["shared_attn"] = B.attn_block_init(ks[4], cfg)
    else:
        raise ValueError(cfg.block)
    return p


def _dec_self_init_fn(cfg):
    def init(k):
        return {"ln1": rmsnorm_init(cfg.d_model),
                "attn": ATT.attn_init(k, cfg)}
    return init


# -------------------------------------------------------------------- forward

def embed_tokens(params: dict, tokens: jax.Array, cfg) -> jax.Array:
    """Token embeddings as activations in cfg.dtype (the weights may be
    stored narrower, e.g. bf16 weights evaluated in f32)."""
    return params["embed"][tokens].astype(dtype_of(cfg))


def model_forward(params: dict, tokens: jax.Array, cfg, extras: dict | None = None):
    """tokens [B, S] -> (x_final [B, S, d] normalized, aux_balance_loss)."""
    extras = extras or {}
    x = embed_tokens(params, tokens, cfg)
    if cfg.block == "attn" and cfg.encoder_layers > 0:
        return _fwd_whisper(params, x, cfg, extras)
    S = tokens.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    if cfg.block == "attn" and cfg.cross_attn_every > 0:
        return _fwd_vlm(params, x, positions, cfg, extras)
    if cfg.block == "attn":
        return _fwd_attn(params, x, positions, cfg)
    if cfg.block == "xlstm":
        return _fwd_xlstm(params, x, cfg)
    if cfg.block == "mamba2":
        return _fwd_zamba(params, x, positions, cfg)
    raise ValueError(cfg.block)


def _fwd_attn(params, x, positions, cfg):
    goe = expert_groups(cfg)
    gm = expert_group_members(cfg)
    windows = jnp.asarray(layer_windows(cfg))

    def body(carry, xs):
        x, bal = carry
        lp, w = xs
        x, aux = B.attn_block(lp, x, cfg=cfg, positions=positions, window=w,
                              group_of_expert=goe, group_members=gm)
        if aux is not None and "balance_loss" in aux:
            bal = bal + jnp.sum(aux["balance_loss"])
        return (x, bal), None

    (x, bal), _ = jax.lax.scan(
        _maybe_remat(body, cfg), (x, jnp.zeros((), jnp.float32)),
        (params["layers"], windows))
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), bal


def _fwd_vlm(params, x, positions, cfg, extras):
    memory = extras["image_embeds"]                    # [B, I, d] stub patches

    def body(x, xs):
        self_stack, cross_p = xs
        n_self = cfg.cross_attn_every - 1
        for i in range(n_self):
            lp = jax.tree.map(lambda a: a[i], self_stack)
            x, _ = B.attn_block(lp, x, cfg=cfg, positions=positions)
        xc, _ = B.attn_block(cross_p, x, cfg=cfg, positions=positions,
                             causal=False, kv_source=memory, use_rope=False)
        return xc, None

    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x,
                        (params["layers"], params["cross_layers"]))
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), jnp.zeros((), jnp.float32)


def _fwd_whisper(params, x, cfg, extras):
    frames = extras["audio_frames"]                    # [B, F, d] stub frames
    F = frames.shape[1]
    enc_pos = jnp.arange(F, dtype=jnp.int32)

    def enc_body(h, lp):
        h, _ = B.attn_block(lp, h, cfg=cfg, positions=enc_pos, causal=False,
                            use_rope=False)
        return h, None

    h, _ = jax.lax.scan(_maybe_remat(enc_body, cfg), frames, params["encoder"])
    memory = rmsnorm(params["enc_norm"], h, cfg.norm_eps)

    S = x.shape[1]
    x = x + params["pos_embed"][:S]
    dec_pos = jnp.arange(S, dtype=jnp.int32)

    def dec_body(x, xs):
        sp, cp = xs
        hh = rmsnorm(sp["ln1"], x, cfg.norm_eps)
        x = x + ATT.attn_forward(sp["attn"], hh, cfg=cfg, positions=dec_pos,
                                 causal=True, use_rope=False)
        x, _ = B.attn_block(cp, x, cfg=cfg, positions=dec_pos, causal=False,
                            kv_source=memory, use_rope=False)
        return x, None

    x, _ = jax.lax.scan(_maybe_remat(dec_body, cfg), x,
                        (params["dec_self"], params["dec_cross"]))
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), jnp.zeros((), jnp.float32)


def _fwd_xlstm(params, x, cfg):
    n_seg, n_m = _xlstm_segments(cfg)

    def m_body(x, lp):
        return B.mlstm_block(lp, x, cfg=cfg), None

    for s in range(n_seg):
        mstack = jax.tree.map(lambda a: a[s], params["mlayers"])
        x, _ = jax.lax.scan(_maybe_remat(m_body, cfg), x, mstack)
        sp = jax.tree.map(lambda a: a[s], params["slayers"])
        x = B.slstm_block(sp, x, cfg=cfg)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), jnp.zeros((), jnp.float32)


def _fwd_zamba(params, x, positions, cfg):
    n_app, seg = _zamba_segments(cfg)

    def m_body(x, lp):
        return B.mamba2_block(lp, x, cfg=cfg), None

    if n_app == 0:
        x, _ = jax.lax.scan(_maybe_remat(m_body, cfg), x, params["layers"])
    else:
        for s in range(n_app):
            stack = jax.tree.map(lambda a: a[s * seg:(s + 1) * seg],
                                 params["layers"])
            x, _ = jax.lax.scan(_maybe_remat(m_body, cfg), x, stack)
            x, _ = B.attn_block(params["shared_attn"], x, cfg=cfg,
                                positions=positions)
        rem = cfg.num_layers - n_app * seg
        if rem:
            stack = jax.tree.map(lambda a: a[n_app * seg:], params["layers"])
            x, _ = jax.lax.scan(_maybe_remat(m_body, cfg), x, stack)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), jnp.zeros((), jnp.float32)


# ----------------------------------------------------------------------- loss

def logits_from_hidden(params: dict, x: jax.Array, cfg) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ w.astype(x.dtype)).astype(jnp.float32)


def chunked_xent(params, x, labels, cfg, chunk: int = 512):
    """Cross-entropy without materializing [B, S, V]: scan over S chunks.
    x [B,S,d]; labels [B,S] int32 (-1 = masked). Returns (sum_loss, count)."""
    Bsz, S, d = x.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    n = S // chunk
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

    xc = x.reshape(Bsz, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(Bsz, n, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        loss, cnt = carry
        xb, lb = inp                                    # [B, c, d], [B, c]
        logits = (xb @ w.astype(xb.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lb, 0)[..., None], axis=-1)[..., 0]
        mask = (lb >= 0).astype(jnp.float32)
        loss = loss + jnp.sum((lse - gold) * mask)
        cnt = cnt + jnp.sum(mask)
        return (loss, cnt), None

    body = jax.checkpoint(body) if cfg.remat else body
    (loss, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc))
    return loss, cnt


def _training_cfg(cfg):
    """Training runs the differentiable XLA realization: the pallas kernels
    define no VJP yet (ROADMAP), so backend="auto" silently pins to xla. An
    EXPLICIT backend="pallas" fails fast HERE: inside the layer scan the
    autodiff tracers are invisible (scan bodies are traced to a jaxpr before
    the JVP rule runs), so the `resolve_backend` guard cannot see the grad
    trace and the failure would otherwise surface as a bare
    NotImplementedError from pallas_call at transpose time."""
    if cfg.moe is None:
        return cfg
    b = getattr(cfg.moe, "backend", "auto")
    if b == "pallas":
        raise NotImplementedError(
            "pallas backend has no backward pass yet; use backend='auto' or "
            "'xla' for training (see ROADMAP: custom VJP over gmm/gmm_swiglu)."
            " For forward-only evaluation on pallas, call model_forward + "
            "chunked_xent directly — loss_fn is the training entry point")
    if b == "auto":
        import dataclasses
        return cfg.with_overrides(
            moe=dataclasses.replace(cfg.moe, backend="xla"))
    return cfg


def loss_fn(params: dict, batch: dict, cfg):
    """batch: tokens [B,S], labels [B,S] (+ stub extras). -> (loss, metrics)."""
    cfg = _training_cfg(cfg)
    extras = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    x, bal = model_forward(params, batch["tokens"], cfg, extras)
    loss_sum, cnt = chunked_xent(params, x, batch["labels"], cfg)
    ce = loss_sum / jnp.maximum(cnt, 1.0)
    coef = cfg.moe.balance_coef if cfg.moe is not None else 0.0
    total = ce + coef * bal / max(1, cfg.num_layers)
    return total, {"ce": ce, "balance": bal}


# --------------------------------------------------------------- decode state

def kv_cache_spec(cfg, batch: int, max_len: int):
    hd = cfg.resolved_head_dim()
    return (batch, max_len, cfg.num_kv_heads, hd)


def paged_supported(cfg) -> bool:
    """Paged KV pools cover the plain attention family — the KV cache is the
    only sequence-shaped decode state there (recurrent families are O(1) per
    slot, enc-dec/vlm carry per-request memories)."""
    return (cfg.block == "attn" and cfg.encoder_layers == 0
            and cfg.cross_attn_every == 0)


def init_decode_state(cfg, batch: int, max_len: int,
                      extras: dict | None = None, *,
                      per_slot_t: bool = False,
                      paged: tuple[int, int] | None = None) -> dict:
    """Zero-initialized decode state. `extras` may carry the cross-attention
    memory (image/audio embeds already encoded) for vlm/enc-dec archs.
    With per_slot_t, `t` is an int32 vector [batch] so every slot advances
    independently (the continuous-batching pool layout).

    `paged=(num_pages, page_size)` swaps the dense per-slot KV rows for a
    shared page pool: `k_pages`/`v_pages` [L, num_pages, page_size, h, hd]
    plus a per-slot `block_table` [batch, max_len // page_size] of physical
    page ids (0 = the reserved null page). HBM then scales with the pool's
    page count, not batch x max_len. GO caches stay slot-resident — they
    are [E, k]-shaped, not sequence-shaped. Attention family only."""
    extras = extras or {}
    dt = jnp.dtype(cfg.dtype)
    st = {"t": jnp.zeros((batch,) if per_slot_t else (), jnp.int32)}
    shp = kv_cache_spec(cfg, batch, max_len)
    if paged is not None:
        if not paged_supported(cfg):
            raise ValueError(
                "paged decode state is attention-family only "
                f"(block={cfg.block!r}, encoder_layers={cfg.encoder_layers}, "
                f"cross_attn_every={cfg.cross_attn_every})")
        num_pages, ps = paged
        if max_len % ps:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"page_size={ps}")
        Q.validate_kv_quant(cfg.kv_quant)
        quant = cfg.kv_quant == "int8"
        L = cfg.num_layers
        hd = cfg.resolved_head_dim()
        page_dt = jnp.int8 if quant else dt
        st["block_table"] = jnp.zeros((batch, max_len // ps), jnp.int32)
        st["k_pages"] = jnp.zeros(
            (L, num_pages, ps, cfg.num_kv_heads, hd), page_dt)
        st["v_pages"] = jnp.zeros(
            (L, num_pages, ps, cfg.num_kv_heads, hd), page_dt)
        if quant:
            # per-page, per-kv-head amax scales; zero = empty page
            st["k_scales"] = jnp.zeros(
                (L, num_pages, cfg.num_kv_heads), jnp.float32)
            st["v_scales"] = jnp.zeros(
                (L, num_pages, cfg.num_kv_heads), jnp.float32)
        if cfg.moe is not None and cfg.moe.routing == "expert_choice" \
                and cfg.moe.go_cache:
            e = cfg.moe
            per = go_cache_init(batch, e.num_experts, e.top_k, cfg.d_model,
                                jnp.int8 if quant else dt)
            st["go"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (L, *a.shape)), per)
            if quant:
                # per-row GO scales (outputs rows are [E, k, d] per slot)
                st["go_scales"] = jnp.zeros(
                    (L, batch, e.num_experts, e.top_k), jnp.float32)
        return st

    if cfg.block == "attn" and cfg.encoder_layers > 0:
        L = cfg.num_layers
        st["k"] = jnp.zeros((L, *shp), dt)
        st["v"] = jnp.zeros((L, *shp), dt)
        st["memory"] = extras.get(
            "memory", jnp.zeros((batch, cfg.num_audio_frames, cfg.d_model), dt))
    elif cfg.block == "attn" and cfg.cross_attn_every > 0:
        n_sup = cfg.num_layers // cfg.cross_attn_every
        n_self = cfg.cross_attn_every - 1
        st["k"] = jnp.zeros((n_sup * n_self, *shp), dt)   # flat self-layer idx
        st["v"] = jnp.zeros((n_sup * n_self, *shp), dt)
        st["memory"] = extras.get(
            "memory", jnp.zeros((batch, cfg.num_image_tokens, cfg.d_model), dt))
    elif cfg.block == "attn":
        L = cfg.num_layers
        st["k"] = jnp.zeros((L, *shp), dt)
        st["v"] = jnp.zeros((L, *shp), dt)
        if cfg.moe is not None and cfg.moe.routing == "expert_choice" \
                and cfg.moe.go_cache:
            e = cfg.moe
            per = go_cache_init(batch, e.num_experts, e.top_k, cfg.d_model, dt)
            st["go"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (L, *a.shape)), per)
    elif cfg.block == "xlstm":
        n_seg, n_m = _xlstm_segments(cfg)
        per_m = mlstm_init_state(cfg, batch)
        st["mlstm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_seg, n_m, *a.shape)), per_m)
        per_s = slstm_init_state(cfg, batch)
        st["slstm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_seg, *a.shape)), per_s)
    elif cfg.block == "mamba2":
        per = mamba2_init_state(cfg, batch)
        st["ssm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)), per)
        n_app, _ = _zamba_segments(cfg)
        if n_app:
            st["k"] = jnp.zeros((n_app, *shp), dt)
            st["v"] = jnp.zeros((n_app, *shp), dt)
    return st


# ------------------------------------------------------------- per-slot state
#
# The continuous-batching engine (repro/serving) owns ONE pooled decode state
# of `num_slots` batch rows and retires/admits requests per row. These two ops
# are the whole interface it needs: reset a row, and splat a single-request
# prefill (batch-1 state) into a row. Batch axes per key:
#   t -> 0 (vector form)   k/v/go/ssm/slstm -> 1 (leading layer axis)
#   mlstm -> 2 (segment, layer, batch)      memory -> 0

def init_decode_slot(state: dict, slot) -> dict:
    """Reset pool slot `slot` (traced int32 ok) to the empty decode state.
    Paged pools only reset the slot's BLOCK TABLE (to the null page) — the
    physical pages go back to the host allocator's free list and are
    rewritten before any future occupant can read them, so clearing their
    contents would be wasted bandwidth. GO rows reset as usual (scores to
    -inf) on this same free path."""
    st = dict(state)
    if st["t"].ndim == 1:
        st["t"] = st["t"].at[slot].set(0)
    else:
        st["t"] = jnp.zeros((), jnp.int32)
    if "block_table" in st:
        st["block_table"] = st["block_table"].at[slot].set(0)
    for key in ("k", "v"):
        if key in st:
            st[key] = st[key].at[:, slot].set(0)
    if "go" in st:
        # vmap over the stacked layer axis -> per-layer [B, ...] caches
        st["go"] = jax.vmap(lambda c: go_cache_init_slot(c, slot))(st["go"])
    if "go_scales" in st:
        st["go_scales"] = st["go_scales"].at[:, slot].set(0)
    if "ssm" in st:
        st["ssm"] = jax.tree.map(lambda a: a.at[:, slot].set(0), st["ssm"])
    if "mlstm" in st:
        st["mlstm"] = jax.tree.map(lambda a: a.at[:, :, slot].set(0), st["mlstm"])
    if "slstm" in st:
        st["slstm"] = jax.tree.map(lambda a: a.at[:, slot].set(0), st["slstm"])
    if "memory" in st:
        st["memory"] = st["memory"].at[slot].set(0)
    return st


def write_decode_slot(state: dict, slot, src: dict, page_ids=None) -> dict:
    """Write a batch-1 decode state `src` (a single-request prefill built with
    the SAME max_len as the pool) into pool slot `slot`.

    Paged pools additionally take `page_ids` [max_len // page_size] int32 —
    the slot's full block-table row. The dense prefill KV splits into
    page-size rows and scatters to those physical pages; entries that are 0
    (null — pages past the request's allocation) dump their rows onto the
    null trash page, so ONE compile serves every allocation size."""
    st = dict(state)
    st["t"] = st["t"].at[slot].set(jnp.asarray(src["t"], jnp.int32).reshape(()))
    if "block_table" in st:
        assert page_ids is not None, "paged pool: pass the slot's page_ids"
        pid = jnp.asarray(page_ids, jnp.int32)
        st["block_table"] = st["block_table"].at[slot].set(pid)
        L, _, ps, h, hd = st["k_pages"].shape
        P = pid.shape[0]
        quant = "k_scales" in st
        for key, srck in (("k_pages", "k"), ("v_pages", "v")):
            if srck not in src:
                # paged-native chunk prefill: the chunk run already scattered
                # its KV into the pool's pages — nothing to splat here
                continue
            assert src[srck].shape[2] == P * ps, \
                f"{srck}: prefill len {src[srck].shape[2]} != pool " \
                f"max_tokens {P * ps} (prefill must use the pool's max_len)"
            pages = src[srck][:, 0].reshape(L, P, ps, h, hd)
            if quant:
                # splat-quantize: each page against its own amax — a pure
                # function of the tokens, independent of pool history
                q, sc = Q.quantize_pages(pages)
                st[key] = st[key].at[:, pid].set(q)
                sk = {"k_pages": "k_scales", "v_pages": "v_scales"}[key]
                st[sk] = st[sk].at[:, pid].set(sc)
            else:
                st[key] = st[key].at[:, pid].set(pages.astype(st[key].dtype))
    for key in ("k", "v"):
        if key in st:
            assert st[key].shape[2:] == src[key].shape[2:], \
                f"{key}: pool {st[key].shape} vs slot {src[key].shape} " \
                "(prefill must use the pool's max_len)"
            st[key] = st[key].at[:, slot].set(src[key][:, 0].astype(st[key].dtype))
    if "go" in st:
        src_go = src["go"]
        if "go_scales" in st:
            # quantize the full-precision prefill rows once, at the splat
            qout, qsc = Q.quantize_rows(src_go.outputs)
            src_go = src_go._replace(outputs=qout)
            st["go_scales"] = st["go_scales"].at[:, slot].set(qsc[:, 0])
        st["go"] = jax.vmap(lambda c, s: go_cache_write_slot(c, slot, s))(
            st["go"], src_go)
    if "ssm" in st:
        st["ssm"] = jax.tree.map(
            lambda a, b: a.at[:, slot].set(b[:, 0].astype(a.dtype)),
            st["ssm"], src["ssm"])
    if "mlstm" in st:
        st["mlstm"] = jax.tree.map(
            lambda a, b: a.at[:, :, slot].set(b[:, :, 0].astype(a.dtype)),
            st["mlstm"], src["mlstm"])
    if "slstm" in st:
        st["slstm"] = jax.tree.map(
            lambda a, b: a.at[:, slot].set(b[:, 0].astype(a.dtype)),
            st["slstm"], src["slstm"])
    if "memory" in st:
        st["memory"] = st["memory"].at[slot].set(
            src["memory"][0].astype(st["memory"].dtype))
    return st


# ----------------------------------------------------------------- serve step

def serve_step(params: dict, state: dict, tokens_t: jax.Array, cfg):
    """One decode step. tokens_t [B] int32 -> (logits [B, V] fp32, state)."""
    with jax.named_scope("embed"):
        x = embed_tokens(params, tokens_t, cfg)[:, None, :]  # [B, 1, d]
    t = state["t"]

    if cfg.block == "attn" and cfg.encoder_layers > 0:
        x, state = _dec_whisper(params, x, state, cfg)
    elif cfg.block == "attn" and cfg.cross_attn_every > 0:
        x, state = _dec_vlm(params, x, state, cfg)
    elif cfg.block == "attn":
        x, state = _dec_attn(params, x, state, cfg)
    elif cfg.block == "xlstm":
        x, state = _dec_xlstm(params, x, state, cfg)
    elif cfg.block == "mamba2":
        x, state = _dec_zamba(params, x, state, cfg)
    else:
        raise ValueError(cfg.block)

    with jax.named_scope("head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = logits_from_hidden(params, x[:, 0, :], cfg)
    state["t"] = t + 1
    return logits, state


def _dec_attn(params, x, state, cfg):
    t = state["t"]
    windows = jnp.asarray(layer_windows(cfg))
    goe = expert_groups(cfg)
    has_go = "go" in state
    paged = "block_table" in state
    qkv = paged and "k_scales" in state
    qgo = has_go and "go_scales" in state
    kk, vk = ("k_pages", "v_pages") if paged else ("k", "v")
    bt = state["block_table"] if paged else None
    layers, banks = _split_experts(params["layers"])

    # The full KV (and GO) caches ride in the scan CARRY and are updated
    # layer-by-layer with dynamic_update_index — XLA keeps them in place
    # (donated buffers), instead of double-buffering a stacked ys output.
    # Quantized pools bundle each cache with its scales — (pages, scales)
    # tuples ride the carry and tree.map generalizes the index/update.
    def body(carry, xs):
        x, K, V, go, l = carry
        lp, w = xs
        lp = _with_experts(lp, banks, l)
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)
        put = lambda full, new: jax.lax.dynamic_update_index_in_dim(
            full, new.astype(full.dtype), l, 0)
        with jax.named_scope("kv_read"):
            ck = jax.tree.map(pick, K)
            cv = jax.tree.map(pick, V)
            go_l = jax.tree.map(pick, go) if has_go else None
            if qgo:
                # layer boundary: int8 GO rows -> f32 (f32, NOT the cfg
                # compute dtype: in f32 an unchanged row requantizes to its
                # exact int8 bits, so idle rows are bit-stable across ticks)
                go_l, gsc = go_l
                go_l = go_l._replace(
                    outputs=Q.dequantize_rows(go_l.outputs, gsc))
        x, ck, cv, go_l, _ = B.attn_block_decode(
            lp, x, ck, cv, t, cfg=cfg, window=w, group_of_expert=goe,
            go_cache=go_l, block_table=bt)
        with jax.named_scope("kv_write"):
            if qgo:
                qout, gsc = Q.quantize_rows(go_l.outputs)
                go_l = (go_l._replace(outputs=qout), gsc)
            K = jax.tree.map(put, K, ck)
            V = jax.tree.map(put, V, cv)
            if has_go:
                go = jax.tree.map(put, go, go_l)
        return (x, K, V, go, l + 1), None

    K0 = (state[kk], state["k_scales"]) if qkv else state[kk]
    V0 = (state[vk], state["v_scales"]) if qkv else state[vk]
    go0 = state.get("go")
    if qgo:
        go0 = (go0, state["go_scales"])
    carry0 = (x, K0, V0, go0, jnp.zeros((), jnp.int32))
    (x, K, V, go, _), _ = jax.lax.scan(body, carry0, (layers, windows))
    if qkv:
        (state[kk], state["k_scales"]) = K
        (state[vk], state["v_scales"]) = V
    else:
        state[kk], state[vk] = K, V
    if has_go:
        if qgo:
            state["go"], state["go_scales"] = go
        else:
            state["go"] = go
    return x, state


def _dec_vlm(params, x, state, cfg):
    t = state["t"]
    memory = state["memory"]
    n_self = cfg.cross_attn_every - 1

    def body(carry, xs):
        x, K, V, sup = carry                 # K/V [n_sup*n_self, B, S, h, hd]
        self_stack, cross_p = xs
        for i in range(n_self):
            lp = jax.tree.map(lambda a: a[i], self_stack)
            l = sup * n_self + i
            ck = jax.lax.dynamic_index_in_dim(K, l, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(V, l, 0, keepdims=False)
            x, ck, cv, _, _ = B.attn_block_decode(lp, x, ck, cv, t, cfg=cfg)
            K = jax.lax.dynamic_update_index_in_dim(
                K, ck.astype(K.dtype), l, 0)
            V = jax.lax.dynamic_update_index_in_dim(
                V, cv.astype(V.dtype), l, 0)
        x = B.cross_block_decode(cross_p, x, memory, cfg=cfg)
        return (x, K, V, sup + 1), None

    carry0 = (x, state["k"], state["v"], jnp.zeros((), jnp.int32))
    (x, K, V, _), _ = jax.lax.scan(
        body, carry0, (params["layers"], params["cross_layers"]))
    state["k"], state["v"] = K, V
    return x, state


def _dec_whisper(params, x, state, cfg):
    t = state["t"]
    memory = state["memory"]
    t_vec = jnp.broadcast_to(
        jnp.asarray(t, jnp.int32).reshape(-1), (x.shape[0],))
    x = x + params["pos_embed"][t_vec][:, None, :]

    def body(carry, xs):
        x, K, V, l = carry
        sp, cp = xs
        ck = jax.lax.dynamic_index_in_dim(K, l, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(V, l, 0, keepdims=False)
        h = rmsnorm(sp["ln1"], x, cfg.norm_eps)
        a, ck, cv = ATT.attn_decode(sp["attn"], h, ck, cv, t, cfg=cfg,
                                    use_rope=False)
        x = x + a
        x = B.cross_block_decode(cp, x, memory, cfg=cfg)
        K = jax.lax.dynamic_update_index_in_dim(K, ck.astype(K.dtype), l, 0)
        V = jax.lax.dynamic_update_index_in_dim(V, cv.astype(V.dtype), l, 0)
        return (x, K, V, l + 1), None

    carry0 = (x, state["k"], state["v"], jnp.zeros((), jnp.int32))
    (x, K, V, _), _ = jax.lax.scan(
        body, carry0, (params["dec_self"], params["dec_cross"]))
    state["k"], state["v"] = K, V
    return x, state


def _dec_xlstm(params, x, state, cfg):
    n_seg, n_m = _xlstm_segments(cfg)

    def m_body(x, xs):
        lp, st = xs
        x, st2 = B.mlstm_block(lp, x, cfg=cfg, decode_state=st)
        return x, st2

    new_m, new_s = [], []
    for s in range(n_seg):
        mstack = jax.tree.map(lambda a: a[s], params["mlayers"])
        mstate = jax.tree.map(lambda a: a[s], state["mlstm"])
        x, mst = jax.lax.scan(m_body, x, (mstack, mstate))
        new_m.append(mst)
        sp = jax.tree.map(lambda a: a[s], params["slayers"])
        sst = jax.tree.map(lambda a: a[s], state["slstm"])
        x, sst2 = B.slstm_block(sp, x, cfg=cfg, decode_state=sst)
        new_s.append(sst2)
    state["mlstm"] = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
    state["slstm"] = jax.tree.map(lambda *a: jnp.stack(a), *new_s)
    return x, state


def _dec_zamba(params, x, state, cfg):
    t = state["t"]
    n_app, seg = _zamba_segments(cfg)

    def m_body(x, xs):
        lp, st = xs
        x, st2 = B.mamba2_block_decode(lp, x, st, cfg=cfg)
        return x, st2

    if n_app == 0:
        x, ssm = jax.lax.scan(m_body, x, (params["layers"], state["ssm"]))
        state["ssm"] = ssm
        return x, state

    new_ssm, new_k, new_v = [], [], []
    for s in range(n_app):
        stack = jax.tree.map(lambda a: a[s * seg:(s + 1) * seg], params["layers"])
        sst = jax.tree.map(lambda a: a[s * seg:(s + 1) * seg], state["ssm"])
        x, ssm2 = jax.lax.scan(m_body, x, (stack, sst))
        new_ssm.append(ssm2)
        x, ck, cv, _, _ = B.attn_block_decode(
            params["shared_attn"], x, state["k"][s], state["v"][s], t, cfg=cfg)
        new_k.append(ck)
        new_v.append(cv)
    rem = cfg.num_layers - n_app * seg
    if rem:
        stack = jax.tree.map(lambda a: a[n_app * seg:], params["layers"])
        sst = jax.tree.map(lambda a: a[n_app * seg:], state["ssm"])
        x, ssm2 = jax.lax.scan(m_body, x, (stack, sst))
        new_ssm.append(ssm2)
    state["ssm"] = jax.tree.map(lambda *a: jnp.concatenate(a), *new_ssm)
    state["k"] = jnp.stack(new_k)
    state["v"] = jnp.stack(new_v)
    return x, state


# -------------------------------------------------------------------- prefill

def prefill(params: dict, tokens: jax.Array, cfg, extras: dict | None = None,
            max_len: int = 0, valid_len=None):
    """Run the full-sequence forward while FILLING the decode state (KV caches,
    GO caches, SSM states). Returns (state, last_token_logits [B, V]).

    `valid_len` (traced int32 scalar) supports BUCKETED prefill: `tokens` is
    right-padded to a bucket length, but only the first valid_len positions
    are real. One compile then serves every prompt length in the bucket.
    Causal attention never lets a real position see a pad; expert-choice
    routing masks pads out of the top-C selection (so the GO cache holds
    only real tokens); the returned logits come from position valid_len - 1
    and the decode position starts there — pad KV rows are overwritten by
    decode steps before they can ever be attended.

    Implemented for the attention families (the serving examples); recurrent
    families can prefill by stepping serve_step (their state is O(1))."""
    extras = extras or {}
    Bsz, S = tokens.shape
    max_len = max_len or (2 * S)
    state = init_decode_state(cfg, Bsz, max_len, extras)
    if cfg.block != "attn" or cfg.encoder_layers > 0:
        assert valid_len is None, \
            "bucketed prefill is attention-family only (recurrent/enc-dec " \
            "archs prefill step-by-step — there is no per-length compile to " \
            "amortize)"
        # step-by-step prefill (exactly equivalent for recurrent/enc-dec archs)
        logits = None
        for i in range(S):
            logits, state = serve_step(params, state, tokens[:, i], cfg)
        return state, logits

    vl = None if valid_len is None else jnp.asarray(valid_len, jnp.int32)
    positions = jnp.arange(S, dtype=jnp.int32)
    windows = jnp.asarray(layer_windows(cfg))
    goe = expert_groups(cfg)
    gm = expert_group_members(cfg)
    with jax.named_scope("embed"):
        x = embed_tokens(params, tokens, cfg)
    has_go = "go" in state
    layers, banks = _split_experts(params["layers"])

    def body(carry, xs):
        x, l = carry
        lp, w = xs
        out = B.attn_block(_with_experts(lp, banks, l), x, cfg=cfg,
                           positions=positions, window=w,
                           group_of_expert=goe, group_members=gm,
                           return_kv=True, valid_len=vl)
        x, aux, k, v = out
        if has_go:
            # build this layer's GO cache from the expert-choice aux
            e = cfg.moe
            go = go_cache_prefill(
                None, None, aux["weighted_outputs"], aux["chosen_tokens"],
                aux["chosen_scores"], e.top_k)
            return (x, l + 1), (k, v, go)
        return (x, l + 1), (k, v)

    if cfg.cross_attn_every > 0:
        assert valid_len is None, "bucketed prefill: cross-attn archs TODO"
        state, x = _prefill_vlm(params, x, positions, state, cfg)
    else:
        (x, _), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.int32)),
                                  (layers, windows))
        k, v = ys[0], ys[1]
        L = cfg.num_layers
        state["k"] = jax.lax.dynamic_update_slice(
            state["k"], k.astype(state["k"].dtype), (0, 0, 0, 0, 0))
        state["v"] = jax.lax.dynamic_update_slice(
            state["v"], v.astype(state["v"].dtype), (0, 0, 0, 0, 0))
        if has_go:
            state["go"] = ys[2]

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if vl is None:
        logits = logits_from_hidden(params, x[:, -1, :], cfg)
        state["t"] = jnp.asarray(S, jnp.int32)
    else:
        logits = logits_from_hidden(params, jnp.take(x, vl - 1, axis=1), cfg)
        state["t"] = vl
    return state, logits


def prefill_chunk(params: dict, state: dict, tokens: jax.Array, cfg,
                  start, valid_len=None):
    """Append ONE prompt chunk (tokens [B, Cs] at absolute positions
    start..start+Cs-1) to a dense decode state mid-prefill. Chained over
    page-granular chunks this replaces the single long `prefill` pass, so a
    long prompt never stalls the serving engine for more than one chunk of
    work per tick.

    `start` and `valid_len` are TRACED int32 scalars: one compile per chunk
    length serves every chunk of every prompt. The last chunk is
    right-padded to Cs and rides in with `valid_len` = its real token count
    — causal attention plus the kv_len mask keep real positions off the
    pads, and expert-choice routing masks pads out of the chunk's top-C
    (blocks.py::attn_block_chunk), so the merged GO cache holds only real
    tokens. Expert-choice capacity derives from the CHUNK length, so MoE
    streams are deterministic per chunking but may differ from one-shot
    prefill (the same caveat as prompt bucketing). Dense archs reproduce
    the one-shot streams.

    Returns (state, logits) where logits come from chunk position
    valid_len - 1 — only meaningful on the final chunk. state["t"] lands on
    start + valid_len. Attention family only.

    A PAGED state (carries "block_table"/"k_pages"/"v_pages" instead of
    dense "k"/"v" rows — the engine threads the pool's page store through a
    batch-1 view) prefills directly into the pool's pages: each chunk
    scatters its KV to the pages backing its positions and attends over the
    prefix's pages (attention.py::attn_chunk paged path), so chunked
    prefill never materializes a dense [1, max_tokens] KV copy."""
    assert paged_supported(cfg), \
        "chunked prefill is attention-family only (recurrent archs prefill " \
        "step-by-step; enc-dec/vlm archs are one-shot)"
    Bsz, Cs = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    vl = jnp.asarray(Cs if valid_len is None else valid_len, jnp.int32)
    windows = jnp.asarray(layer_windows(cfg))
    goe = expert_groups(cfg)
    gm = expert_group_members(cfg)
    with jax.named_scope("embed"):
        x = embed_tokens(params, tokens, cfg)
    has_go = "go" in state
    paged = "block_table" in state
    qkv = paged and "k_scales" in state
    kk, vk = ("k_pages", "v_pages") if paged else ("k", "v")
    bt = state["block_table"] if paged else None
    layers, banks = _split_experts(params["layers"])

    # Quantized pools bundle (pages, scales) in the carry — same tree.map
    # generalization as _dec_attn. The chunk job's GO cache stays full
    # precision (go_cache_merge reads it); it quantizes once at the
    # write_decode_slot splat on completion.
    def body(carry, xs):
        x, K, V, go, l = carry
        lp, w = xs
        lp = _with_experts(lp, banks, l)
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)
        put = lambda full, new: jax.lax.dynamic_update_index_in_dim(
            full, new.astype(full.dtype), l, 0)
        with jax.named_scope("kv_read"):
            ck = jax.tree.map(pick, K)
            cv = jax.tree.map(pick, V)
            go_l = jax.tree.map(pick, go) if has_go else None
        x, ck, cv, go_l, _ = B.attn_block_chunk(
            lp, x, ck, cv, start, cfg=cfg, window=w, valid_len=vl,
            group_of_expert=goe, group_members=gm, go_cache=go_l,
            block_table=bt)
        with jax.named_scope("kv_write"):
            K = jax.tree.map(put, K, ck)
            V = jax.tree.map(put, V, cv)
            if has_go:
                go = jax.tree.map(put, go, go_l)
        return (x, K, V, go, l + 1), None

    K0 = (state[kk], state["k_scales"]) if qkv else state[kk]
    V0 = (state[vk], state["v_scales"]) if qkv else state[vk]
    carry0 = (x, K0, V0, state.get("go"), jnp.zeros((), jnp.int32))
    (x, K, V, go, _), _ = jax.lax.scan(body, carry0, (layers, windows))
    state = dict(state)
    if qkv:
        (state[kk], state["k_scales"]) = K
        (state[vk], state["v_scales"]) = V
    else:
        state[kk], state[vk] = K, V
    if has_go:
        state["go"] = go
    with jax.named_scope("head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = logits_from_hidden(params, jnp.take(x, vl - 1, axis=1), cfg)
    state["t"] = start + vl
    return state, logits


def _prefill_vlm(params, x, positions, state, cfg):
    memory = state["memory"]
    n_self = cfg.cross_attn_every - 1

    def body(x, xs):
        self_stack, cross_p = xs
        ks, vs = [], []
        for i in range(n_self):
            lp = jax.tree.map(lambda a: a[i], self_stack)
            x, _, k, v = B.attn_block(lp, x, cfg=cfg, positions=positions,
                                      return_kv=True)
            ks.append(k)
            vs.append(v)
        x, _ = B.attn_block(cross_p, x, cfg=cfg, positions=positions,
                            causal=False, kv_source=memory, use_rope=False)
        return x, (jnp.stack(ks), jnp.stack(vs))

    x, (k, v) = jax.lax.scan(body, x, (params["layers"], params["cross_layers"]))
    # [n_sup, n_self, B, S, h, hd] -> flat layer index, matching decode state
    k = k.reshape(-1, *k.shape[2:])
    v = v.reshape(-1, *v.shape[2:])
    state["k"] = jax.lax.dynamic_update_slice(
        state["k"], k.astype(state["k"].dtype), (0,) * 5)
    state["v"] = jax.lax.dynamic_update_slice(
        state["v"], v.astype(state["v"].dtype), (0,) * 5)
    return state, x

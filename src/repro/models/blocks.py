"""Residual blocks assembled from attention / MoE / SSM / xLSTM primitives.

Every block is an (init, apply) pair over plain dict pytrees, with a matching
single-token decode variant that threads its cache/state explicitly. Blocks
are *stackable*: inits are vmap-safe so whole layer stacks can be built with
`stack_init` and consumed by `jax.lax.scan`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import moe as MOE
from repro.core.go_cache import go_cache_step
from repro.kernels import ops as OPS
from repro.models import attention as ATT
from repro.models.layers import (gelu_mlp, gelu_mlp_init, mlp, mlp_init,
                                 rmsnorm, rmsnorm_init)
from repro.models.ssm import mamba2_decode, mamba2_forward, mamba2_init
from repro.models.xlstm import mlstm_block, mlstm_block_init, slstm_block, slstm_block_init


# ------------------------------------------------------- attention (+FFN) block

def attn_block_init(key, cfg, *, use_moe: bool = False, cross: bool = False,
                    gelu: bool = False) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": rmsnorm_init(cfg.d_model),
        "attn": (ATT.mla_init(k1, cfg) if cfg.mla
                 else ATT.attn_init(k1, cfg, cross=cross)),
        "ln2": rmsnorm_init(cfg.d_model),
    }
    if use_moe:
        p["moe"] = MOE.moe_init(k2, cfg.d_model, cfg.moe, jnp.dtype(cfg.dtype))
    elif cfg.d_ff > 0:
        p["mlp"] = (gelu_mlp_init if gelu else mlp_init)(
            k2, cfg.d_model, cfg.d_ff, jnp.dtype(cfg.dtype))
    return p


def _ffn_apply(params: dict, x: jax.Array, cfg, group_of_expert,
               group_members=None, valid_len=None) -> tuple:
    """Post-attention FFN sublayer (dense MLP or MoE). x [B,S,d].

    `valid_len` (traced int32 scalar, bucketed prefill) masks right-padded
    positions out of the EXPERT-CHOICE routing — a pad can never win an
    expert slot, so the GO cache built from this pass holds only real
    tokens. Token-choice paths ignore it: routing is per token and pads'
    outputs land only on pad rows (their pairs also rank AFTER every real
    pair in the capacity order, so real drops are unchanged)."""
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    aux = None
    if "moe" in params:
        with jax.named_scope("moe"):
            B, S, d = h.shape
            backend = MOE.resolve_backend(cfg.moe, (h, params))
            # XLA backend routes per sequence (vmap over batch), two
            # reasons:
            #  * the sort-based dispatch never crosses the batch dim, so
            #    GSPMD keeps dispatch buffers batch-sharded (a global
            #    argsort over B*S would gather the whole batch onto every
            #    device);
            #  * expert-choice selection per sequence is what the GO cache
            #    serves, so train == serve semantics.
            # The pallas backend keeps ROUTING per sequence (same
            # semantics) but flattens the FFN pairs of the whole batch into
            # one tile plan, so the grouped GEMM pays its per-expert tile
            # padding once, not B times.
            if cfg.moe.routing == "expert_choice":
                if backend == "pallas":
                    y, aux = MOE.expert_choice_forward_batched(
                        params["moe"], h, cfg.moe, valid_len=valid_len)
                else:
                    y, aux = jax.vmap(
                        lambda xb: MOE.expert_choice_forward(
                            params["moe"], xb, cfg.moe,
                            valid_len=valid_len))(h)
            elif MOE.ep_available(cfg.moe):
                y, aux = MOE.moe_forward_ep(params["moe"], h, cfg.moe)
            elif backend == "pallas":
                y, aux = MOE.moe_forward(params["moe"],
                                         h.reshape(B * S, d), cfg.moe,
                                         group_of_expert, group_members)
                y = y.reshape(B, S, d)
            else:
                y, aux = jax.vmap(
                    lambda xb: MOE.moe_forward(params["moe"], xb, cfg.moe,
                                               group_of_expert,
                                               group_members))(h)
                aux = {"counts": aux["counts"].sum(0),
                       "balance_loss": aux["balance_loss"].mean(),
                       "dropped": aux["dropped"].sum()}
    elif "mlp" in params:
        w = params["mlp"]
        y = gelu_mlp(w, h) if "wg" not in w else mlp(w, h)
    else:
        y = jnp.zeros_like(h)
    return x + y, aux


def attn_block(params: dict, x: jax.Array, *, cfg, positions, window=0,
               causal: bool = True, group_of_expert=None, group_members=None,
               kv_source=None, use_rope: bool = True,
               return_kv: bool = False, valid_len=None) -> tuple:
    """Full-sequence attention block. Returns (x, aux) with MoE aux or None;
    with return_kv also the post-RoPE (k, v) for KV-cache prefill."""
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    a = ATT.attn_forward(params["attn"], h, cfg=cfg, positions=positions,
                         window=window, causal=causal, kv_source=kv_source,
                         use_rope=use_rope, return_kv=return_kv)
    if return_kv:
        a, k, v = a
    x = x + a
    x, aux = _ffn_apply(params, x, cfg, group_of_expert, group_members,
                        valid_len)
    if return_kv:
        return x, aux, k, v
    return x, aux


def attn_block_decode(params: dict, x_t: jax.Array, cache_k, cache_v, t, *,
                      cfg, window=0, group_of_expert=None,
                      go_cache=None, block_table=None) -> tuple:
    """One-token decode. x_t [B,1,d]. Returns (x, ck, cv, go_cache, aux).
    With `block_table`, cache_k/cache_v are the shared paged KV pool
    (attention.py::attn_decode paged path); the GO cache stays slot-resident
    either way — it is [E, k]-shaped, not sequence-shaped."""
    with jax.named_scope("attn"):
        h = rmsnorm(params["ln1"], x_t, cfg.norm_eps)
        a, ck, cv = ATT.attn_decode(params["attn"], h, cache_k, cache_v, t,
                                    cfg=cfg, window=window,
                                    block_table=block_table)
        x = x_t + a
    h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
    aux = None
    if "moe" in params:
        with jax.named_scope("moe"):
            B = h2.shape[0]
            h2f = h2[:, 0]                                   # [B, d]
            if go_cache is not None:
                # C4: expert-choice decode through the GO cache. On the
                # pallas backend only the SELECTED experts' tiles stream
                # through the grouped GEMM (~B*k rows); the xla fallback
                # computes all E expert FFNs per token and masks.
                moe_p = params["moe"]
                e = cfg.moe
                if MOE.resolve_backend(e, (h2f, moe_p)) == "pallas":
                    res = go_cache_step(
                        go_cache, h2f, t, moe_p["gate"],
                        contrib_fn=lambda xt, sel, g: OPS.go_selected_ffn(
                            xt, sel, g, moe_p["experts"], e.num_experts,
                            bn=MOE._block_rows(e), topk_hint=e.top_k)[0])
                else:
                    res = go_cache_step(
                        go_cache, h2f, t, moe_p["gate"],
                        lambda xt: MOE.expert_ffn_all(moe_p, xt))
                y = res.y + MOE._shared_out(moe_p, h2f)
                go_cache = res.cache
                aux = {"selected": res.selected}
            else:
                y = MOE.token_choice_decode(params["moe"], h2f, cfg.moe)
            x = x + y[:, None, :]
    elif "mlp" in params:
        w = params["mlp"]
        y = gelu_mlp(w, h2) if "wg" not in w else mlp(w, h2)
        x = x + y
    return x, ck, cv, go_cache, aux


def attn_block_chunk(params: dict, x: jax.Array, cache_k, cache_v, start, *,
                     cfg, window=0, valid_len=None, group_of_expert=None,
                     group_members=None, go_cache=None,
                     block_table=None) -> tuple:
    """Chunked-prefill block: append one prompt chunk (x [B,Cs,d] at
    absolute positions start..start+Cs-1) to the KV cache — dense, or with
    `block_table` the shared paged pool — then run the FFN sublayer over
    the chunk. For expert-choice MoE the chunk's routing (capacity from the
    CHUNK length) builds a per-chunk GO cache that merges into the
    accumulated one — `valid_len` (chunk-relative) masks the last chunk's
    right-padding out of the routing, so pads never enter the cache.
    Returns (x, ck, cv, go_cache, aux)."""
    start = jnp.asarray(start, jnp.int32)
    vl = jnp.asarray(x.shape[1] if valid_len is None else valid_len, jnp.int32)
    with jax.named_scope("attn"):
        h = rmsnorm(params["ln1"], x, cfg.norm_eps)
        a, ck, cv = ATT.attn_chunk(params["attn"], h, cache_k, cache_v,
                                   start, cfg=cfg, window=window,
                                   kv_len=start + vl, block_table=block_table)
        x = x + a
    x, aux = _ffn_apply(params, x, cfg, group_of_expert, group_members, vl)
    if go_cache is not None:
        from repro.core.go_cache import go_cache_merge, go_cache_prefill
        chunk_go = go_cache_prefill(
            None, None, aux["weighted_outputs"],
            aux["chosen_tokens"] + start, aux["chosen_scores"],
            cfg.moe.top_k)
        go_cache = go_cache_merge(go_cache, chunk_go)
    return x, ck, cv, go_cache, aux


# ------------------------------------------- latent-attention (+FFN) block

def mla_block(params: dict, x: jax.Array, *, cfg, positions,
              group_of_expert=None, group_members=None,
              valid_len=None) -> tuple:
    """Full-sequence latent-attention block: (x, aux, latent rows
    [B, S, rank + rope] for the cache)."""
    with jax.named_scope("attn"):
        h = rmsnorm(params["ln1"], x, cfg.norm_eps)
        a, rows = ATT.mla_forward(params["attn"], h, cfg=cfg,
                                  positions=positions)
        x = x + a
    x, aux = _ffn_apply(params, x, cfg, group_of_expert, group_members,
                        valid_len)
    return x, aux, rows


def mla_block_chunk(params: dict, x: jax.Array, cache, l, start, *, cfg,
                    valid_len, group_of_expert=None, group_members=None,
                    block_table=None) -> tuple:
    """Chunked-prefill latent-attention block over layer `l` of the stacked
    latent cache (attention.py::mla_chunk). Returns (x, cache)."""
    start = jnp.asarray(start, jnp.int32)
    with jax.named_scope("attn"):
        h = rmsnorm(params["ln1"], x, cfg.norm_eps)
        a, cache = ATT.mla_chunk(params["attn"], h, cache, l, start, cfg=cfg,
                                 kv_len=start + valid_len,
                                 block_table=block_table)
        x = x + a
    x, _ = _ffn_apply(params, x, cfg, group_of_expert, group_members,
                      valid_len)
    return x, cache


def mla_block_decode(params: dict, x_t: jax.Array, cache, l, t, *, cfg,
                     block_table=None) -> tuple:
    """One-token latent-attention block over layer `l` of the stacked latent
    cache (attention.py::mla_decode), then its dense or token-choice MoE
    FFN. Returns (x, cache)."""
    with jax.named_scope("attn"):
        h = rmsnorm(params["ln1"], x_t, cfg.norm_eps)
        a, cache = ATT.mla_decode(params["attn"], h, cache, l, t, cfg=cfg,
                                  block_table=block_table)
        x = x_t + a
    h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
    if "moe" in params:
        with jax.named_scope("moe"):
            y = MOE.token_choice_decode(params["moe"], h2[:, 0], cfg.moe)
            x = x + y[:, None, :]
    else:
        x = x + mlp(params["mlp"], h2)
    return x, cache


def cross_block_decode(params: dict, x_t: jax.Array, memory, *, cfg) -> jax.Array:
    """Cross-attention block decode (static memory, no cache growth)."""
    h = rmsnorm(params["ln1"], x_t, cfg.norm_eps)
    a = ATT.cross_attn_decode(params["attn"], h, memory, cfg=cfg)
    x = x_t + a
    x, _ = _ffn_apply(params, x, cfg, None)
    return x


# ------------------------------------------------------------- mamba2 block

def mamba2_block_init(key, cfg) -> dict:
    return {"ln": rmsnorm_init(cfg.d_model), "mix": mamba2_init(key, cfg)}


def mamba2_block(params: dict, x: jax.Array, *, cfg) -> jax.Array:
    h = rmsnorm(params["ln"], x, cfg.norm_eps)
    return x + mamba2_forward(params["mix"], h, cfg=cfg)


def mamba2_block_decode(params: dict, x_t: jax.Array, state, *, cfg) -> tuple:
    h = rmsnorm(params["ln"], x_t, cfg.norm_eps)
    y, new_state = mamba2_decode(params["mix"], h, state, cfg=cfg)
    return x_t + y, new_state


__all__ = [
    "attn_block_init", "attn_block", "attn_block_decode", "attn_block_chunk",
    "cross_block_decode", "mla_block", "mla_block_chunk", "mla_block_decode",
    "mamba2_block_init", "mamba2_block", "mamba2_block_decode",
    "mlstm_block_init", "mlstm_block", "slstm_block_init", "slstm_block",
]

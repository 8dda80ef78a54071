"""MoE routing: token-choice (eq. 1-3) and expert-choice, plus the paper's
incremental TopKUpdate (eq. 4-5) that powers the GO cache.

All functions are jit-safe (static shapes, no data-dependent control flow).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class TokenChoiceRouting(NamedTuple):
    expert_idx: jax.Array     # [T, k] int32 chosen experts per token
    weights: jax.Array        # [T, k] fp32 combine weights (softmax over top-k)
    scores: jax.Array         # [T, E] fp32 raw gate scores (pre-softmax)


class ExpertChoiceRouting(NamedTuple):
    token_idx: jax.Array      # [E, C] int32 tokens chosen by each expert
    weights: jax.Array        # [E, C] fp32 combine weights G[t,e]
    scores: jax.Array         # [T, E] fp32 gate affinity matrix (softmax over E)


def gate_scores(x: jax.Array, w_gate: jax.Array) -> jax.Array:
    """x [T, d] -> raw scores [T, E] in fp32."""
    return (x.astype(jnp.float32) @ w_gate.astype(jnp.float32))


def token_choice(x: jax.Array, w_gate: jax.Array, k: int) -> TokenChoiceRouting:
    """Eq. (1)-(2): softmax(KeepTopK(x W_G, k)) — softmax over the k kept."""
    s = gate_scores(x, w_gate)                          # [T, E]
    top_s, top_i = jax.lax.top_k(s, k)                  # [T, k]
    w = jax.nn.softmax(top_s, axis=-1)
    return TokenChoiceRouting(top_i.astype(jnp.int32), w, s)


def sigmoid_choice(x: jax.Array, w_gate: jax.Array, bias: jax.Array,
                   k: int) -> TokenChoiceRouting:
    """DeepSeek-V3 `noaux_tc` with one expert group: the top-k of
    sigmoid(x W_G) + bias, weighted by the unbiased sigmoid scores — the
    bias steers which experts are chosen, never how much each adds — and
    renormalised to sum to 1. `scores` holds the raw logits, as in
    `token_choice`. The logits are computed in float32 at full precision,
    as the source computes its gate: the sigmoid narrows their spread, and
    a one-pass bfloat16 product (the TPU's default for float32) would flip
    the biased top-k choice often."""
    s = jnp.matmul(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)      # [T, E]
    p = jax.nn.sigmoid(s)
    _, top_i = jax.lax.top_k(p + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(p, top_i, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return TokenChoiceRouting(top_i.astype(jnp.int32), w, s)


def route(x: jax.Array, router: dict, e) -> TokenChoiceRouting:
    """Token-choice routing as the MoE config `e` scores it. `router` holds
    the layer's `gate` [d, E] and, for sigmoid scoring, its `bias` [E]. The
    weights carry `e.routed_scale`; the softmax router with scale 1 is
    `token_choice` as it is."""
    if e.scoring == "softmax":
        r = token_choice(x, router["gate"], e.top_k)
    elif e.scoring == "sigmoid":
        r = sigmoid_choice(x, router["gate"], router["bias"], e.top_k)
    else:
        raise ValueError(f"unknown MoE scoring {e.scoring!r}")
    if e.routed_scale != 1.0:
        r = r._replace(weights=r.weights * e.routed_scale)
    return r


def expert_choice(x: jax.Array, w_gate: jax.Array, capacity: int,
                  valid_len=None) -> ExpertChoiceRouting:
    """Zhou et al. expert-choice: G = softmax over experts; each expert takes
    its top-`capacity` tokens by affinity.

    `valid_len` (traced int32 scalar) masks the affinities of positions
    >= valid_len to zero BEFORE the top-C selection — the bucketed-prefill
    hook: right-padded prompt slots can never win an expert slot (softmax
    affinities of real tokens are > 0, so any real token outranks a pad),
    and a pad chosen only because fewer than C real tokens exist carries a
    zero combine weight."""
    s = gate_scores(x, w_gate)
    g = jax.nn.softmax(s, axis=-1)                      # [T, E] over experts
    if valid_len is not None:
        T = x.shape[0]
        g = g * (jnp.arange(T) < valid_len)[:, None]
    top_g, top_t = jax.lax.top_k(g.T, capacity)         # [E, C]
    return ExpertChoiceRouting(top_t.astype(jnp.int32), top_g, g)


class TopKUpdateResult(NamedTuple):
    new_scores: jax.Array     # [E, k] updated cached top-k scores
    new_token_ids: jax.Array  # [E, k] updated token ids per slot
    selected: jax.Array       # [E] bool: did expert select the incoming token
    slot: jax.Array           # [E] int32 slot replaced (valid where selected)


def topk_update(
    s_prev: jax.Array,        # [E, k] cached scores (fp32)
    tok_prev: jax.Array,      # [E, k] cached token ids
    s_new: jax.Array,         # [E] incoming token's affinity per expert
    new_token_id,             # scalar int32
) -> TopKUpdateResult:
    """Paper eq. (5): per expert, if the new score beats the current min of the
    cached top-k, it replaces that min slot; otherwise the cache is unchanged.
    O(E k) per decode step — no recompute over history."""
    slot = jnp.argmin(s_prev, axis=-1)                  # [E]
    cur_min = jnp.take_along_axis(s_prev, slot[:, None], axis=-1)[:, 0]
    selected = s_new >= cur_min
    onehot = jax.nn.one_hot(slot, s_prev.shape[1], dtype=bool)
    upd = selected[:, None] & onehot
    new_scores = jnp.where(upd, s_new[:, None], s_prev)
    new_tok = jnp.where(upd, jnp.asarray(new_token_id, tok_prev.dtype), tok_prev)
    return TopKUpdateResult(new_scores, new_tok, selected, slot.astype(jnp.int32))


def load_balance_loss(scores: jax.Array, expert_idx: jax.Array, num_experts: int):
    """Shazeer-style auxiliary loss (importance * load) for token-choice
    training; returns scalar fp32."""
    g = jax.nn.softmax(scores, axis=-1)                 # [T, E]
    importance = g.mean(axis=0)                         # fraction of prob mass
    onehot = jax.nn.one_hot(expert_idx, num_experts).sum(axis=1)  # [T, E]
    load = onehot.mean(axis=0) / max(1, expert_idx.shape[-1])
    return num_experts * jnp.sum(importance * load) * expert_idx.shape[-1]

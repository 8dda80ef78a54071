"""MoE layer: the paper's techniques as first-class JAX features.

Execution paths (all numerically validated against `dense_forward`):

  dense_forward      reference oracle: every expert over every token, masked.
  dispatch_forward   production path (train/prefill): sort-based capacity
                     dispatch (megablocks-style), batched expert GEMM, combine.
                     Expert dim is EP-sharded; the C2 load-aware permutation is
                     applied to the expert axis at deployment so each EP shard
                     carries balanced aggregate load.
  group_forward      C1 group-multiplexed path: experts share a group lane
                     with POOLED capacity (the TPU analogue of shared
                     peripherals: padding amortized at group granularity).
  expert-choice      routing where experts pick tokens (Zhou et al.); decode
                     uses the GO cache (core/go_cache.py) instead of this.

Every routed path executes on one of two BACKENDS, selected by
`MoEConfig.backend` (resolved by `resolve_backend`):

  "xla"     masked/capacity-padded einsum realization. group_forward masks
            over the g group members (g x redundant FLOPs); dispatch packs
            [E, C, d] capacity buffers. Correct everywhere; the CPU default.
  "pallas"  the tile-dispatch grouped GEMM (kernels/moe_gmm + kernels/ops):
            (group, expert)-sorted rows stream through ONE execution lane,
            each expert weight tile staged exactly once per column stripe —
            the paper's C1 multiplexing with ZERO redundant member passes.
            Combine weights are applied in-kernel (gmm_scaled); the path is
            dropless (worst-case tile padding instead of capacity drops;
            pooled-capacity overflow reduces to zero combine weights so the
            C1 drop semantics are preserved bit-for-bit).
  "auto"    pallas on TPU (Mosaic lowering), xla elsewhere.

Aux outputs carry load statistics for the balance loss and for the C2
workload tracer.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core import routing as R
from repro.kernels import ops as OPS
from repro.models.layers import dense_init, split


def resolve_backend(e: MoEConfig, refs=None) -> str:
    """Resolve `MoEConfig.backend` to the concrete engine for this host.

    Pass the layer inputs/params (any pytree) as `refs` to fail fast when an
    EXPLICIT backend="pallas" is traced for differentiation: the pallas
    kernels define no VJP yet, and without this guard the failure surfaces
    deep inside jax at transpose time as a bare `NotImplementedError` with
    an EMPTY message (grads flow through the params, so the activations
    alone are not enough — a layer-level `jax.grad` over params closes over
    constant activations)."""
    b = getattr(e, "backend", "auto")
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if b not in ("xla", "pallas"):
        raise ValueError(f"unknown MoE backend: {b!r}")
    if b == "pallas" and refs is not None and any(
            _under_autodiff(l) for l in jax.tree.leaves(refs)):
        raise NotImplementedError(
            "pallas backend has no backward pass yet; use backend='auto' or "
            "'xla' for training (loss_fn already pins 'auto' to xla — see "
            "ROADMAP: custom VJP over gmm/gmm_swiglu)")
    return b


def _under_autodiff(x) -> bool:
    """Best-effort: is `x` being traced for differentiation? Walks the tracer
    nesting for a linearize tracer (grad/vjp) or a JVP tracer (jvp),
    unwrapping vmap tracers along the way. grad-of-jit retraces are caught at
    transpose time by jax itself — this only makes the common paths fail
    early and clearly. jax does not export the linearize tracer's class, so
    it is matched by name."""
    from jax.interpreters import ad
    t = x
    for _ in range(16):
        if not isinstance(t, jax.core.Tracer):
            return False
        if isinstance(t, ad.JVPTracer) or \
                type(t).__name__ == "LinearizeTracer":
            return True
        t = getattr(t, "primal", getattr(t, "val", None))
    return False


def _block_rows(e: MoEConfig) -> int:
    return getattr(e, "gmm_block_rows", 0) or OPS.default_block_rows()


# ----------------------------------------------------------------------- init

def moe_init(key, d_model: int, e: MoEConfig, dtype) -> dict:
    ks = split(key, 7)
    E, de = e.num_experts, e.d_expert

    def bank(k1, k2, k3, n):
        kk1 = jax.random.split(k1, n)
        kk2 = jax.random.split(k2, n)
        kk3 = jax.random.split(k3, n)
        return {
            "wi": jax.vmap(lambda k: dense_init(k, d_model, de, dtype))(kk1),
            "wg": jax.vmap(lambda k: dense_init(k, d_model, de, dtype))(kk2),
            "wo": jax.vmap(lambda k: dense_init(k, de, d_model, dtype))(kk3),
        }

    p = {
        "gate": dense_init(ks[0], d_model, E, jnp.float32),
        "experts": bank(ks[1], ks[2], ks[3], E),
    }
    if e.num_shared_experts:
        p["shared"] = bank(ks[4], ks[5], ks[6], e.num_shared_experts)
    return p


def _expert_gemm(bank: dict, x: jax.Array) -> jax.Array:
    """x [E, C, d] -> [E, C, d] through each expert's SwiGLU FFN."""
    bank = OPS.ExpertStack.of(bank).sliced()
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, bank["wg"])) * jnp.einsum(
        "ecd,edf->ecf", x, bank["wi"])
    return jnp.einsum("ecf,efd->ecd", h, bank["wo"])


def _shared_out(params: dict, x: jax.Array) -> jax.Array:
    """Always-on shared experts (deepseek-style). x [T, d]."""
    if "shared" not in params:
        return jnp.zeros_like(x)
    sh = params["shared"]
    with jax.named_scope("shared"):
        h = jax.nn.silu(jnp.einsum("td,sdf->stf", x, sh["wg"])) * \
            jnp.einsum("td,sdf->stf", x, sh["wi"])
        return jnp.einsum("stf,sfd->td", h, sh["wo"]).astype(x.dtype)


def expert_ffn_all(params: dict, x: jax.Array) -> jax.Array:
    """All-expert outputs for a token batch. x [B, d] -> [B, E, d].
    Used by the GO-cache decode step (dense fallback) and the oracle."""
    b = OPS.ExpertStack.of(params["experts"]).sliced()
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, b["wg"])) * jnp.einsum(
        "td,edf->etf", x, b["wi"])
    return jnp.einsum("etf,efd->ted", h, b["wo"])


# --------------------------------------------------------------------- oracle

def dense_forward(params: dict, x: jax.Array, e: MoEConfig) -> jax.Array:
    """Reference: [T, d] -> [T, d], token-choice or expert-choice, no capacity
    limits (expert-choice uses exact top-C over the full batch)."""
    T = x.shape[0]
    eo = expert_ffn_all(params, x)                       # [T, E, d]
    if e.routing == "token_choice":
        r = R.token_choice(x, params["gate"], e.top_k)
        mask = jnp.zeros((T, e.num_experts), jnp.float32)
        mask = jax.vmap(lambda m, i, w: m.at[i].add(w))(mask, r.expert_idx, r.weights)
    else:
        cap = ec_capacity(T, e)
        r = R.expert_choice(x, params["gate"], cap)
        mask = jnp.zeros((e.num_experts, T), jnp.float32)
        mask = jax.vmap(lambda m, i, w: m.at[i].add(w))(
            mask, r.token_idx, r.weights)
        mask = mask.T
    y = jnp.einsum("te,ted->td", mask, eo.astype(jnp.float32))
    return (y + _shared_out(params, x).astype(jnp.float32)).astype(x.dtype)


def ec_capacity(num_tokens: int, e: MoEConfig) -> int:
    """Expert-choice capacity: on average top_k experts per token."""
    return max(1, (num_tokens * e.top_k) // e.num_experts)


# --------------------------------------------- sort-based capacity dispatch

class DispatchPlan(NamedTuple):
    x_disp: jax.Array        # [E, C, d] dispatched tokens (zeros where empty)
    dest: jax.Array          # [N] flat slot (E*C = dropped)
    weights: jax.Array       # [N] combine weights
    token: jax.Array         # [N] source token per pair
    counts: jax.Array        # [E] tokens routed per expert (pre-capacity)


def _expert_positions(expert_flat):
    """Stable expert-sort of routed pairs + each pair's position within its
    expert's run — THE capacity-eviction order. Every realization of a
    capacity drop (buffer eviction in `_plan_dispatch`, zero combine weights
    in the EP pallas branch) must consume this one definition, or sharded
    xla-vs-pallas drop parity silently breaks."""
    N = expert_flat.shape[0]
    order = jnp.argsort(expert_flat, stable=True)
    se = expert_flat[order]
    pos = jnp.arange(N, dtype=jnp.int32) - jnp.searchsorted(
        se, se, side="left").astype(jnp.int32)
    return order, se, pos


def _plan_dispatch(x, expert_flat, weights_flat, token_flat, E, C):
    N = expert_flat.shape[0]
    order, se, pos = _expert_positions(expert_flat)
    dest_sorted = jnp.where(pos < C, se * C + pos, E * C)
    # O(N) scatter inversion of the sort permutation (was a second argsort)
    dest = jnp.zeros((N,), jnp.int32).at[order].set(dest_sorted)
    buf = jnp.zeros((E * C + 1, x.shape[-1]), x.dtype)
    x_disp = buf.at[dest].set(x[token_flat], mode="drop")[:-1].reshape(E, C, -1)
    counts = jnp.bincount(expert_flat, length=E)
    return DispatchPlan(x_disp, dest, weights_flat, token_flat, counts)


def _combine(y_disp, plan, T, out_dtype):
    flat = jnp.concatenate(
        [y_disp.reshape(-1, y_disp.shape[-1]),
         jnp.zeros((1, y_disp.shape[-1]), y_disp.dtype)], axis=0)
    y_pairs = flat[plan.dest].astype(jnp.float32) * plan.weights[:, None]
    out = jnp.zeros((T, y_disp.shape[-1]), jnp.float32)
    out = out.at[plan.token].add(y_pairs)
    return out.astype(out_dtype)


def dispatch_forward(params: dict, x: jax.Array, e: MoEConfig,
                     capacity: int = 0) -> tuple:
    """Production token-choice path. x [T, d] -> (y [T, d], aux dict).

    backend="pallas" routes through the tile-dispatch grouped GEMM: no
    [E, C, d] capacity buffer and no drops (padding absorbs the worst case),
    combine weights fused in-kernel."""
    if resolve_backend(e, (x, params)) == "pallas":
        return _dispatch_forward_pallas(params, x, e)
    T = x.shape[0]
    E, k = e.num_experts, e.top_k
    C = capacity or max(1, int(math.ceil(T * k / E * e.capacity_factor)))
    with jax.named_scope("router"):
        r = R.token_choice(x, params["gate"], k)
    expert_flat = r.expert_idx.reshape(-1).astype(jnp.int32)
    weights_flat = r.weights.reshape(-1)
    token_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    with jax.named_scope("dispatch"):
        plan = _plan_dispatch(x, expert_flat, weights_flat, token_flat, E, C)
    with jax.named_scope("experts"):
        y_disp = _expert_gemm(params["experts"], plan.x_disp)
    shared = _shared_out(params, x)
    with jax.named_scope("combine"):
        y = _combine(y_disp, plan, T, x.dtype) + shared
    aux = {
        "counts": plan.counts,
        "balance_loss": R.load_balance_loss(r.scores, r.expert_idx, E),
        "dropped": (plan.dest == E * C).sum(),
    }
    return y, aux


def _dispatch_forward_pallas(params: dict, x: jax.Array, e: MoEConfig) -> tuple:
    """Token-choice through the tile-dispatch grouped GEMM (dropless)."""
    T = x.shape[0]
    E, k = e.num_experts, e.top_k
    with jax.named_scope("router"):
        r = R.token_choice(x, params["gate"], k)
    ef = r.expert_idx.reshape(-1).astype(jnp.int32)
    wf = r.weights.reshape(-1)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    y, _, plan = OPS.moe_ffn_fused(x, tok, ef, wf, params["experts"], E, T,
                                   bn=_block_rows(e))
    shared = _shared_out(params, x)
    with jax.named_scope("combine"):
        y = y.astype(x.dtype) + shared
    aux = {
        "counts": plan.counts,
        "balance_loss": R.load_balance_loss(r.scores, r.expert_idx, E),
        "dropped": jnp.zeros((), jnp.int32),
    }
    return y, aux


def group_forward(params: dict, x: jax.Array, e: MoEConfig,
                  group_of_expert: jax.Array, pool_factor: float = 0.7,
                  members: jax.Array | None = None) -> tuple:
    """C1 — group-multiplexed path with POOLED group capacity.

    Experts of a group share one lane buffer of size C_grp = g * C_exp *
    pool_factor: pooling lets a hot expert borrow slots from its cold
    group-mates (the paper pairs them by sorted load precisely so this works),
    cutting padded slots vs per-expert buckets at equal drop rate.
    The XLA realization masks over the g members (g x redundant FLOPs); the
    pallas backend removes the redundancy by expert-indexed weight staging
    over (group, expert)-sorted tiles. `members` is the [G, g] expert-id
    matrix precomputed at deployment (models/model.py:expert_group_members);
    when None it is derived from `group_of_expert` in-trace.
    """
    T = x.shape[0]
    E, k, g = e.num_experts, e.top_k, e.group_size
    G = E // g
    C_exp = max(1, int(math.ceil(T * k / E * e.capacity_factor)))
    C_grp = max(1, int(math.ceil(g * C_exp * pool_factor)))
    if members is None:
        members = _members_matrix(group_of_expert, G, g)         # [G, g]
    if resolve_backend(e, (x, params)) == "pallas":
        return _group_forward_pallas(params, x, e, group_of_expert, members,
                                     C_grp)
    r = R.token_choice(x, params["gate"], k)
    expert_flat = r.expert_idx.reshape(-1).astype(jnp.int32)
    grp_flat = group_of_expert[expert_flat]
    weights_flat = r.weights.reshape(-1)
    token_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    # dispatch by GROUP, but keep rows sorted by (group, expert) so the kernel
    # sees expert-contiguous runs (dispatch-locality analogue of Alg. 1)
    order, sg, pos = _group_sorted_positions(grp_flat, expert_flat, E)
    dest_sorted = jnp.where(pos < C_grp, sg * C_grp + pos, G * C_grp)
    N = order.shape[0]
    dest = jnp.zeros((N,), jnp.int32).at[order].set(dest_sorted)
    buf = jnp.zeros((G * C_grp + 1, x.shape[-1]), x.dtype)
    x_disp = buf.at[dest].set(x[token_flat], mode="drop")[:-1].reshape(G, C_grp, -1)
    row_expert = jnp.full((G * C_grp + 1,), -1, jnp.int32).at[dest].set(
        expert_flat, mode="drop")[:-1].reshape(G, C_grp)

    # XLA fallback: accumulate each member's masked contribution
    bank = OPS.ExpertStack.of(params["experts"]).sliced()
    y_disp = jnp.zeros(x_disp.shape, jnp.float32)
    for j in range(g):
        eid = members[:, j]                                      # [G]
        wg = bank["wg"][eid]
        wi = bank["wi"][eid]
        wo = bank["wo"][eid]
        h = jax.nn.silu(jnp.einsum("gcd,gdf->gcf", x_disp, wg)) * jnp.einsum(
            "gcd,gdf->gcf", x_disp, wi)
        yj = jnp.einsum("gcf,gfd->gcd", h, wo)
        m = (row_expert == eid[:, None])[..., None]
        y_disp = y_disp + jnp.where(m, yj.astype(jnp.float32), 0.0)

    plan = DispatchPlan(x_disp, dest, weights_flat, token_flat,
                        jnp.bincount(expert_flat, length=E))
    y = _combine(y_disp.astype(x.dtype), plan, T, x.dtype) + _shared_out(params, x)
    aux = {
        "counts": plan.counts,
        "balance_loss": R.load_balance_loss(r.scores, r.expert_idx, E),
        "dropped": (dest == G * C_grp).sum(),
        "slots": G * C_grp,
    }
    return y, aux


def _group_sorted_positions(grp: jax.Array, ef: jax.Array, E: int):
    """(group, expert)-stable sort of routed pairs + position of each pair
    within its GROUP's run. ONE definition shared by both backends: the
    pooled-capacity drop set (pos >= C_grp) must be identical whether it is
    realized as a buffer eviction (xla) or a zero combine weight (pallas) —
    pinned by tests/test_moe_paths.py drop-parity."""
    sort_key = grp * E + ef
    order = jnp.argsort(sort_key, stable=True)
    sg = grp[order]
    pos = jnp.arange(order.shape[0], dtype=jnp.int32) - jnp.searchsorted(
        sg, sg, side="left").astype(jnp.int32)
    return order, sg, pos


@functools.lru_cache(maxsize=None)
def _group_fuse_pairs(E: int, g: int) -> tuple:
    """Pairwise lane-fusion map over the group-major lane ranks: members of
    one C2 group pair up two at a time (an odd trailing member rides alone),
    so each pair of under-occupied member runs shares its boundary tile —
    the roadmap's dynamic lane fusion, static per deployment."""
    fuse = [0] * E
    nid = 0
    for grp in range(E // g):
        for j in range(0, g, 2):
            fuse[grp * g + j] = nid
            if j + 1 < g:
                fuse[grp * g + j + 1] = nid
            nid += 1
    return tuple(fuse)


def group_lane_map(members: jax.Array, group_size: int):
    """ONE definition of the C1 group-major lane layout, shared by the
    production path (`_group_forward_pallas`) and the benchmark's plan
    accounting: lane rank r holds expert `lane_of_rank[r]`, and lanes fuse
    pairwise within their group. Returns (lane_of_rank [E], rank_of_expert
    [E], fuse tuple [E])."""
    lane_of_rank = jnp.asarray(members, jnp.int32).reshape(-1)
    E = lane_of_rank.shape[0]
    rank_of_expert = jnp.zeros((E,), jnp.int32).at[lane_of_rank].set(
        jnp.arange(E, dtype=jnp.int32))
    return lane_of_rank, rank_of_expert, _group_fuse_pairs(E, group_size)


def _group_forward_pallas(params: dict, x: jax.Array, e: MoEConfig,
                          group_of_expert: jax.Array, members: jax.Array,
                          C_grp: int) -> tuple:
    """C1 pooled-capacity semantics on the zero-redundancy kernel.

    The SAME (group, expert)-stable order as the XLA path decides which pairs
    overflow the pooled group buffer; overflow pairs keep their rows but get a
    ZERO combine weight — numerically identical to a drop, while every
    surviving row streams through the grouped GEMM exactly once (no g x
    member masking). Tiles are planned in group-major lane order with the
    group's member lanes FUSED pairwise (`_group_fuse_pairs`), so the
    multiplexed lane sees its members' runs back to back in shared tiles.
    """
    T = x.shape[0]
    E, k, g = e.num_experts, e.top_k, e.group_size
    G = E // g
    r = R.token_choice(x, params["gate"], k)
    ef = r.expert_idx.reshape(-1).astype(jnp.int32)
    grp = group_of_expert[ef]
    wf = r.weights.reshape(-1)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    N = ef.shape[0]

    # pooled-capacity overflow in (group, expert)-stable order == XLA drops
    order, _, pos = _group_sorted_positions(grp, ef, E)
    keep = jnp.zeros((N,), bool).at[order].set(pos < C_grp)
    wf = jnp.where(keep, wf, 0.0)

    # group-major lane ranks: lane r holds expert members.flatten()[r]
    lane_of_rank, rank_of_expert, fuse = group_lane_map(members, g)
    y, _, plan = OPS.moe_ffn_fused(
        x, tok, rank_of_expert[ef], wf, params["experts"], E, T,
        expert_of_lane=lane_of_rank, bn=_block_rows(e), fuse=fuse)
    y = y.astype(x.dtype) + _shared_out(params, x)
    aux = {
        "counts": jnp.bincount(ef, length=E),
        "balance_loss": R.load_balance_loss(r.scores, r.expert_idx, E),
        "dropped": (~keep).sum(),
        "slots": G * C_grp,
    }
    return y, aux


def _members_matrix(group_of_expert: jax.Array, G: int, g: int) -> jax.Array:
    """[E] group ids -> [G, g] expert ids per group (host-traceable)."""
    E = group_of_expert.shape[0]
    order = jnp.argsort(group_of_expert * E + jnp.arange(E), stable=True)
    return order.reshape(G, g).astype(jnp.int32)


# ------------------------------------------------------------- expert choice

def expert_choice_forward(params: dict, x: jax.Array, e: MoEConfig,
                          valid_len=None) -> tuple:
    """Expert-choice prefill/train: each expert gathers its top-C tokens.
    Returns (y, aux) where aux also carries what the GO cache needs.
    `valid_len` masks right-padded (bucketed-prefill) positions out of the
    routing, so pads never enter the GO cache."""
    if resolve_backend(e, (x, params)) == "pallas":
        return _expert_choice_forward_pallas(params, x, e, valid_len)
    T = x.shape[0]
    cap = ec_capacity(T, e)
    r = R.expert_choice(x, params["gate"], cap, valid_len=valid_len)
    x_disp = x[r.token_idx]                               # [E, C, d] (gather)
    y_disp = _expert_gemm(params["experts"], x_disp)      # [E, C, d]
    w = r.weights                                         # [E, C]
    contrib = y_disp.astype(jnp.float32) * w[..., None]
    out = jnp.zeros((T, x.shape[-1]), jnp.float32)
    out = out.at[r.token_idx.reshape(-1)].add(contrib.reshape(-1, x.shape[-1]))
    y = out.astype(x.dtype) + _shared_out(params, x)
    aux = {
        "counts": jnp.bincount(r.token_idx.reshape(-1), length=T),
        "chosen_tokens": r.token_idx,
        "chosen_scores": w,
        "weighted_outputs": contrib.astype(x.dtype),      # [E, C, d]
        "scores": r.scores,
    }
    return y, aux


def _expert_choice_forward_pallas(params: dict, x: jax.Array,
                                  e: MoEConfig, valid_len=None) -> tuple:
    """Expert-choice through the grouped GEMM: (expert, slot) pairs are
    already expert-contiguous, so the tile plan is the identity layout and
    every expert's top-C tokens stream through the lane in one run."""
    T, d = x.shape
    cap = ec_capacity(T, e)
    E = e.num_experts
    r = R.expert_choice(x, params["gate"], cap, valid_len=valid_len)
    ef = jnp.repeat(jnp.arange(E, dtype=jnp.int32), cap)
    tok = r.token_idx.reshape(-1).astype(jnp.int32)
    wf = r.weights.reshape(-1)
    y, y_rows, plan = OPS.moe_ffn_fused(x, tok, ef, wf, params["experts"],
                                        E, T, bn=_block_rows(e))
    contrib = OPS.gather_rows(y_rows, plan).reshape(E, cap, d)   # fp32
    y_out = y.astype(x.dtype) + _shared_out(params, x)
    aux = {
        "counts": jnp.bincount(tok, length=T),
        "chosen_tokens": r.token_idx,
        "chosen_scores": r.weights,
        "weighted_outputs": contrib.astype(x.dtype),             # [E, C, d]
        "scores": r.scores,
    }
    return y_out, aux


def expert_choice_forward_batched(params: dict, h: jax.Array,
                                  e: MoEConfig, valid_len=None) -> tuple:
    """Batched expert-choice on the pallas backend: routing stays PER
    SEQUENCE (the GO-cache / train==serve semantics), but the FFN pairs of
    the whole batch flatten into ONE tile plan so the grouped GEMM amortizes
    its per-expert padding across the batch instead of paying it B times.
    h [B, S, d] -> (y [B, S, d], aux vmapped like the per-sequence path)."""
    B, S, d = h.shape
    cap = ec_capacity(S, e)
    E = e.num_experts
    r = jax.vmap(lambda xb: R.expert_choice(
        xb, params["gate"], cap, valid_len=valid_len))(h)
    ef = jnp.tile(jnp.repeat(jnp.arange(E, dtype=jnp.int32), cap), B)
    tok = (r.token_idx.astype(jnp.int32)
           + (jnp.arange(B, dtype=jnp.int32) * S)[:, None, None]).reshape(-1)
    wf = r.weights.reshape(-1)
    y, y_rows, plan = OPS.moe_ffn_fused(
        h.reshape(B * S, d), tok, ef, wf, params["experts"], E, B * S,
        bn=_block_rows(e))
    contrib = OPS.gather_rows(y_rows, plan).reshape(B, E, cap, d)
    y = y.reshape(B, S, d).astype(h.dtype) + jax.vmap(
        lambda xb: _shared_out(params, xb))(h)
    aux = {
        "counts": jax.vmap(lambda t: jnp.bincount(t.reshape(-1), length=S))(
            r.token_idx),
        "chosen_tokens": r.token_idx,
        "chosen_scores": r.weights,
        "weighted_outputs": contrib.astype(h.dtype),             # [B, E, C, d]
        "scores": r.scores,
    }
    return y, aux


# -------------------------------------------------------------------- decode

def token_choice_decode(params: dict, x: jax.Array, e: MoEConfig) -> jax.Array:
    """Decode step for token-choice: x [B, d] one token per sequence.
    Dropless: capacity bounds the worst case (every row picks the same expert),
    so serving never silently drops a token's expert contribution. (The pallas
    backend is dropless by construction.)"""
    y, _ = dispatch_forward(
        params, x, e, capacity=max(1, x.shape[0] * e.top_k))
    return y


def moe_forward(params: dict, x: jax.Array, e: MoEConfig,
                group_of_expert=None, group_members=None) -> tuple:
    """Router for the full-sequence paths; x [T, d]."""
    if e.routing == "expert_choice":
        return expert_choice_forward(params, x, e)
    if e.use_grouped_gemm and e.group_size > 1 and group_of_expert is not None:
        return group_forward(params, x, e, group_of_expert,
                             members=group_members)
    return dispatch_forward(params, x, e)


# --------------------------------------------------- expert-parallel (EP)

def moe_forward_ep(params: dict, h: jax.Array, e: MoEConfig) -> tuple:
    """True expert parallelism via shard_map over the model axis.

    Each model shard owns E/M experts ([E, ...] banks are EP-sharded by the
    rule-based sharder); the routing gate is replicated and each shard
    dispatches ONLY the (token, expert) pairs that hit its local experts, so
    dispatch buffers shrink by M and never cross the batch sharding. Partial
    outputs are combined with a psum — the EP analogue of the paper's
    shared-peripheral combine. The C2 load-aware permutation is applied to
    the expert index at deployment so each shard's aggregate load balances
    (straggler mitigation at the MoE layer).

    Both backends run INSIDE the shard body. backend="xla" packs a per-shard
    [E_loc, C, d] capacity buffer; backend="pallas" builds a PER-SHARD tile
    plan (plan_tile_dispatch with the shard's expert_offset/num_local window:
    non-local pairs ride a skipped drop lane) and streams the local pairs
    through the grouped GEMM. Capacity overflow is decided by ONE rule —
    position in the expert-stable sorted order, the same order _plan_dispatch
    evicts in — so both backends drop the SAME pairs (pallas realizes a drop
    as a zero combine weight, pinned by tests/test_moe_mesh.py).

    h [B, S, d] -> (y [B, S, d], aux). Token-choice only; requires
    E % model_axis == 0 (callers fall back to the vmapped path otherwise).
    """
    from jax.sharding import PartitionSpec as P

    from repro.models.layers import current_mesh, dp_spec

    mesh = current_mesh()
    M = mesh.shape["model"]
    E, k = e.num_experts, e.top_k
    E_loc = E // M
    B, S, d = h.shape
    dp = dp_spec()
    C = max(1, int(math.ceil(S * k / E * e.capacity_factor)))
    use_pallas = resolve_backend(e, (h, params)) == "pallas"
    bn = _block_rows(e)

    def body(h_loc, gate, wg, wi, wo):
        i = jax.lax.axis_index("model")
        lo = i * E_loc

        def per_seq(xb):
            r = R.token_choice(xb, gate, k)
            ef = r.expert_idx.reshape(-1).astype(jnp.int32)
            wf = r.weights.reshape(-1)
            tok = jnp.repeat(jnp.arange(S, dtype=jnp.int32), k)
            local = (ef >= lo) & (ef < lo + E_loc)
            ef_l = jnp.where(local, ef - lo, E_loc)     # E_loc = drop bucket
            bal = R.load_balance_loss(r.scores, r.expert_idx, E)
            if use_pallas:
                # same per-shard capacity rule as the xla buffer below: the
                # planner's `pos` is the pair's rank within its lane's stable
                # run (derived from the plan's own sort — no second argsort);
                # evicted pairs keep their rows, lose their combine weight
                y, _, plan = OPS.moe_ffn_fused(
                    xb, tok, ef, wf, {"wg": wg, "wi": wi, "wo": wo}, E, S,
                    bn=bn, expert_offset=lo, num_local=E_loc, capacity=C)
                cnt = plan.counts[:E_loc]
                dropped = (local & (plan.pos >= C)).sum()
            else:
                plan = _plan_dispatch(xb, ef_l, wf, tok, E_loc, C)
                hdn = jax.nn.silu(jnp.einsum(
                    "ecd,edf->ecf", plan.x_disp, wg)) * jnp.einsum(
                    "ecd,edf->ecf", plan.x_disp, wi)
                y_disp = jnp.einsum("ecf,efd->ecd", hdn, wo)
                y = _combine(y_disp, plan, S, jnp.float32)
                cnt = jnp.bincount(ef_l, length=E_loc + 1)[:E_loc]
                dropped = (local & (plan.dest == E_loc * C)).sum()
            return y, bal, cnt, dropped

        y, bal, cnt, dropped = jax.vmap(per_seq)(h_loc)
        y = jax.lax.psum(y, "model")
        cnt = jax.lax.psum(cnt.sum(0), dp) if dp else cnt.sum(0)
        dropped = jax.lax.psum(dropped.sum(), ("model",) + (dp or ()))
        bal = jax.lax.pmean(bal.mean(), dp) if dp else bal.mean()
        return (y, bal, cnt, dropped)

    bank = OPS.ExpertStack.of(params["experts"]).sliced()
    y, bal, cnt, dropped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(dp, None, None), P(), P("model"), P()),
        check_vma=False,
    )(h, params["gate"], bank["wg"], bank["wi"], bank["wo"])

    y = y.astype(h.dtype) + jax.vmap(lambda xb: _shared_out(params, xb))(h)
    aux = {"counts": cnt, "balance_loss": bal, "dropped": dropped}
    return y, aux


def ep_available(e: MoEConfig) -> bool:
    """EP path usable: inside a mesh whose model axis divides E."""
    from repro.models.layers import current_mesh
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return False
    M = mesh.shape["model"]
    return M > 1 and e.num_experts % M == 0

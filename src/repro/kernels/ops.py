"""jit'd wrappers around the Pallas kernels + the PACKED tile-dispatch planner
that connects them to the MoE layer.

`plan_tile_dispatch` realizes the paper's scheduling insight in TPU terms:
tokens are sorted by (group, expert) and packed into row tiles so the grouped
GEMM stages every expert weight tile into VMEM exactly once per column stripe
(Algorithm 1's "no repeated transfers"). Three packing rules keep the grid at
a static occupancy bound instead of the padded worst case:

  elision   dropped pairs (the EP non-local window, capacity-evicted rows of
            a foreign shard) consume NO buffer rows: their `dest` is the
            n_pad sentinel, so the packed buffer holds only planned lanes.
  fusion    lanes of one C2 group are PAIRED: a pair's two runs concatenate
            unpadded and round to the tile boundary together (the roadmap's
            dynamic lane fusion). At most one tile per pair straddles both
            lanes; the kernels resolve it with a per-row selector
            (`row_sel`) and a secondary weight stream (`tile_expert2`).
            Static tiles drop from N/bn + L to N/bn + P (P = lane pairs).
  counting  for decode-sized inputs the stable per-lane ranks come from an
            O(N·L) one-hot cumsum (no argsort); the structural layout
            (pairing, lane order, static tile count) is host-computed once
            per shape (`_fusion_layout`, lru-cached) and reused by every
            tick, layer and trace — the persistent part of the planner.

Concrete (non-traced) routing outputs additionally hit a host-side
`PlanCache`, so repeated eager planning over the same routing is free.

Production entry points (what core/moe.py's `backend="pallas"` routes to):

  moe_ffn_fused       (token, expert) pairs -> combined [T, d] output with
                      the per-pair combine weights applied IN-KERNEL
                      (gmm_scaled) and rows scatter-added straight into the
                      token buffer — no gather + fp32 multiply pass.
  go_selected_ffn     C4 decode: the per-tick shape is fixed (B tokens, at
                      most B rows per expert), so the decode plan is STATIC
                      per-lane capacity slots — one `top_k` builds the whole
                      gather map, the tile map is a compile-time constant,
                      and a `lax.cond` executes the C_fast ≈ 2·B·k/E budget
                      tiles unless a tick overflows it (then the full B-row
                      plan runs — always correct, never dropped).
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe_gmm import (default_interpret, gmm, gmm_scaled,
                                   gmm_swiglu, lowering_platform)


def default_block_rows() -> int:
    """Row-tile height: MXU-aligned when lowering for TPU; small otherwise so
    the interpreted correctness path does not drown in padding tiles."""
    return 128 if lowering_platform() == "tpu" else 8


class TilePlan(NamedTuple):
    dest: jax.Array           # [N] packed row per pair (n_pad = elided/dropped)
    row_pair: jax.Array       # [n_pad] source pair per packed row (N = padding)
    row_sel: jax.Array        # [n_pad, 1] fp32 1.0 primary-lane row, 0.0
                              # secondary-lane row of a fused pair
    tile_expert: jax.Array    # [n_tiles] primary lane per row tile
    tile_expert2: jax.Array   # [n_tiles] secondary lane (== tile_expert
                              # except on a fused pair's straddle tile)
    tile_valid: jax.Array     # [n_tiles] bool — tile carries >=1 real row
    row_valid: jax.Array      # [n_pad] bool — real row vs alignment padding
    counts: jax.Array         # [lanes] pairs per lane (pre-capacity);
                              # windowed plans append the drop-lane count
    pos: jax.Array            # [N] pair's rank within its lane's stable run —
                              # THE capacity-eviction order shared with the
                              # xla dispatch buffer (0 for dropped pairs)
    occupied: jax.Array       # [] traced number of valid tiles
    n_pad: int                # static packed row count
    n_tiles: int              # static grid size (n_pad // bn)


def padded_rows(num_pairs: int, num_lanes: int, bn: int,
                num_pairs_fused: int = 0) -> int:
    """Static packed row bound: whole-N tiles plus one boundary tile per lane
    pair (every lane its own pair without fusion — the pre-packing worst
    case padded_rows(N, L) == round_up(N + L*bn))."""
    P = num_pairs_fused or num_lanes
    return -(-num_pairs // bn) * bn + P * bn


class _FusionLayout(NamedTuple):
    prim: np.ndarray          # [P] primary lane of each pair
    sec: np.ndarray           # [P] secondary lane (== prim for singletons)
    pair_of: np.ndarray       # [L] pair id per lane
    is_sec: np.ndarray        # [L] lane is its pair's secondary member
    P: int


@functools.lru_cache(maxsize=None)
def _fusion_layout(L: int, fuse: tuple | None) -> _FusionLayout:
    """Host-side structural plan, computed once per (lane count, pairing) and
    shared by every tick/layer/trace of that shape. `fuse` maps each lane to
    a fusion-pair id; each id may own one or two lanes."""
    if fuse is None:
        ar = np.arange(L)
        return _FusionLayout(ar, ar.copy(), ar.copy(),
                             np.zeros(L, bool), L)
    fuse = np.asarray(fuse, np.int64)
    assert fuse.shape == (L,), f"fuse covers {fuse.shape} of {L} lanes"
    ids = np.unique(fuse)
    prim = np.empty(len(ids), np.int64)
    sec = np.empty(len(ids), np.int64)
    pair_of = np.empty(L, np.int64)
    is_sec = np.zeros(L, bool)
    for j, fid in enumerate(ids):
        members = np.where(fuse == fid)[0]
        assert 1 <= len(members) <= 2, \
            f"fusion pair {fid} has {len(members)} lanes (max 2)"
        prim[j], sec[j] = members[0], members[-1]
        pair_of[members] = j
        if len(members) == 2:
            is_sec[members[1]] = True
    return _FusionLayout(prim, sec, pair_of, is_sec, len(ids))


def _lane_rank(lane: jax.Array, L: int):
    """Stable rank of each pair within its lane + per-lane counts [L].
    Entries == L (the drop sentinel) are excluded from counts and get rank 0.
    Decode-sized inputs use an O(N·L) one-hot cumsum (a vectorized counting
    sort — no argsort); large inputs fall back to the argsort ranking. Both
    produce the SAME stable order, so capacity parity is path-independent."""
    N = lane.shape[0]
    if N * (L + 1) <= (1 << 16):
        oh = (lane[:, None] == jnp.arange(L, dtype=lane.dtype)[None, :])
        cs = jnp.cumsum(oh.astype(jnp.int32), axis=0)
        pos = jnp.take_along_axis(
            cs, jnp.minimum(lane, L - 1).astype(jnp.int32)[:, None], 1)[:, 0] - 1
        counts = cs[-1]
    else:
        order = jnp.argsort(lane, stable=True)
        se = lane[order]
        ps = jnp.arange(N, dtype=jnp.int32) - jnp.searchsorted(
            se, se, side="left").astype(jnp.int32)
        # O(N) scatter inversion of the sort permutation (no second argsort)
        pos = jnp.zeros((N,), jnp.int32).at[order].set(ps)
        counts = jnp.bincount(lane, length=L)
    return jnp.where(lane < L, pos, 0).astype(jnp.int32), counts


class PlanCache:
    """Host-side memo over CONCRETE routing outputs: eager planning (tools,
    benchmarks, repeated decode ticks outside jit) reuses the finished plan
    instead of re-dispatching the planner ops. Traced inputs bypass it —
    inside jit the plan is part of the compiled step already."""

    def __init__(self, maxsize: int = 256):
        self.maxsize = maxsize
        self._store: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key):
        plan = self._store.get(key)
        if plan is not None:
            self._store.move_to_end(key)
            self.hits += 1
        else:
            self.misses += 1
        return plan

    def put(self, key, plan):
        self._store[key] = plan
        if len(self._store) > self.maxsize:
            self._store.popitem(last=False)

    def clear(self):
        self._store.clear()
        self.hits = self.misses = 0

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._store)}


_PLAN_CACHE = PlanCache()


def plan_cache_stats() -> dict:
    return _PLAN_CACHE.stats()


def _fuse_key(fuse):
    if fuse is None:
        return None
    return tuple(int(v) for v in np.asarray(fuse).reshape(-1))


def plan_tile_dispatch(expert_flat: jax.Array, num_experts: int, bn: int, *,
                       expert_offset: jax.Array | int = 0,
                       num_local: int = 0, fuse=None) -> TilePlan:
    """expert_flat [N] int32 (one entry per (token, expert) pair) -> packed
    tile layout. All shapes static; pure jnp (jit/pjit-safe).

    With `num_local > 0` the plan covers ONLY the local expert window
    [expert_offset, expert_offset + num_local): pairs outside it are ELIDED —
    they take no buffer rows (`dest` = the n_pad sentinel) and no tiles, so
    each EP shard's packed buffer scales with its local traffic. `counts`
    still appends the drop-lane tally. `tile_expert` indexes the LOCAL weight
    bank [0, num_local); the offset may be a traced `axis_index` (`num_local`
    is static so shapes agree across shards).

    `fuse` (static, [lanes] pair ids with <= 2 lanes per id) turns on lane
    fusion: a pair's runs pack into shared tiles, cutting the static grid
    from N/bn + L to N/bn + P tiles.
    """
    fuse_t = _fuse_key(fuse)
    cacheable = (not isinstance(expert_flat, jax.core.Tracer)
                 and not isinstance(expert_offset, jax.core.Tracer))
    if cacheable:
        key = (np.asarray(expert_flat).tobytes(), expert_flat.shape[0],
               int(num_experts), int(bn), int(expert_offset), int(num_local),
               fuse_t)
        hit = _PLAN_CACHE.get(key)
        if hit is not None:
            return hit
    plan = _plan_tile_dispatch(expert_flat, num_experts, bn, expert_offset,
                               num_local, fuse_t)
    if cacheable:
        _PLAN_CACHE.put(key, plan)
    return plan


def _plan_tile_dispatch(expert_flat, num_experts, bn, expert_offset,
                        num_local, fuse_t) -> TilePlan:
    if num_local:
        local_idx = expert_flat - expert_offset
        local = (local_idx >= 0) & (local_idx < num_local)
        lane = jnp.where(local, local_idx, num_local).astype(jnp.int32)
        L = num_local
        has_drop = True
    else:
        lane = expert_flat.astype(jnp.int32)
        L = num_experts
        has_drop = False
    N = lane.shape[0]
    lay = _fusion_layout(L, fuse_t)
    n_pad = padded_rows(N, L, bn, lay.P)
    n_tiles = n_pad // bn

    pos, counts = _lane_rank(lane, L)
    prim = jnp.asarray(lay.prim, jnp.int32)
    sec = jnp.asarray(lay.sec, jnp.int32)
    cA = counts[prim]
    cB = jnp.where(jnp.asarray(lay.sec != lay.prim), counts[sec], 0)
    pair_rows = (cA + cB).astype(jnp.int32)
    pair_pad = ((pair_rows + bn - 1) // bn) * bn
    ends = jnp.cumsum(pair_pad)
    pair_off = (ends - pair_pad).astype(jnp.int32)
    pair_of = jnp.asarray(lay.pair_of, jnp.int32)
    lane_start = pair_off[pair_of] + jnp.where(
        jnp.asarray(lay.is_sec), cA[pair_of], 0).astype(jnp.int32)
    dest = jnp.where(lane < L,
                     lane_start[jnp.minimum(lane, L - 1)] + pos,
                     n_pad).astype(jnp.int32)
    row_pair = jnp.full((n_pad,), N, jnp.int32).at[dest].set(
        jnp.arange(N, dtype=jnp.int32), mode="drop")

    # tile map: tile t covers packed rows [t*bn, (t+1)*bn); within one pair,
    # primary rows precede secondary rows, so at most ONE boundary (the
    # straddle) falls inside a tile. Trailing/empty tiles clamp to an
    # in-range lane (constant weight index -> the pipeline re-uses the
    # staged buffer) and are marked invalid so the kernel skips their MXU
    # work.
    ts = jnp.arange(n_tiles, dtype=jnp.int32) * bn
    tp_raw = jnp.searchsorted(ends, ts, side="right").astype(jnp.int32)
    tp = jnp.minimum(tp_raw, lay.P - 1)
    bound = pair_off[tp] + cA[tp]
    real_end = pair_off[tp] + pair_rows[tp]
    te = jnp.where(ts < bound, prim[tp], sec[tp]).astype(jnp.int32)
    te2 = jnp.where((bound > ts) & (bound < jnp.minimum(ts + bn, real_end)),
                    sec[tp], te).astype(jnp.int32)
    tile_valid = (tp_raw < lay.P) & (ts < real_end)

    ri = jnp.arange(n_pad, dtype=jnp.int32)
    rp = jnp.minimum(jnp.searchsorted(ends, ri, side="right"),
                     lay.P - 1).astype(jnp.int32)
    row_sel = (ri < (pair_off + cA)[rp]).astype(jnp.float32)[:, None]
    row_valid = ri < (pair_off + pair_rows)[rp]

    if has_drop:
        counts = jnp.concatenate(
            [counts, (N - counts.sum())[None].astype(counts.dtype)])
    return TilePlan(dest, row_pair, row_sel, te, te2, tile_valid, row_valid,
                    counts, pos, tile_valid.sum(), n_pad, n_tiles)


def scatter_rows(x_pairs: jax.Array, plan: TilePlan) -> jax.Array:
    """x_pairs [N, d] -> packed rows [n_pad, d] (zeros in padding and for
    elided pairs) — one gather through the plan's row_pair map."""
    xz = jnp.concatenate(
        [x_pairs, jnp.zeros((1, x_pairs.shape[-1]), x_pairs.dtype)])
    return xz[plan.row_pair]


def gather_rows(y_rows: jax.Array, plan: TilePlan) -> jax.Array:
    """Packed rows back to pair order [N, d]; elided pairs read zeros."""
    yz = jnp.concatenate(
        [y_rows, jnp.zeros((1, y_rows.shape[-1]), y_rows.dtype)])
    return yz[plan.dest]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def expert_ffn_gmm(x_rows: jax.Array, wg: jax.Array, wi: jax.Array,
                   wo: jax.Array, tile_expert: jax.Array,
                   tile_valid: jax.Array | None = None, *, bn: int = 128,
                   interpret: bool | None = None) -> jax.Array:
    """Tile-aligned rows [N_pad, d] through per-expert SwiGLU FFNs.
    interpret=None auto-selects: Mosaic on TPU, interpreter elsewhere."""
    h = gmm_swiglu(x_rows, wg, wi, tile_expert, tile_valid, bn=bn,
                   interpret=interpret)
    return gmm(h, wo, tile_expert, tile_valid, bn=bn, interpret=interpret)


def moe_ffn_fused(x_src: jax.Array, tok: jax.Array, ef: jax.Array,
                  wf: jax.Array, bank: dict, num_experts: int,
                  num_tokens: int, *, expert_of_lane: jax.Array | None = None,
                  bn: int = 0, interpret: bool | None = None,
                  expert_offset: jax.Array | int = 0, num_local: int = 0,
                  capacity: int = 0, fuse=None):
    """Grouped-GEMM MoE FFN over (token, expert) pairs with fused combine.

    x_src [T_src, d] source rows; tok [N] source row per pair; ef [N] lane id
    per pair (expert id, or a group-major lane rank when `expert_of_lane`
    maps lanes back to weight indices); wf [N] combine weights (zeroed pairs
    contribute nothing — capacity drops reduce to zero weights).

    With `num_local > 0`, `bank` holds only the LOCAL expert slice and `ef`
    carries GLOBAL ids: pairs outside [expert_offset, expert_offset +
    num_local) are elided from the packed buffer and contribute zero rows —
    the per-shard EP path (each model shard runs this over its own slice and
    psums the partial outputs).

    With `capacity > 0`, pairs past that position in their lane's stable run
    (`plan.pos`, the same rank the xla dispatch buffer evicts at) get a ZERO
    combine weight — capacity drops without a second sort; read the kept
    mask back off `plan.pos < capacity`.

    `fuse` (static pair ids per lane) packs paired lanes into shared tiles;
    the straddle tile's rows are resolved in-kernel via the plan's per-row
    selector, so fusion is numerically exact.

    Returns (y [num_tokens, d] fp32 combined output, y_rows [n_pad, d] fp32
    weighted per-row outputs, plan). The combine weight is applied in-kernel
    (gmm_scaled) and rows are scatter-added directly into the token buffer.
    """
    bn = bn or default_block_rows()
    plan = plan_tile_dispatch(ef, num_experts, bn, expert_offset=expert_offset,
                              num_local=num_local, fuse=fuse)
    if capacity:
        wf = jnp.where(plan.pos < capacity, wf, 0.0)
    te = (plan.tile_expert if expert_of_lane is None
          else expert_of_lane[plan.tile_expert])
    te2 = (plan.tile_expert2 if expert_of_lane is None
           else expert_of_lane[plan.tile_expert2])
    fused = fuse is not None
    N = ef.shape[0]
    # one gather per operand through the plan's row_pair map (sentinel N ->
    # the appended zero/sink entry)
    tok_z = jnp.concatenate(
        [tok.astype(jnp.int32), jnp.full((1,), num_tokens, jnp.int32)])
    row_token = tok_z[plan.row_pair]
    x_z = jnp.concatenate([x_src, jnp.zeros((1, x_src.shape[-1]), x_src.dtype)])
    x_rows = x_z[row_token]
    wf_z = jnp.concatenate([wf.astype(jnp.float32), jnp.zeros((1,))])
    scale = wf_z[plan.row_pair][:, None]
    h = gmm_swiglu(x_rows, bank["wg"], bank["wi"], te, plan.tile_valid,
                   tile_expert2=te2 if fused else None,
                   row_sel=plan.row_sel if fused else None,
                   bn=bn, interpret=interpret)
    y_rows = gmm_scaled(h, bank["wo"], te, plan.tile_valid, scale,
                        tile_expert2=te2 if fused else None,
                        row_sel=plan.row_sel if fused else None,
                        bn=bn, interpret=interpret)
    y = jnp.zeros((num_tokens, x_src.shape[-1]), jnp.float32).at[
        row_token].add(y_rows, mode="drop")
    return y, y_rows, plan


# ------------------------------------------------------------ GO decode plan

class GoDecodePlan(NamedTuple):
    counts: jax.Array         # [E] selected pairs per expert this tick
    C_fast: int               # static per-lane budget (rows) of the fast path
    C_full: int               # static per-lane rows of the fallback (== B)
    n_tiles_fast: int         # static grid of the fast path (E * C_fast / bn)
    n_tiles_full: int
    fallback: jax.Array       # [] traced bool — this tick overflowed C_fast


def go_decode_budget(batch: int, num_experts: int, topk_hint: int,
                     bn: int) -> int:
    """Static per-lane row budget for the fast decode path: with a warm GO
    cache each tick selects ~B·k pairs, so 2·B·k/E rows per expert plus two
    rows of small-batch headroom (rounded to the row tile) covers the
    steady state; the lax.cond fallback keeps overflow ticks exact."""
    if topk_hint <= 0:
        return batch
    c = -(-2 * batch * topk_hint // num_experts) + 2
    return min(-(-c // bn) * bn, batch)


def go_selected_ffn(x: jax.Array, selected: jax.Array, g: jax.Array,
                    bank: dict, num_experts: int, *, bn: int = 0,
                    interpret: bool | None = None, topk_hint: int = 0,
                    executor: str = "auto"):
    """C4 decode FFN over ONLY the (token, expert) pairs the TopKUpdate
    selected. x [B, d]; selected [B, E] bool; g [B, E] softmax affinities.

    The decode tick's shape is FIXED ([B, E] mask, at most B rows per
    expert), so the plan is static per-lane capacity slots: lane e owns rows
    [e*C, (e+1)*C), the tile map is a compile-time constant, and ONE
    `top_k` per tick recovers the selected row gather (the persistent decode
    planner — no sort, no cumsum offsets). With `topk_hint` (the router's k)
    a `lax.cond` executes only the C_fast = ~2·B·k/E budget rows unless the
    tick overflows the budget, in which case the full B-row plan runs —
    always exact, nothing is dropped.

    `executor` picks how the planned tiles execute: "pallas" streams them
    through gmm_swiglu/gmm_scaled (the TPU path; per-lane tiles, static
    tile_expert, dynamic tile_valid from the counts), "xla" runs the
    identical layout as a batched per-lane einsum (what interpret-mode hosts
    use — same plan, no interpreter overhead), "auto" resolves per platform.

    Returns (contrib [B, E, d] fp32 weighted outputs, zeros where
    unselected; GoDecodePlan) — exactly what `go_cache_step` caches and
    combines.
    """
    B, d = x.shape
    E = num_experts
    bn = bn or default_block_rows()
    if interpret is None:
        interpret = default_interpret()
    if executor == "auto":
        executor = "xla" if interpret else "pallas"
    selT = selected.T                                    # [E, B]
    counts = selT.sum(axis=1).astype(jnp.int32)
    # selected b's per expert in ascending order, via one top_k: selected
    # rows get descending positive keys, unselected distinct negatives
    ar = jnp.arange(B, dtype=jnp.int32)
    keys = jnp.where(selT, B - ar[None, :], -1 - ar[None, :])
    gT = g.T

    gsel = jnp.where(selT, gT, 0.0)           # softmax affinities are > 0

    def run(C: int):
        idx = jax.lax.top_k(keys, C)[1]                  # [E, C]
        w = jnp.take_along_axis(gsel, idx, axis=1)       # 0 on invalid slots
        if executor == "xla":
            x_disp = x[idx]                              # [E, C, d]
            h = jax.nn.silu(
                jnp.einsum("ecd,edf->ecf", x_disp, bank["wg"])) * jnp.einsum(
                "ecd,edf->ecf", x_disp, bank["wi"])
            y = jnp.einsum("ecf,efd->ecd", h,
                           bank["wo"]).astype(jnp.float32) * w[..., None]
        else:
            Cp = -(-C // bn) * bn
            idx_p = jnp.pad(idx, ((0, 0), (0, Cp - C)))
            x_rows = x[idx_p].reshape(E * Cp, d)
            scale = jnp.pad(w, ((0, 0), (0, Cp - C))).reshape(E * Cp, 1)
            te = jnp.repeat(jnp.arange(E, dtype=jnp.int32), Cp // bn)
            slot = jnp.arange(Cp // bn, dtype=jnp.int32) * bn
            tv = (slot[None, :] < counts[:, None]).reshape(-1)
            h = gmm_swiglu(x_rows, bank["wg"], bank["wi"], te, tv, bn=bn,
                           interpret=interpret)
            y_rows = gmm_scaled(h, bank["wo"], te, tv, scale, bn=bn,
                                interpret=interpret)
            y = y_rows.reshape(E, Cp, d)[:, :C]
        # scatter straight into the token-major contrib buffer (invalid
        # slots land in the sink row B) — no [E, B, d] transpose pass
        z = jnp.zeros((B + 1, E, d), jnp.float32)
        eix = jnp.broadcast_to(jnp.arange(E, dtype=jnp.int32)[:, None],
                               idx.shape)
        z = z.at[jnp.where(w > 0, idx, B), eix].set(y)
        return z[:B]

    C_full = B
    C_fast = go_decode_budget(B, E, topk_hint, bn if executor != "xla" else 1)
    n_fast = E * (-(-C_fast // bn))
    n_full = E * (-(-C_full // bn))
    if C_fast >= C_full:
        contrib = run(C_full)
        fallback = jnp.zeros((), bool)
    else:
        fallback = counts.max() > C_fast
        contrib = jax.lax.cond(fallback, lambda: run(C_full),
                               lambda: run(C_fast))
    plan = GoDecodePlan(counts, C_fast, C_full, n_fast, n_full, fallback)
    return contrib, plan


def moe_ffn_pallas(x: jax.Array, expert_idx: jax.Array, weights: jax.Array,
                   bank: dict, num_experts: int, *, bn: int = 0,
                   interpret: bool | None = None) -> jax.Array:
    """Full MoE FFN through the Pallas path.

    x [T, d]; expert_idx [T, k]; weights [T, k] -> y [T, d].
    Zero-redundancy counterpart of core.moe.group_forward's XLA fallback: no
    masked duplicate member passes, no capacity drops (worst-case padding)."""
    T, d = x.shape
    k = expert_idx.shape[1]
    ef = expert_idx.reshape(-1).astype(jnp.int32)
    wf = weights.reshape(-1)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    y, _, _ = moe_ffn_fused(x, tok, ef, wf, bank, num_experts, T, bn=bn,
                            interpret=interpret)
    return y.astype(x.dtype)

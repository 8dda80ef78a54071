"""Grouped expert GEMM — the TPU realization of the paper's C1 crossbar-level
multiplexing.

PIM mapping: several expert crossbars share one peripheral (ADC) set; MoE
sparsity bounds contention. TPU mapping: all experts of a multiplexing group
stream their selected tokens through ONE execution lane; the shared
"peripheral" is the HBM->VMEM weight-staging buffer + MXU issue slot. Rows
(dispatched token slots) are sorted by expert and PADDED to row-tile
boundaries, so every (row-tile, k, f) grid step stages exactly one expert's
weight tile into VMEM — each expert tile is fetched once per column stripe,
never per token (the dispatch-locality analogue of Algorithm 1).

Kernels:
  gmm(x, w, tile_expert[, tile_valid])     y[i] = x[i] @ w[e(i)]
  gmm_scaled(..., row_scale)               y[i] = (x[i] @ w[e(i)]) * s[i]
                                           (fused combine: per-row weights
                                           applied in-kernel at the fp32
                                           accumulator, out_dtype=fp32)
  gmm_swiglu(x, wg, wi, tile_expert[, tile_valid])
                                           h[i] = silu(x[i] @ wg[e(i)])
                                                  * (x[i] @ wi[e(i)])

Grid: (num_row_tiles, F/bf, K/bk); fp32 VMEM scratch accumulates over k.
Block shapes default to MXU-aligned (128, 512, 128).

Alignment: non-tile-aligned shapes are zero-padded to block multiples — K and
F on both operands (dot products unchanged; extra output columns sliced off),
rows up to the row-tile boundary. `tile_valid` marks row tiles that carry at
least one real dispatched row: invalid tiles (alignment padding, empty expert
runs, the drop lane of the selected-decode path) SKIP the MXU work entirely
via `pl.when`, so the executed FLOPs track the planner's occupied tiles, not
the static worst-case shape. The planner emits constant weight indices across
invalid tail tiles, so the pipeline re-uses the staged VMEM buffer instead of
issuing fresh HBM copies for tiles it will not compute.

`interpret=None` auto-selects from the LOWERING context, not the host default:
inside a mesh (`jax.set_mesh(mesh)` — shard_map bodies, sharded jits) the
kernel lowers for the mesh's devices, which may differ from
`jax.default_backend()` (a forced CPU host mesh on a TPU host, or explicit
device placement). The resolved value
is part of the jit cache key — the public entry points resolve it BEFORE the
jit boundary, so a process that lowers for both platforms (TPU eager + CPU
mesh tests) compiles both variants instead of replaying whichever traced
first. Validated against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec


def lowering_platform() -> str:
    """The platform the next pallas_call actually lowers for: the active
    mesh's devices when inside `jax.set_mesh(mesh)` (shard_map / sharded jit
    tracing happens there), the host default backend otherwise. The abstract
    mesh names its devices by kind only; every TPU kind starts with "TPU"."""
    m = jax.sharding.get_abstract_mesh()
    if not m.empty and m.abstract_device is not None:
        kind = m.abstract_device.device_kind
        return "tpu" if kind.lower().startswith("tpu") else kind.lower()
    return jax.default_backend()


def default_interpret() -> bool:
    """Interpret unless we can actually lower via Mosaic (i.e. for TPU)."""
    return lowering_platform() != "tpu"


def _gspmd_mesh():
    """The ambient mesh while GSPMD partitions the traced program (a mesh
    with automatic axes), or None: no mesh, or a shard_map body."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty or m.are_all_axes_manual else m


def mosaic_call(kernel, *operands):
    """Apply `kernel` (a pallas_call) to `operands` so that it also lowers
    inside a sharded program. Mosaic kernels have no partitioning rule, and
    JAX lowers one only where every mesh axis is manual. Under a GSPMD mesh
    the call therefore runs in a shard_map over that mesh with every operand
    and the result replicated: each device computes the whole call, the same
    numbers as unsharded."""
    m = _gspmd_mesh()
    if m is None:
        return kernel(*operands)
    return jax.shard_map(kernel, mesh=m, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)(*operands)


def _pad_to(a: jax.Array, axis: int, size: int) -> jax.Array:
    if a.shape[axis] == size:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, pads)


def _row_tiles(N: int, bn: int, tile_expert: jax.Array, tile_valid):
    """Validate the (tile_expert, tile_valid) map against ceil(N/bn) row
    tiles. The map must cover every row — a short map means it was built with
    a different bn and auto-extending it would silently zero real rows, so
    fail fast (the planner always emits tile-aligned buffers). A LONGER map
    is fine: the extra rows are zero-padded."""
    ni = -(-N // bn)
    if tile_expert.shape[0] < ni:
        raise ValueError(
            f"tile_expert covers {tile_expert.shape[0]} tiles but x has "
            f"{N} rows at bn={bn} ({ni} tiles) — tile map built with a "
            "different bn, or rows not padded to the tile boundary?")
    ni = tile_expert.shape[0]
    te = tile_expert.astype(jnp.int32)
    tv = (jnp.ones(te.shape, jnp.int32) if tile_valid is None
          else tile_valid.astype(jnp.int32))
    return ni, te, tv


def _gmm_kernel(te_ref, tv_ref, x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tv_ref[i] != 0)
    def _mac():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm_scaled_kernel(te_ref, tv_ref, x_ref, w_ref, s_ref, o_ref, acc_ref,
                       *, nk: int):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tv_ref[i] != 0)
    def _mac():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


# Fused-pair kernel variants: a STRADDLE tile of a fused lane pair carries
# rows of two experts (primary rows first — the planner guarantees at most
# one boundary per tile). `sel_ref` is the per-row primary mask; the primary
# dot masks rows to the primary run, and a second dot over the complement
# streams the secondary expert's weights (w2_ref, indexed by tile_expert2).
# Non-straddle tiles (te2 == te) skip the second dot and the row masking, so
# they cost exactly what the unfused kernels cost.

def _gmm_scaled_fused_kernel(te_ref, te2_ref, tv_ref, x_ref, w_ref, w2_ref,
                             sel_ref, s_ref, o_ref, acc_ref, *, nk: int):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    straddle = te2_ref[i] != te_ref[i]

    @pl.when(tv_ref[i] != 0)
    def _mac():
        x = x_ref[...]
        sel = sel_ref[...].astype(x.dtype)
        x1 = jnp.where(straddle, x * sel, x)
        acc_ref[...] += jnp.dot(x1, w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when((tv_ref[i] != 0) & straddle)
    def _mac2():
        x2 = x_ref[...] * (1.0 - sel_ref[...]).astype(x_ref.dtype)
        acc_ref[...] += jnp.dot(x2, w2_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _gmm_swiglu_fused_kernel(te_ref, te2_ref, tv_ref, x_ref, wg_ref, wi_ref,
                             wg2_ref, wi2_ref, sel_ref, o_ref, accg_ref,
                             acci_ref, *, nk: int):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        acci_ref[...] = jnp.zeros_like(acci_ref)

    straddle = te2_ref[i] != te_ref[i]

    @pl.when(tv_ref[i] != 0)
    def _mac():
        x = x_ref[...]
        sel = sel_ref[...].astype(x.dtype)
        x1 = jnp.where(straddle, x * sel, x)
        accg_ref[...] += jnp.dot(x1, wg_ref[0],
                                 preferred_element_type=jnp.float32)
        acci_ref[...] += jnp.dot(x1, wi_ref[0],
                                 preferred_element_type=jnp.float32)

    @pl.when((tv_ref[i] != 0) & straddle)
    def _mac2():
        x2 = x_ref[...] * (1.0 - sel_ref[...]).astype(x_ref.dtype)
        accg_ref[...] += jnp.dot(x2, wg2_ref[0],
                                 preferred_element_type=jnp.float32)
        acci_ref[...] += jnp.dot(x2, wi2_ref[0],
                                 preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        h = jax.nn.silu(accg_ref[...]) * acci_ref[...]
        o_ref[...] = h.astype(o_ref.dtype)


def _gmm_swiglu_kernel(te_ref, tv_ref, x_ref, wg_ref, wi_ref, o_ref,
                       accg_ref, acci_ref, *, nk: int):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        acci_ref[...] = jnp.zeros_like(acci_ref)

    @pl.when(tv_ref[i] != 0)
    def _mac():
        accg_ref[...] += jnp.dot(x_ref[...], wg_ref[0],
                                 preferred_element_type=jnp.float32)
        acci_ref[...] += jnp.dot(x_ref[...], wi_ref[0],
                                 preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        h = jax.nn.silu(accg_ref[...]) * acci_ref[...]
        o_ref[...] = h.astype(o_ref.dtype)


def gmm(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
        tile_valid: jax.Array | None = None, *, bn: int = 128, bk: int = 512,
        bf: int = 128, interpret: bool | None = None,
        out_dtype=None) -> jax.Array:
    """x [N, K] (rows tile-aligned by expert), w [E, K, F],
    tile_expert [n_tiles] int32, tile_valid [n_tiles] optional -> y [N, F]."""
    if interpret is None:
        interpret = default_interpret()
    return _gmm(x, w, tile_expert, tile_valid, bn=bn, bk=bk, bf=bf,
                interpret=interpret, out_dtype=out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "bf", "interpret", "out_dtype"))
def _gmm(x, w, tile_expert, tile_valid, *, bn, bk, bf, interpret, out_dtype):
    N, K = x.shape
    E, _, F = w.shape
    bk, bf = min(bk, K), min(bf, F)
    ni, te, tv = _row_tiles(N, bn, tile_expert, tile_valid)
    Kp, Fp = -(-K // bk) * bk, -(-F // bf) * bf
    xp = _pad_to(_pad_to(x, 0, ni * bn), 1, Kp)
    wp = _pad_to(_pad_to(w, 1, Kp), 2, Fp)
    nk, nf = Kp // bk, Fp // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ni, nf, nk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k, te, tv: (i, k)),
            pl.BlockSpec((1, bk, bf), lambda i, j, k, te, tv: (te[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j, k, te, tv: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, bf), jnp.float32)],
    )
    y = mosaic_call(pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ni * bn, Fp), out_dtype or x.dtype),
        interpret=interpret,
    ), te, tv, xp, wp)
    return y[:N, :F]


def gmm_scaled(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
               tile_valid: jax.Array | None, row_scale: jax.Array, *,
               tile_expert2: jax.Array | None = None,
               row_sel: jax.Array | None = None,
               bn: int = 128, bk: int = 512, bf: int = 128,
               interpret: bool | None = None,
               out_dtype=jnp.float32) -> jax.Array:
    """Fused-combine grouped GEMM: y[i] = (x[i] @ w[e(i)]) * row_scale[i].

    The per-row combine weight is applied against the fp32 accumulator in the
    kernel's epilogue, so the caller can scatter-add the rows straight into the
    token buffer — no separate gather + fp32 multiply pass. row_scale [N, 1].

    With `tile_expert2`/`row_sel` (fused lane pairs), a straddle tile's rows
    split between two experts: rows where row_sel==1 hit tile_expert's
    weights, the complement hits tile_expert2's."""
    if interpret is None:
        interpret = default_interpret()
    if tile_expert2 is None:
        return _gmm_scaled(x, w, tile_expert, tile_valid, row_scale, bn=bn,
                           bk=bk, bf=bf, interpret=interpret,
                           out_dtype=out_dtype)
    return _gmm_scaled_fused(x, w, tile_expert, tile_expert2, tile_valid,
                             row_scale, row_sel, bn=bn, bk=bk, bf=bf,
                             interpret=interpret, out_dtype=out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "bf", "interpret", "out_dtype"))
def _gmm_scaled(x, w, tile_expert, tile_valid, row_scale, *, bn, bk, bf,
                interpret, out_dtype):
    N, K = x.shape
    E, _, F = w.shape
    bk, bf = min(bk, K), min(bf, F)
    ni, te, tv = _row_tiles(N, bn, tile_expert, tile_valid)
    Kp, Fp = -(-K // bk) * bk, -(-F // bf) * bf
    xp = _pad_to(_pad_to(x, 0, ni * bn), 1, Kp)
    wp = _pad_to(_pad_to(w, 1, Kp), 2, Fp)
    sp = _pad_to(row_scale.astype(jnp.float32), 0, ni * bn)
    nk, nf = Kp // bk, Fp // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ni, nf, nk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k, te, tv: (i, k)),
            pl.BlockSpec((1, bk, bf), lambda i, j, k, te, tv: (te[i], k, j)),
            pl.BlockSpec((bn, 1), lambda i, j, k, te, tv: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j, k, te, tv: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, bf), jnp.float32)],
    )
    y = mosaic_call(pl.pallas_call(
        functools.partial(_gmm_scaled_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ni * bn, Fp), out_dtype),
        interpret=interpret,
    ), te, tv, xp, wp, sp)
    return y[:N, :F]


def gmm_swiglu(x: jax.Array, wg: jax.Array, wi: jax.Array,
               tile_expert: jax.Array, tile_valid: jax.Array | None = None, *,
               tile_expert2: jax.Array | None = None,
               row_sel: jax.Array | None = None,
               bn: int = 128, bk: int = 512, bf: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """Fused per-expert SwiGLU up-projection: silu(x@wg[e]) * (x@wi[e]).
    One x-tile staging feeds BOTH weight streams (multiplexed operand reuse).
    `tile_expert2`/`row_sel` resolve fused-pair straddle tiles per row."""
    if interpret is None:
        interpret = default_interpret()
    if tile_expert2 is None:
        return _gmm_swiglu(x, wg, wi, tile_expert, tile_valid, bn=bn, bk=bk,
                           bf=bf, interpret=interpret)
    return _gmm_swiglu_fused(x, wg, wi, tile_expert, tile_expert2, tile_valid,
                             row_sel, bn=bn, bk=bk, bf=bf, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "bf", "interpret", "out_dtype"))
def _gmm_scaled_fused(x, w, tile_expert, tile_expert2, tile_valid, row_scale,
                      row_sel, *, bn, bk, bf, interpret, out_dtype):
    N, K = x.shape
    E, _, F = w.shape
    bk, bf = min(bk, K), min(bf, F)
    ni, te, tv = _row_tiles(N, bn, tile_expert, tile_valid)
    te2 = tile_expert2.astype(jnp.int32)
    Kp, Fp = -(-K // bk) * bk, -(-F // bf) * bf
    xp = _pad_to(_pad_to(x, 0, ni * bn), 1, Kp)
    wp = _pad_to(_pad_to(w, 1, Kp), 2, Fp)
    sp = _pad_to(row_scale.astype(jnp.float32), 0, ni * bn)
    selp = _pad_to(row_sel.astype(jnp.float32), 0, ni * bn)
    nk, nf = Kp // bk, Fp // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ni, nf, nk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k, te, te2, tv: (i, k)),
            pl.BlockSpec((1, bk, bf),
                         lambda i, j, k, te, te2, tv: (te[i], k, j)),
            pl.BlockSpec((1, bk, bf),
                         lambda i, j, k, te, te2, tv: (te2[i], k, j)),
            pl.BlockSpec((bn, 1), lambda i, j, k, te, te2, tv: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j, k, te, te2, tv: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j, k, te, te2, tv: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, bf), jnp.float32)],
    )
    y = mosaic_call(pl.pallas_call(
        functools.partial(_gmm_scaled_fused_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ni * bn, Fp), out_dtype),
        interpret=interpret,
    ), te, te2, tv, xp, wp, wp, selp, sp)
    return y[:N, :F]


@functools.partial(jax.jit, static_argnames=("bn", "bk", "bf", "interpret"))
def _gmm_swiglu_fused(x, wg, wi, tile_expert, tile_expert2, tile_valid,
                      row_sel, *, bn, bk, bf, interpret):
    N, K = x.shape
    E, _, F = wg.shape
    bk, bf = min(bk, K), min(bf, F)
    ni, te, tv = _row_tiles(N, bn, tile_expert, tile_valid)
    te2 = tile_expert2.astype(jnp.int32)
    Kp, Fp = -(-K // bk) * bk, -(-F // bf) * bf
    xp = _pad_to(_pad_to(x, 0, ni * bn), 1, Kp)
    wgp = _pad_to(_pad_to(wg, 1, Kp), 2, Fp)
    wip = _pad_to(_pad_to(wi, 1, Kp), 2, Fp)
    selp = _pad_to(row_sel.astype(jnp.float32), 0, ni * bn)
    nk, nf = Kp // bk, Fp // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ni, nf, nk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k, te, te2, tv: (i, k)),
            pl.BlockSpec((1, bk, bf),
                         lambda i, j, k, te, te2, tv: (te[i], k, j)),
            pl.BlockSpec((1, bk, bf),
                         lambda i, j, k, te, te2, tv: (te[i], k, j)),
            pl.BlockSpec((1, bk, bf),
                         lambda i, j, k, te, te2, tv: (te2[i], k, j)),
            pl.BlockSpec((1, bk, bf),
                         lambda i, j, k, te, te2, tv: (te2[i], k, j)),
            pl.BlockSpec((bn, 1), lambda i, j, k, te, te2, tv: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j, k, te, te2, tv: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, bf), jnp.float32),
                        pltpu.VMEM((bn, bf), jnp.float32)],
    )
    y = mosaic_call(pl.pallas_call(
        functools.partial(_gmm_swiglu_fused_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ni * bn, Fp), x.dtype),
        interpret=interpret,
    ), te, te2, tv, xp, wgp, wip, wgp, wip, selp)
    return y[:N, :F]


@functools.partial(jax.jit, static_argnames=("bn", "bk", "bf", "interpret"))
def _gmm_swiglu(x, wg, wi, tile_expert, tile_valid, *, bn, bk, bf, interpret):
    N, K = x.shape
    E, _, F = wg.shape
    bk, bf = min(bk, K), min(bf, F)
    ni, te, tv = _row_tiles(N, bn, tile_expert, tile_valid)
    Kp, Fp = -(-K // bk) * bk, -(-F // bf) * bf
    xp = _pad_to(_pad_to(x, 0, ni * bn), 1, Kp)
    wgp = _pad_to(_pad_to(wg, 1, Kp), 2, Fp)
    wip = _pad_to(_pad_to(wi, 1, Kp), 2, Fp)
    nk, nf = Kp // bk, Fp // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ni, nf, nk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k, te, tv: (i, k)),
            pl.BlockSpec((1, bk, bf), lambda i, j, k, te, tv: (te[i], k, j)),
            pl.BlockSpec((1, bk, bf), lambda i, j, k, te, tv: (te[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j, k, te, tv: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, bf), jnp.float32),
                        pltpu.VMEM((bn, bf), jnp.float32)],
    )
    y = mosaic_call(pl.pallas_call(
        functools.partial(_gmm_swiglu_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ni * bn, Fp), x.dtype),
        interpret=interpret,
    ), te, tv, xp, wgp, wip)
    return y[:N, :F]

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

Layer operand: each kernel also takes the STACKED banks of a layer scan,
[L, E, K, F], with `layer` as one more scalar-prefetch operand; weight
blocks are read in place at (layer, e(i), k, j), the layer axis squeezed,
so no per-layer copy of the bank is made (a Mosaic call needs each operand
as a buffer of its own: a sliced layer would be copied whole, every layer
of every program).

Block rule: for each bank dimension the K and F blocks divide it — the
default where it divides, else the whole dimension (a full-extent block,
which Mosaic accepts: deepseek's d_expert of 1408 is one K block, llama's
688 one F block). Where a whole-dimension block would not fit VMEM
(`_FULL_BLOCK_BYTES`), the default stays and the bank is zero-padded to
block multiples; a stacked bank is then sliced to its layer first. Under a
GSPMD mesh a stacked bank is sliced too: `mosaic_call` replicates every
operand there, and a stacked one would gather all L layers onto every
device.

Alignment: rows are zero-padded up to the row-tile boundary, and a padded
bank's K and F on both operands (dot products unchanged; extra output
columns sliced off). `tile_valid` marks row tiles that carry at
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


def layer_slice(w: jax.Array, layer) -> jax.Array:
    """Layer `layer` of a stacked [L, ...] bank; the bank itself when
    `layer` is None."""
    if layer is None:
        return w
    return jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)


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


# Where the default block does not divide a bank dimension, the kernel takes
# the whole dimension as one block, provided one weight block stays within
# this size: the fused SwiGLU kernel stages four weight streams,
# double-buffered, and they must stay well inside the 16 MiB of scoped VMEM.
_FULL_BLOCK_BYTES = 1 << 20


def _bank_layout(banks: tuple, layer, bk: int, bf: int):
    """The (K, F) blocks for weight banks of one shape, and the banks as the
    kernel reads them: (banks, layer, bk, bf).

    Blocks divide the bank: the default where it divides, else the whole
    dimension. Where that block would not fit VMEM, the default stays and
    the bank is zero-padded to block multiples. A stacked bank ([L, E, K, F]
    with `layer`) is read in place, except where it needs that padding, or
    under a GSPMD mesh, where `mosaic_call` replicates every operand and
    would gather all L layers onto every device: there it is sliced to the
    layer's [E, K, F] first."""
    if (layer is None) != (banks[0].ndim == 3):
        raise ValueError(f"bank of shape {banks[0].shape} with layer={layer}:"
                         " a stacked [L, E, K, F] bank takes a layer index, "
                         "an [E, K, F] bank none")
    K, F = banks[0].shape[-2:]
    fk, ff = (bk if K % bk == 0 else K), (bf if F % bf == 0 else F)
    if fk * ff * banks[0].dtype.itemsize <= _FULL_BLOCK_BYTES:
        bk, bf = fk, ff
    else:
        bk, bf = min(bk, K), min(bf, F)
    if layer is not None and (K % bk or F % bf or _gspmd_mesh() is not None):
        banks = tuple(layer_slice(w, layer) for w in banks)
        layer = None
    if layer is not None:
        layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return banks, layer, bk, bf


def _launch(kernel, x, banks, w_maps, te, te2, tv, layer, rows, *, n_acc,
            bn, bk, bf, interpret, out_dtype):
    """One grouped-GEMM pallas_call over (row tile i, F block j, K block k).

    Scalar prefetch: [layer,] te, [te2,] tv. Weight stream s reads
    `banks[s]` by tile map `w_maps[s]` (0: te, 1: te2); `rows` are per-row
    [N, 1] operands. With `layer`, the banks are the stacked [L, E, K, F]
    and each block index leads with the layer, squeezed, so the kernel
    bodies see the same (1, bk, bf) weight refs either way."""
    N, K = x.shape
    F = banks[0].shape[-1]
    ni = te.shape[0]
    Kp, Fp = -(-K // bk) * bk, -(-F // bf) * bf
    xp = _pad_to(_pad_to(x, 0, ni * bn), 1, Kp)
    banks = [_pad_to(_pad_to(w, w.ndim - 2, Kp), w.ndim - 1, Fp)
             for w in banks]
    rows = [_pad_to(r.astype(jnp.float32), 0, ni * bn) for r in rows]
    scalars = (te,) + (() if te2 is None else (te2,)) + (tv,)
    nk = Kp // bk
    if layer is None:
        w_block = (1, bk, bf)
        body = functools.partial(kernel, nk=nk)

        def w_index(m):
            return lambda i, j, k, *s: (s[m][i], k, j)
    else:
        # the layer leads the scalar prefetch; only the index maps read it
        scalars = (layer,) + scalars
        w_block = (pl.squeezed, 1, bk, bf)

        def body(lyr_ref, *refs):
            return kernel(*refs, nk=nk)

        def w_index(m):
            return lambda i, j, k, lyr, *s: (lyr[0], s[m][i], k, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(ni, Fp // bf, nk),
        in_specs=[pl.BlockSpec((bn, bk), lambda i, j, k, *s: (i, k))]
        + [pl.BlockSpec(w_block, w_index(m)) for m in w_maps]
        + [pl.BlockSpec((bn, 1), lambda i, j, k, *s: (i, 0)) for _ in rows],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j, k, *s: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, bf), jnp.float32)] * n_acc,
    )
    y = mosaic_call(pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ni * bn, Fp), out_dtype),
        interpret=interpret,
    ), *scalars, xp, *banks, *rows)
    return y[:N, :F]


def gmm(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
        tile_valid: jax.Array | None = None, *, layer=None, bn: int = 128,
        bk: int = 512, bf: int = 128, interpret: bool | None = None,
        out_dtype=None) -> jax.Array:
    """x [N, K] (rows tile-aligned by expert), w [E, K, F] (or the stacked
    [L, E, K, F] with `layer`), tile_expert [n_tiles] int32, tile_valid
    [n_tiles] optional -> y [N, F]."""
    if interpret is None:
        interpret = default_interpret()
    (w,), layer, bk, bf = _bank_layout((w,), layer, bk, bf)
    return _gmm(x, w, tile_expert, tile_valid, layer, bn=bn, bk=bk, bf=bf,
                interpret=interpret, out_dtype=out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "bf", "interpret", "out_dtype"))
def _gmm(x, w, tile_expert, tile_valid, layer, *, bn, bk, bf, interpret,
         out_dtype):
    _, te, tv = _row_tiles(x.shape[0], bn, tile_expert, tile_valid)
    return _launch(_gmm_kernel, x, (w,), (0,), te, None, tv, layer, (),
                   n_acc=1, bn=bn, bk=bk, bf=bf, interpret=interpret,
                   out_dtype=out_dtype or x.dtype)


def gmm_scaled(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
               tile_valid: jax.Array | None, row_scale: jax.Array, *,
               tile_expert2: jax.Array | None = None,
               row_sel: jax.Array | None = None, layer=None,
               bn: int = 128, bk: int = 512, bf: int = 128,
               interpret: bool | None = None,
               out_dtype=jnp.float32) -> jax.Array:
    """Fused-combine grouped GEMM: y[i] = (x[i] @ w[e(i)]) * row_scale[i].

    The per-row combine weight is applied against the fp32 accumulator in the
    kernel's epilogue, so the caller can scatter-add the rows straight into the
    token buffer — no separate gather + fp32 multiply pass. row_scale [N, 1].

    With `tile_expert2`/`row_sel` (fused lane pairs), a straddle tile's rows
    split between two experts: rows where row_sel==1 hit tile_expert's
    weights, the complement hits tile_expert2's. With `layer`, w is the
    stacked [L, E, K, F] bank."""
    if interpret is None:
        interpret = default_interpret()
    (w,), layer, bk, bf = _bank_layout((w,), layer, bk, bf)
    if tile_expert2 is None:
        return _gmm_scaled(x, w, tile_expert, tile_valid, row_scale, layer,
                           bn=bn, bk=bk, bf=bf, interpret=interpret,
                           out_dtype=out_dtype)
    return _gmm_scaled_fused(x, w, tile_expert, tile_expert2, tile_valid,
                             row_scale, row_sel, layer, bn=bn, bk=bk, bf=bf,
                             interpret=interpret, out_dtype=out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "bf", "interpret", "out_dtype"))
def _gmm_scaled(x, w, tile_expert, tile_valid, row_scale, layer, *, bn, bk,
                bf, interpret, out_dtype):
    _, te, tv = _row_tiles(x.shape[0], bn, tile_expert, tile_valid)
    return _launch(_gmm_scaled_kernel, x, (w,), (0,), te, None, tv, layer,
                   (row_scale,), n_acc=1, bn=bn, bk=bk, bf=bf,
                   interpret=interpret, out_dtype=out_dtype)


def gmm_swiglu(x: jax.Array, wg: jax.Array, wi: jax.Array,
               tile_expert: jax.Array, tile_valid: jax.Array | None = None, *,
               tile_expert2: jax.Array | None = None,
               row_sel: jax.Array | None = None, layer=None,
               bn: int = 128, bk: int = 512, bf: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """Fused per-expert SwiGLU up-projection: silu(x@wg[e]) * (x@wi[e]).
    One x-tile staging feeds BOTH weight streams (multiplexed operand reuse).
    `tile_expert2`/`row_sel` resolve fused-pair straddle tiles per row. With
    `layer`, wg and wi are the stacked [L, E, K, F] banks."""
    if interpret is None:
        interpret = default_interpret()
    (wg, wi), layer, bk, bf = _bank_layout((wg, wi), layer, bk, bf)
    if tile_expert2 is None:
        return _gmm_swiglu(x, wg, wi, tile_expert, tile_valid, layer, bn=bn,
                           bk=bk, bf=bf, interpret=interpret)
    return _gmm_swiglu_fused(x, wg, wi, tile_expert, tile_expert2, tile_valid,
                             row_sel, layer, bn=bn, bk=bk, bf=bf,
                             interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "bf", "interpret", "out_dtype"))
def _gmm_scaled_fused(x, w, tile_expert, tile_expert2, tile_valid, row_scale,
                      row_sel, layer, *, bn, bk, bf, interpret, out_dtype):
    _, te, tv = _row_tiles(x.shape[0], bn, tile_expert, tile_valid)
    return _launch(_gmm_scaled_fused_kernel, x, (w, w), (0, 1), te,
                   tile_expert2.astype(jnp.int32), tv, layer,
                   (row_sel, row_scale), n_acc=1, bn=bn, bk=bk, bf=bf,
                   interpret=interpret, out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "bf", "interpret"))
def _gmm_swiglu_fused(x, wg, wi, tile_expert, tile_expert2, tile_valid,
                      row_sel, layer, *, bn, bk, bf, interpret):
    _, te, tv = _row_tiles(x.shape[0], bn, tile_expert, tile_valid)
    return _launch(_gmm_swiglu_fused_kernel, x, (wg, wi, wg, wi),
                   (0, 0, 1, 1), te, tile_expert2.astype(jnp.int32), tv,
                   layer, (row_sel,), n_acc=2, bn=bn, bk=bk, bf=bf,
                   interpret=interpret, out_dtype=x.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "bf", "interpret"))
def _gmm_swiglu(x, wg, wi, tile_expert, tile_valid, layer, *, bn, bk, bf,
                interpret):
    _, te, tv = _row_tiles(x.shape[0], bn, tile_expert, tile_valid)
    return _launch(_gmm_swiglu_kernel, x, (wg, wi), (0, 0), te, None, tv,
                   layer, (), n_acc=2, bn=bn, bk=bk, bf=bf,
                   interpret=interpret, out_dtype=x.dtype)

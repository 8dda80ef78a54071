"""Compile-only guards: the main path's Pallas kernels at real model widths,
compiled by the TPU compiler for a described (not attached) v5e chip.

Interpret mode cannot see what Mosaic refuses — unaligned block shapes,
in-kernel relayouts, selects it cannot legalize, VMEM overshoot. These
tests hand each kernel the shapes of granite-moe-3b-a800m (E=40, d=1536,
F=512; Hq=24, Hkv=8, hd=64) and llama_moe_4_16 (E=16, d=4096, F=688;
Hq=Hkv=32, hd=128) and require a compiled program that holds a Mosaic
kernel (`tpu_custom_call`). Nothing runs; the topology is described inside
a fixture, so a host that cannot describe it skips these tests alone.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attn as PA
from repro.kernels.moe_gmm import gmm_scaled, gmm_swiglu

WIDTHS = {  # E, d_model, d_expert, Hq, Hkv, head_dim
    "granite": (40, 1536, 512, 24, 8, 64),
    "llama": (16, 4096, 688, 32, 32, 128),
}
BN = 128            # grouped-GEMM row tile on the chip
PS, PAGES, SLOTS, CHUNK = 16, 24, 4, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_grouped_gemm_compiles(one_chip, arch, fused):
    """SwiGLU up-projection + scaled down-projection, one row tile per
    expert; `fused` runs the lane-pair variants (straddle tiles)."""
    E, d, F = WIDTHS[arch][:3]
    N = E * BN

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def ffn(x, wg, wi, wo, te, te2, tv, scale, sel):
        kw = dict(tile_expert2=te2, row_sel=sel) if fused else {}
        h = gmm_swiglu(x, wg, wi, te, tv, bn=BN, interpret=False, **kw)
        return gmm_scaled(h, wo, te, tv, scale, bn=BN, interpret=False, **kw)

    _compile(ffn, S((N, d), jnp.bfloat16), S((E, d, F), jnp.bfloat16),
             S((E, d, F), jnp.bfloat16), S((E, F, d), jnp.bfloat16),
             S((E,), jnp.int32), S((E,), jnp.int32), S((E,), jnp.int32),
             S((N, 1), jnp.float32), S((N, 1), jnp.int32))


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("arch,quant", [("granite", False), ("llama", True)],
                         ids=["granite-bf16", "llama-int8"])
def test_paged_attention_compiles(one_chip, arch, quant, kind):
    """The paged-attention kernels over a pool of bf16 pages, or of int8
    pages with per-(page, kv head) f32 scales."""
    Hq, Hkv, hd = WIDTHS[arch][3:]
    NP = 1 + SLOTS * PAGES

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    page_dt = jnp.int8 if quant else jnp.bfloat16
    pages = [S((NP, PS, Hkv, hd), page_dt)] * 2
    scales = [S((NP, Hkv), jnp.float32)] * 2 if quant else []
    bt = S((SLOTS, PAGES), jnp.int32)

    def attn(q, kp, vp, bt, pos, *sc):
        ks, vs = sc if sc else (None, None)
        if kind == "decode":
            return PA.paged_attn_decode(q, kp, vp, bt, pos, window=0,
                                        k_scales=ks, v_scales=vs,
                                        interpret=False)
        return PA.paged_attn_chunk(q, kp, vp, bt, pos[0], pos[0] + CHUNK,
                                   window=0, k_scales=ks, v_scales=vs,
                                   interpret=False)

    q = (S((SLOTS, Hq, hd), jnp.bfloat16) if kind == "decode"
         else S((SLOTS, CHUNK, Hq, hd), jnp.bfloat16))
    _compile(attn, q, *pages, bt, S((SLOTS,), jnp.int32), *scales)

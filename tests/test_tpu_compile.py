"""Compile-only guards: the main path's Pallas kernels at real model widths,
compiled by the TPU compiler for a described (not attached) v5e chip.

Interpret mode cannot see what Mosaic refuses — unaligned block shapes,
in-kernel relayouts, selects it cannot legalize, VMEM overshoot. These
tests hand each kernel the shapes of granite-moe-3b-a800m (E=40, d=1536,
F=512; Hq=24, Hkv=8, hd=64), llama_moe_4_16 (E=16, d=4096, F=688;
Hq=Hkv=32, hd=128) and deepseek-moe-16b (E=64, d=2048, F=1408: the
down-projection's K of 1408 is one full-extent block) and require a
compiled program that holds a Mosaic kernel (`tpu_custom_call`). The
decode step of a two-layer model is compiled whole once per MoE width, to
see that its grouped GEMM reads the stacked expert banks in place. The
latent-attention decode kernel and the decode tick of moonlight-16b-a3b's
7-layer stage compile at published widths (16 heads, latent 512 + rotary
64, rows stored 640 wide), the tick reading the stacked latent pool in
place; granite's and deepseek-7l's ticks, and deepseek-moe-16b's decode
tick and expert-parallel prefill over the four chips of a v5e:2x2, keep
the instruction counts they compiled to before latent attention and the
sigmoid router were added. Nothing runs; the topology
is described inside a fixture, so a host that cannot describe it skips
these tests alone.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import paged_attn as PA
from repro.kernels.moe_gmm import gmm_scaled, gmm_swiglu

WIDTHS = {  # E, d_model, d_expert, Hq, Hkv, head_dim
    "granite": (40, 1536, 512, 24, 8, 64),
    "llama": (16, 4096, 688, 32, 32, 128),
    "deepseek": (64, 2048, 1408, 16, 16, 128),
}
BN = 128            # grouped-GEMM row tile on the chip
PS, PAGES, SLOTS, CHUNK = 16, 24, 4, 128


@pytest.fixture(scope="module")
def v5e_2x2():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _S(one_chip, shape, dt):
    return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)


def _tick_hlo(one_chip, cfg, slots, max_tokens, num_pages):
    """The engine's decode tick for `cfg` (Pallas paths), compiled."""
    from repro.models import model as M
    from repro.serving import engine as ENG

    def shaped(tree):
        return jax.tree.map(lambda a: _S(one_chip, a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(
        lambda: M.model_init(jax.random.PRNGKey(0), cfg)))
    pool = shaped(jax.eval_shape(lambda: M.init_decode_state(
        cfg, slots, max_tokens, per_slot_t=True, paged=(num_pages, PS))))
    return ENG._decode_step.lower(
        params, pool, _S(one_chip, (slots,), jnp.int32),
        _S(one_chip, (slots,), jnp.bool_), cfg).compile().as_text()


def _kernel_paths(monkeypatch):
    from repro.kernels import moe_gmm, ops
    for mod in (moe_gmm, ops, PA):
        monkeypatch.setattr(mod, "lowering_platform", lambda: "tpu")


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("fused,layers", [(False, 0), (True, 0), (False, 3),
                                          (True, 3)],
                         ids=["plain", "fused", "plain-stacked",
                              "fused-stacked"])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_grouped_gemm_compiles(one_chip, arch, fused, layers):
    """SwiGLU up-projection + scaled down-projection, one row tile per
    expert; `fused` runs the lane-pair variants (straddle tiles); `stacked`
    hands the kernels [3, E, K, F] banks and a layer to read in place."""
    E, d, F = WIDTHS[arch][:3]
    N = E * BN
    L = (layers,) if layers else ()

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def ffn(x, wg, wi, wo, te, te2, tv, scale, sel, layer):
        kw = dict(tile_expert2=te2, row_sel=sel) if fused else {}
        kw.update(layer=layer if layers else None, bn=BN, interpret=False)
        h = gmm_swiglu(x, wg, wi, te, tv, **kw)
        return gmm_scaled(h, wo, te, tv, scale, **kw)

    text = _compile(
        ffn, S((N, d), jnp.bfloat16), S(L + (E, d, F), jnp.bfloat16),
        S(L + (E, d, F), jnp.bfloat16), S(L + (E, F, d), jnp.bfloat16),
        S((E,), jnp.int32), S((E,), jnp.int32), S((E,), jnp.int32),
        S((N, 1), jnp.float32), S((N, 1), jnp.int32), S((), jnp.int32))
    if layers:
        assert " pad(" not in text and "dynamic-slice" not in text


def _bank_ops(hlo: str, E: int, d: int, F: int) -> list:
    """Instructions of an optimised HLO whose result is one layer's expert
    bank, [E, d, F] or [E, F, d], as it is or padded to the default blocks
    (K to a multiple of 512, F to one of 128)."""
    def up(n, m):
        return -(-n // m) * m
    shapes = {(E, d, F), (E, F, d), (E, up(d, 512), up(F, 128)),
              (E, up(F, 512), up(d, 128))}
    found = []
    for m in re.finditer(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]", hlo,
                         re.M):
        if tuple(int(n) for n in m.group(1).split(",")) in shapes:
            found.append(m.group(0).strip())
    return found


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "deepseek-moe-16b"])
def test_decode_step_reads_expert_banks_in_place(one_chip, monkeypatch,
                                                 arch):
    """The engine's decode tick at real widths, two layers, token choice on
    the Pallas path: no instruction of its optimised HLO makes one layer's
    [E, K, F] expert bank or a padded one, and both grouped-GEMM kernels
    take the stacked [L, E, K, F] banks as operands. When the layer scan
    still sliced the banks, this program made 6 such instructions at
    granite widths (per bank one `dynamic-slice_bitcast_fusion` and the
    bitcast at its root) and 7 at deepseek's (the same, and the `pad` of
    `wo` to K = 1536)."""
    from repro.kernels import moe_gmm, ops
    from repro.models import model as M
    from repro.serving import engine as ENG
    for mod in (moe_gmm, ops, PA):
        monkeypatch.setattr(mod, "lowering_platform", lambda: "tpu")
    cfg = get_config(arch)
    cfg = cfg.with_overrides(num_layers=2, paged_attn="kernel",
                             moe=dataclasses.replace(cfg.moe, group_size=1,
                                                     backend="pallas"))

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: M.model_init(jax.random.PRNGKey(0), cfg)))
    pool = shaped(jax.eval_shape(lambda: M.init_decode_state(
        cfg, SLOTS, PAGES * PS, per_slot_t=True,
        paged=(1 + SLOTS * PAGES, PS))))
    hlo = ENG._decode_step.lower(
        params, pool, jax.ShapeDtypeStruct((SLOTS,), jnp.int32,
                                           sharding=one_chip),
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one_chip),
        cfg).compile().as_text()
    E, d, F = cfg.moe.num_experts, cfg.d_model, cfg.moe.d_expert
    assert _bank_ops(hlo, E, d, F) == []
    gmm_calls = [ln for ln in hlo.splitlines()
                 if re.match(r"\s*(ROOT )?%_gmm", ln)
                 and "tpu_custom_call" in ln]
    assert len(gmm_calls) == 2
    stacked = (f"bf16[2,{E},{d},{F}]", f"bf16[2,{E},{F},{d}]")
    assert all(any(s in ln for s in stacked) for ln in gmm_calls)


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


def test_latent_decode_kernel_compiles(one_chip):
    """The latent-attention decode kernel over moonlight's stacked pool of
    7 layers: 16 heads, rows of 640 (latent 512, rotary key 64, zeros)."""
    L, H, W, P = 7, 16, 640, 512
    NP = 1 + SLOTS * P

    def attn(q, pages, layer, bt, t):
        return PA.mla_paged_decode(q, pages, layer, bt, t, rank=512,
                                   scale=192 ** -0.5, interpret=False)

    text = _compile(attn, _S(one_chip, (SLOTS, H, W), jnp.bfloat16),
                    _S(one_chip, (L, NP, PS, W), jnp.bfloat16),
                    _S(one_chip, (), jnp.int32),
                    _S(one_chip, (SLOTS, P), jnp.int32),
                    _S(one_chip, (SLOTS,), jnp.int32))
    # the pool reaches the kernel as it is: no copy into another layout
    assert f"bf16[{L},{NP},{PS},{W}]" in text
    assert not re.search(rf"= bf16\[{L},{NP},{PS},{W}\]\S* copy\(", text)


def test_latent_decode_tick_reads_the_pool_in_place(one_chip, monkeypatch):
    """moonlight-16b-a3b's 7-layer stage (the dense layer 0 and 6 MoE
    layers), its decode tick at published widths over 32 slots of 8192
    tokens: every layer's kernel takes the stacked [L, NP, ps, 640] pool
    and the layer index, and no instruction makes one layer's pool."""
    _kernel_paths(monkeypatch)
    cfg = get_config("moonlight-16b-a3b")
    cfg = cfg.with_overrides(num_layers=7, tie_embeddings=True,
                             paged_attn="kernel",
                             moe=dataclasses.replace(cfg.moe, group_size=1,
                                                     backend="pallas"))
    NP, W = 1 + 32 * 512, cfg.latent_row_width()
    hlo = _tick_hlo(one_chip, cfg, 32, 8192, NP)
    calls = [ln for ln in hlo.splitlines()
             if re.match(r"\s*(ROOT )?%_mla_paged_decode", ln)]
    assert len(calls) == 2               # the dense layer, the MoE scan
    assert all(f"bf16[7,{NP},{PS},{W}]" in ln for ln in calls)
    assert f"[{NP},{PS},{W}]" not in hlo


# instructions of the optimised decode tick before latent attention and
# the sigmoid router were added, at each cell's engine (16 slots of 4096
# tokens over 2049 pages) and its layers
TICK_INSTRUCTIONS = {"granite-moe-3b-a800m": (32, 1285),
                     "deepseek-moe-16b": (7, 1373)}


@pytest.mark.parametrize("arch", sorted(TICK_INSTRUCTIONS))
def test_existing_ticks_keep_their_instructions(one_chip, monkeypatch, arch):
    _kernel_paths(monkeypatch)
    layers, count = TICK_INSTRUCTIONS[arch]
    cfg = get_config(arch)
    cfg = cfg.with_overrides(num_layers=layers, paged_attn="kernel",
                             moe=dataclasses.replace(cfg.moe, group_size=1,
                                                     backend="pallas"))
    hlo = _tick_hlo(one_chip, cfg, 16, 4096, 2049)
    assert len(re.findall(r"^\s*(?:ROOT )?%\S+ = \S+ [\w-]+\(", hlo,
                          re.M)) == count


# the same, for deepseek-moe-16b at its 28 layers over the four chips, laid
# out as its four-chip cell lays it out (16 slots of 4096 tokens over 2049
# pages, experts over "model"): the decode tick, and the one-shot prefill of
# 512 tokens, whose MoE runs expert-parallel (core/moe.py moe_forward_ep)
EP4_INSTRUCTIONS = {"decode": 1736, "prefill": 1759}


@pytest.mark.parametrize("program", sorted(EP4_INSTRUCTIONS))
def test_four_chip_programs_keep_their_instructions(v5e_2x2, monkeypatch,
                                                    program):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import moe as MOE
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import param_shardings, serve_state_shardings
    from repro.models import model as M
    from repro.serving import engine as ENG
    _kernel_paths(monkeypatch)
    cfg = get_config("deepseek-moe-16b")
    cfg = cfg.with_overrides(
        num_layers=28, tie_embeddings=True, paged_attn="kernel",
        moe=dataclasses.replace(cfg.moe, group_size=1, backend="pallas",
                                capacity_factor=64 / 6))
    slots, max_tokens, num_pages = 16, 4096, 2049
    mesh = make_mesh((1, 4), ("data", "model"), devices=v5e_2x2.devices[:4])
    one = NamedSharding(mesh, P())

    def shaped(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    params = jax.eval_shape(lambda: M.model_init(jax.random.PRNGKey(0), cfg))
    params = shaped(params, param_shardings(params, cfg, mesh, mode="serve"))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    with jax.set_mesh(mesh):
        if program == "decode":
            pool = jax.eval_shape(lambda: M.init_decode_state(
                cfg, slots, max_tokens, per_slot_t=True,
                paged=(num_pages, PS)))
            pool = shaped(pool, serve_state_shardings(
                cfg, mesh, slots, max_tokens, paged=(num_pages, PS)))
            lowered = ENG._decode_step.lower(
                params, pool, i32(slots),
                jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one), cfg)
        else:
            assert MOE.ep_available(cfg.moe)
            lowered = ENG._jit_prefill.lower(params, i32(1, 512), cfg, {},
                                             max_tokens, i32())
        hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    assert len(re.findall(r"^\s*(?:ROOT )?%\S+ = \S+ [\w-]+\(", hlo,
                          re.M)) == EP4_INSTRUCTIONS[program]

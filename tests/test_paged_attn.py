"""Paged-attention kernel parity lane: the Pallas block-table kernel
(kernels/paged_attn.py) against the dense-gather paged path and the dense
pool, for decode AND chunked prefill.

Layers pinned here:
  kernel vs gather   bounded-ulp (online vs one-shot softmax; same masks,
                     same GQA broadcast, same softcap);
  gather vs dense    BIT equality (the gathered pages reproduce the dense
                     layout exactly — masked stale/null positions contribute
                     exactly 0);
  poisoned vs clean  BIT equality per mode (null pages, fresh admissions,
                     freed-then-reused pages: stale KV must never reach a
                     live softmax);
  engine streams     kernel-mode == gather-mode == dense-pool greedy token
                     streams, unsharded and under 2x2 / 1x4 meshes.

The cases sweep ragged per-slot positions crossing page boundaries
(t % page_size in {0, 1, ps-1}), GQA head ratios, sliding windows and logit
softcap. Mesh cases run in-process on >= 4 devices (the CI mesh job);
single-device hosts re-run them in a forced-4-device subprocess. The
companion CI lane REPRO_FORCE_PAGED_KERNEL=1 runs tests/test_serving.py
through the kernel end to end."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import paged_attn as PA
from repro.launch.mesh import make_mesh
from repro.models import attention as ATT

MULTI = jax.device_count() >= 4

needs_mesh = pytest.mark.skipif(
    not MULTI, reason="needs >= 4 host devices (mesh CI job / subprocess)")

MESHES = [(2, 2), (1, 4)]

PS = 8          # page size
P = 4           # logical pages per slot -> Smax = 32


def _mesh(shape):
    return make_mesh(shape, ("data", "model"))


def _cfg(nkv=2, softcap=0.0, mode="auto"):
    return ModelConfig(name="tiny", family="dense", num_layers=1, d_model=32,
                       num_heads=4, num_kv_heads=nkv, d_ff=0, vocab_size=64,
                       dtype="float32", logit_softcap=softcap,
                       paged_attn=mode)


def _pools(cfg, t, seed=0):
    """Random page pools + contiguous per-row block tables covering each
    row's positions 0..t[b] (page for the NEXT write included, like
    grow_active). Unallocated entries stay at the null page 0."""
    rng = np.random.default_rng(seed)
    hd = cfg.resolved_head_dim()
    B = len(t)
    NP = B * P + 1
    kp = jnp.asarray(rng.normal(size=(NP, PS, cfg.num_kv_heads, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NP, PS, cfg.num_kv_heads, hd)),
                     jnp.float32)
    bt = np.zeros((B, P), np.int32)
    nxt = 1
    for b in range(B):
        for j in range(int(t[b]) // PS + 1):
            bt[b, j] = nxt
            nxt += 1
    return kp, vp, jnp.asarray(bt)


# page-boundary sweep: t % ps in {0, 1, ps-1} at several page counts
RAGGED_T = np.array([0, 1, 7, 8, 9, 15, 24])


# ----------------------------------------------------------------- decode

@pytest.mark.parametrize("nkv", [1, 2, 4])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (5, 0.0), (0, 4.0)])
def test_decode_kernel_gather_dense_parity(nkv, window, softcap):
    """attn_decode through the kernel vs the gather path vs a dense cache,
    on ragged positions crossing page boundaries. Gather == dense bitwise;
    kernel == gather to fp32 accumulation tolerance; all three scatter the
    new token identically."""
    cfg = _cfg(nkv=nkv, softcap=softcap)
    params = ATT.attn_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    B = len(RAGGED_T)
    x_t = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    t = jnp.asarray(RAGGED_T, jnp.int32)
    kp, vp, bt = _pools(cfg, RAGGED_T)
    hd = cfg.resolved_head_dim()

    # dense reference built from the same pre-write page contents
    dk = kp[bt].reshape(B, P * PS, nkv, hd)
    dv = vp[bt].reshape(B, P * PS, nkv, hd)

    outg, ckg, cvg = ATT.attn_decode(
        params, x_t, kp, vp, t, cfg=cfg.with_overrides(paged_attn="gather"),
        window=window, block_table=bt)
    outk, ckk, cvk = ATT.attn_decode(
        params, x_t, kp, vp, t, cfg=cfg.with_overrides(paged_attn="kernel"),
        window=window, block_table=bt)
    outd, _, _ = ATT.attn_decode(params, x_t, dk, dv, t, cfg=cfg,
                                 window=window)

    np.testing.assert_array_equal(np.asarray(ckg), np.asarray(ckk))
    np.testing.assert_array_equal(np.asarray(cvg), np.asarray(cvk))
    np.testing.assert_array_equal(np.asarray(outg), np.asarray(outd))
    np.testing.assert_allclose(np.asarray(outk), np.asarray(outg),
                               rtol=2e-5, atol=2e-5)


def test_resolve_mode():
    assert PA.resolve_mode(_cfg(mode="kernel")) == "kernel"
    assert PA.resolve_mode(_cfg(mode="gather")) == "gather"
    # auto resolves per lowering platform — gather on CPU hosts
    expected = "kernel" if jax.default_backend() == "tpu" else "gather"
    assert PA.resolve_mode(_cfg(mode="auto")) == expected
    with pytest.raises(ValueError, match="paged_attn"):
        PA.resolve_mode(_cfg(mode="bogus"))


# ---------------------------------------------------------- chunked prefill

def test_chunk_kernel_gather_dense_parity():
    """attn_chunk over a 27-token prompt in 8-token chunks: the paged
    scatter + gather reproduces the dense chunk path bit for bit (caches
    AND outputs, pads included), and the kernel tracks it to tolerance."""
    cfg = _cfg(nkv=2)
    params = ATT.attn_init(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(3)
    hd = cfg.resolved_head_dim()
    nkv = cfg.num_kv_heads
    plen, Cs = 27, 8
    x = jnp.asarray(rng.normal(size=(1, -(-plen // Cs) * Cs, cfg.d_model)),
                    jnp.float32)

    NP = P + 1
    kpg = jnp.zeros((NP, PS, nkv, hd), jnp.float32)
    vpg = jnp.zeros((NP, PS, nkv, hd), jnp.float32)
    kpk, vpk = kpg, vpg
    dk = jnp.zeros((1, P * PS, nkv, hd), jnp.float32)
    dv = jnp.zeros((1, P * PS, nkv, hd), jnp.float32)
    bt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    for start in range(0, x.shape[1], Cs):
        xc = x[:, start:start + Cs]
        valid = min(Cs, plen - start)
        kvl = start + valid
        outd, dk, dv = ATT.attn_chunk(params, xc, dk, dv, start, cfg=cfg,
                                      kv_len=kvl)
        outg, kpg, vpg = ATT.attn_chunk(
            params, xc, kpg, vpg, start,
            cfg=cfg.with_overrides(paged_attn="gather"), kv_len=kvl,
            block_table=bt)
        outk, kpk, vpk = ATT.attn_chunk(
            params, xc, kpk, vpk, start,
            cfg=cfg.with_overrides(paged_attn="kernel"), kv_len=kvl,
            block_table=bt)
        np.testing.assert_array_equal(np.asarray(outg), np.asarray(outd))
        np.testing.assert_allclose(np.asarray(outk), np.asarray(outg),
                                   rtol=2e-5, atol=2e-5)

    np.testing.assert_array_equal(np.asarray(kpk), np.asarray(kpg))
    np.testing.assert_array_equal(
        np.asarray(kpg[bt].reshape(1, P * PS, nkv, hd)), np.asarray(dk))
    np.testing.assert_array_equal(
        np.asarray(vpg[bt].reshape(1, P * PS, nkv, hd)), np.asarray(dv))


# ----------------------------------------------------------- quantized pages

def test_quantized_decode_kernel_gather_parity():
    """int8 pages + per-page scales through attn_decode: the kernel's
    in-kernel dequant (scales ride a scalar-prefetch BlockSpec) must track
    the gather path's dequant-at-gather to fp32 tolerance, and both modes
    must write the SAME int8 bytes and scales back (the rescale-on-write
    scatter runs outside the kernel, shared by both paths)."""
    from repro.core import quant as Q
    cfg = _cfg(nkv=2)
    params = ATT.attn_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    B = len(RAGGED_T)
    x_t = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    t = jnp.asarray(RAGGED_T, jnp.int32)
    kp, vp, bt = _pools(cfg, RAGGED_T)
    qk, ks = Q.quantize_pages(kp)
    qv, vs = Q.quantize_pages(vp)

    outg, (ckg, ksg), (cvg, vsg) = ATT.attn_decode(
        params, x_t, (qk, ks), (qv, vs), t,
        cfg=cfg.with_overrides(paged_attn="gather"), block_table=bt)
    outk, (ckk, ksk), (cvk, vsk) = ATT.attn_decode(
        params, x_t, (qk, ks), (qv, vs), t,
        cfg=cfg.with_overrides(paged_attn="kernel"), block_table=bt)

    np.testing.assert_array_equal(np.asarray(ckg), np.asarray(ckk))
    np.testing.assert_array_equal(np.asarray(cvg), np.asarray(cvk))
    np.testing.assert_array_equal(np.asarray(ksg), np.asarray(ksk))
    np.testing.assert_array_equal(np.asarray(vsg), np.asarray(vsk))
    np.testing.assert_allclose(np.asarray(outk), np.asarray(outg),
                               rtol=2e-5, atol=2e-5)


def test_quantized_chunk_kernel_gather_parity():
    """Chunked prefill over int8 pages: kernel vs gather, chunk by chunk —
    identical int8 scatter results, outputs within fp32 tolerance."""
    from repro.core import quant as Q
    cfg = _cfg(nkv=2)
    params = ATT.attn_init(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(3)
    hd = cfg.resolved_head_dim()
    nkv = cfg.num_kv_heads
    plen, Cs = 27, 8
    x = jnp.asarray(rng.normal(size=(1, -(-plen // Cs) * Cs, cfg.d_model)),
                    jnp.float32)
    NP = P + 1
    zero_p = jnp.zeros((NP, PS, nkv, hd), jnp.int8)
    zero_s = jnp.zeros((NP, nkv), jnp.float32)
    kg, vg = (zero_p, zero_s), (zero_p, zero_s)
    kk, vk = (zero_p, zero_s), (zero_p, zero_s)
    bt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    for start in range(0, x.shape[1], Cs):
        xc = x[:, start:start + Cs]
        kvl = start + min(Cs, plen - start)
        outg, kg, vg = ATT.attn_chunk(
            params, xc, kg, vg, start,
            cfg=cfg.with_overrides(paged_attn="gather"), kv_len=kvl,
            block_table=bt)
        outk, kk, vk = ATT.attn_chunk(
            params, xc, kk, vk, start,
            cfg=cfg.with_overrides(paged_attn="kernel"), kv_len=kvl,
            block_table=bt)
        np.testing.assert_allclose(np.asarray(outk), np.asarray(outg),
                                   rtol=2e-5, atol=2e-5)

    np.testing.assert_array_equal(np.asarray(kk[0]), np.asarray(kg[0]))
    np.testing.assert_array_equal(np.asarray(kk[1]), np.asarray(kg[1]))
    np.testing.assert_array_equal(np.asarray(vk[0]), np.asarray(vg[0]))
    np.testing.assert_array_equal(np.asarray(vk[1]), np.asarray(vg[1]))


# --------------------------------------------- adversarial null / stale pages

@pytest.mark.parametrize("mode", ["gather", "kernel"])
def test_null_and_reused_pages_never_leak(mode):
    """Poisoning every UNREACHABLE position — the null page, unallocated
    pages, and the stale tails of freed-then-reused pages — must not change
    a single output bit. Row 0 is a fresh admission (t=0: everything past
    position 0 is null/stale), row 1 sits mid-page, row 2's second page is
    'reused' with a hot stale tail."""
    cfg = _cfg(nkv=2, mode=mode)
    params = ATT.attn_init(jax.random.PRNGKey(4), cfg)
    rng = np.random.default_rng(5)
    t = np.array([0, 3, 11])
    B = len(t)
    x_t = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    kp, vp, bt = _pools(cfg, t, seed=6)
    btn = np.asarray(bt)

    # poison: huge finite values everywhere a correct path must never look
    poison = np.full(np.asarray(kp).shape, 1e4, np.float32)
    kpp, vpp = np.array(poison), np.array(-poison)
    live = np.zeros((kp.shape[0], PS), bool)          # position-level liveness
    for b in range(B):
        for pos in range(int(t[b]) + 1):              # 0..t live (t rewritten)
            live[btn[b, pos // PS], pos % PS] = True
    kpc = np.where(live[:, :, None, None], np.asarray(kp), 0.0)
    vpc = np.where(live[:, :, None, None], np.asarray(vp), 0.0)
    kpp = np.where(live[:, :, None, None], np.asarray(kp), kpp)
    vpp = np.where(live[:, :, None, None], np.asarray(vp), vpp)

    out_c, _, _ = ATT.attn_decode(params, x_t, jnp.asarray(kpc),
                                  jnp.asarray(vpc), jnp.asarray(t, jnp.int32),
                                  cfg=cfg, block_table=bt)
    out_p, _, _ = ATT.attn_decode(params, x_t, jnp.asarray(kpp),
                                  jnp.asarray(vpp), jnp.asarray(t, jnp.int32),
                                  cfg=cfg, block_table=bt)
    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_p))


@pytest.mark.parametrize("mode", ["gather", "kernel"])
def test_engine_page_reuse_streams_clean(mode):
    """Engine-level freed-then-reused pages: 4 requests over 2 slots force
    retirement + page reuse mid-trace; every greedy stream must equal the
    dense pool's, through the gather path AND the kernel."""
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_continuous
    from repro.models.model import model_init
    cfg = get_config("llama_moe_4_16", smoke=True)
    params = model_init(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in (12, 12, 16, 12)]
    kw = dict(num_slots=2, max_tokens=32, arrival_steps=[0, 1, 3, 3])
    ref = serve_continuous(params, cfg, prompts, 6, **kw)
    got = serve_continuous(params, cfg.with_overrides(paged_attn=mode),
                           prompts, 6, paged=True, page_size=8, **kw)
    assert got["stats"]["paged"]
    for rid in ref["tokens"]:
        np.testing.assert_array_equal(ref["tokens"][rid],
                                      got["tokens"][rid])


@pytest.mark.parametrize("mode", ["gather", "kernel"])
def test_engine_chunked_prefill_paged_native(mode):
    """Chunked prefill on a paged pool prefills STRAIGHT into the pool's
    pages (no dense [1, max_tokens] copy) — streams must still equal the
    dense-pool chunked engine's, on both paged realizations."""
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_continuous
    from repro.models.model import model_init
    cfg = get_config("starcoder2-3b", smoke=True)
    params = model_init(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in (27, 9, 21)]
    kw = dict(num_slots=2, max_tokens=48, arrival_steps=[0, 0, 2],
              prefill_chunk=16)
    ref = serve_continuous(params, cfg, prompts, 5, **kw)
    got = serve_continuous(params, cfg.with_overrides(paged_attn=mode),
                           prompts, 5, paged=True, page_size=8, **kw)
    assert got["stats"]["chunk_ticks"] >= 2
    for rid in ref["tokens"]:
        np.testing.assert_array_equal(ref["tokens"][rid],
                                      got["tokens"][rid])


# ------------------------------------------------------------------- meshes

@needs_mesh
@pytest.mark.parametrize("shape", MESHES)
def test_kernel_mesh_decode_parity(shape):
    """The kernel under a GSPMD mesh (inputs pinned replicated — pallas has
    no SPMD rule) must reproduce its unsharded output."""
    cfg = _cfg(nkv=2)
    hd = cfg.resolved_head_dim()
    rng = np.random.default_rng(7)
    kp, vp, bt = _pools(cfg, RAGGED_T, seed=8)
    B = len(RAGGED_T)
    q = jnp.asarray(rng.normal(size=(B, cfg.num_heads, hd)), jnp.float32)
    t = jnp.asarray(RAGGED_T, jnp.int32)
    ref = PA.paged_attn_decode(q, kp, vp, bt, t, window=5)
    with jax.set_mesh(_mesh(shape)):
        got = PA.paged_attn_decode(q, kp, vp, bt, t, window=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@needs_mesh
@pytest.mark.parametrize("shape", MESHES)
@pytest.mark.parametrize("mode", ["gather", "kernel"])
def test_sharded_engine_mesh_stream_parity(shape, mode):
    """Paged engine under the mesh (kernel mode flips the page-store layout
    to whole-page staging: heads over "model" — launch/sharding.py): every
    stream equals the unsharded paged engine's."""
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_continuous
    from repro.models.model import model_init
    cfg = get_config("llama_moe_4_16", smoke=True).with_overrides(
        paged_attn=mode)
    params = model_init(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
               for _ in range(3)]
    kw = dict(num_slots=2, max_tokens=32, arrival_steps=[0, 1, 3],
              paged=True, page_size=8)
    ref = serve_continuous(params, cfg, prompts, 5, **kw)
    got = serve_continuous(params, cfg, prompts, 5, mesh=_mesh(shape), **kw)
    assert got["stats"]["mesh"] == dict(zip(("data", "model"), shape))
    for rid in ref["tokens"]:
        np.testing.assert_array_equal(ref["tokens"][rid],
                                      got["tokens"][rid])


def test_mesh_cases_subprocess():
    """Tier-1 fallback: on a single-device host, re-run this file's mesh
    cases in a subprocess with 4 forced host devices."""
    if MULTI:
        pytest.skip("mesh cases already ran in-process")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", __file__,
         "-k", "mesh and not subprocess"],
        env=env, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]

"""Quantized decode state (cfg.kv_quant="int8"): int8 KV pages + per-page
per-kv-head amax scales, int8 GO rows + per-row scales (src/repro/core/quant.py).

Layers pinned here:
  round-trip bound    |dequant(quant(x)) - x| <= amax / (2 * QMAX) per page
                      per head — property-tested over magnitudes, all-zero
                      pages and outlier-dominated pages;
  write determinism   a page's int8 contents are a pure function of the
                      tokens written to it: scrubbed-then-reused pages equal
                      fresh pages bit for bit (rescale-on-write + zeroed
                      scales on free);
  fp32 divergence     quantized attention output sits a BOUNDED distance
                      from fp32 (scale-derived tolerance), never bit-equal
                      by accident of tiny inputs;
  engine lifecycle    solo-vs-pooled bit-identity, prefix-share hits,
                      preemption + resume, NaN-poison quarantine and
                      journal crash recovery — all quant-vs-quant exact,
                      with the per-tick invariant audit on;
  meshes              quantized streams under 2x2 / 1x4 GSPMD meshes equal
                      the unsharded quantized engine (scales follow the
                      page-axis sharding rules in launch/sharding.py).

The kernel-vs-gather parity of the quantized Pallas kernel lives in
tests/test_paged_attn.py; the end-to-end CI lane is
REPRO_KV_QUANT=1 REPRO_FORCE_PAGED=1 over tests/test_serving.py."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st
from repro.configs.registry import get_config
from repro.core import quant as Q
from repro.launch.mesh import make_mesh
from repro.launch.serve import generate
from repro.models.model import model_init
from repro.serving import RequestStatus, ServingEngine

MAX_TOKENS = 48

MULTI = jax.device_count() >= 4
needs_mesh = pytest.mark.skipif(
    not MULTI, reason="needs >= 4 host devices (mesh CI job / subprocess)")
MESHES = [(2, 2), (1, 4)]


def _setup(arch="llama_moe_4_16"):
    cfg = get_config(arch, smoke=True)
    params = model_init(jax.random.PRNGKey(5), cfg)
    return cfg, params


def _solo_tokens(params, cfg, prompt, gen, **kw):
    """The request alone on a 1-slot QUANTIZED engine: decode is row-wise
    independent, so this is the bit-identity oracle for pooled quantized
    streams (fp32 generate() is only boundedly close — near-tied greedy
    argmaxes flip on smoke weights)."""
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    kw.setdefault("kv_quant", "int8")
    eng = ServingEngine(params, cfg, num_slots=1, max_tokens=MAX_TOKENS, **kw)
    rid = eng.submit(np.asarray(prompt, np.int32), gen)
    return eng.run()[rid].tokens


# ------------------------------------------------------ round-trip properties

def _assert_page_roundtrip_bound(x):
    q, s = Q.quantize_pages(jnp.asarray(x))
    back = np.asarray(Q.dequantize_pages(q, s))
    amax = np.abs(x).max(axis=(-3, -1))                   # [..., Hkv]
    bound = amax / (2 * Q.QMAX)
    err = np.abs(back - x).max(axis=(-3, -1))
    # (1 + 1e-6) absorbs f32 rounding in the quotient/product themselves
    assert (err <= bound * (1 + 1e-6) + 1e-30).all(), \
        f"round-trip error {err.max()} above amax/(2*QMAX) bound"
    return q, s, back


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 32 - 1), st.integers(-6, 6), st.booleans(),
       st.booleans())
def test_page_roundtrip_error_bound_property(seed, expo, zero_page, outlier):
    """quantize_pages/dequantize_pages: per-(page, head) error is bounded by
    amax / (2 * QMAX) across magnitudes 1e-6..1e6, including all-zero pages
    (exact zeros, scale 0) and pages whose amax is set by a single outlier
    1e3 above the rest (the bound scales with amax — outliers widen it,
    they never break it)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(3, 8, 2, 4)).astype(np.float32) * (10.0 ** expo)
    if outlier:
        x[2, 3, 1, 2] *= 1e3
    if zero_page:
        x[1] = 0.0
    q, s, back = _assert_page_roundtrip_bound(x)
    if zero_page:
        assert (np.asarray(q[1]) == 0).all()
        assert (np.asarray(s[1]) == 0).all()
        assert (back[1] == 0).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 32 - 1), st.integers(-6, 6))
def test_row_roundtrip_error_bound_property(seed, expo):
    """quantize_rows/dequantize_rows (the GO-cache layout): per-row error is
    bounded by the row amax / (2 * QMAX)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(2, 3, 4, 8)).astype(np.float32) * (10.0 ** expo)
    x[0, 0, 1] = 0.0                                      # all-zero row
    q, s = Q.quantize_rows(jnp.asarray(x))
    back = np.asarray(Q.dequantize_rows(q, s))
    bound = np.abs(x).max(axis=-1) / (2 * Q.QMAX)
    err = np.abs(back - x).max(axis=-1)
    assert (err <= bound * (1 + 1e-6) + 1e-30).all()
    assert (back[0, 0, 1] == 0).all()


def test_page_roundtrip_bound_cases():
    """Deterministic pin of the property's named edge cases (runs even
    without the hypothesis dev extra): all-zero page and outlier page."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(3, 8, 2, 4)).astype(np.float32)
    x[1] = 0.0
    x[2, 0, 0, 0] = 1e4                                   # outlier
    q, s, back = _assert_page_roundtrip_bound(x)
    assert (back[1] == 0).all() and (np.asarray(s)[1] == 0).all()
    # the outlier element itself survives to within half a quantum
    assert abs(back[2, 0, 0, 0] - 1e4) <= 1e4 / (2 * Q.QMAX) * (1 + 1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 32 - 1))
def test_chunk_scatter_roundtrip_bound_property(seed):
    """scatter_chunk into empty pages: written positions round-trip within
    the final page scales' half-quantum; untouched positions stay zero."""
    rng = np.random.default_rng(seed)
    NP, ps, Hkv, hd, Cs = 4, 8, 2, 4, 8
    cache = jnp.zeros((NP, ps, Hkv, hd), jnp.int8)
    scales = jnp.zeros((NP, Hkv), jnp.float32)
    vals = rng.normal(size=(1, Cs, Hkv, hd)).astype(np.float32)
    pages = jnp.asarray([[1] * ps], jnp.int32)            # one full page
    offs = jnp.asarray([list(range(ps))], jnp.int32)
    cache, scales = Q.scatter_chunk(cache, scales, pages, offs,
                                    jnp.asarray(vals))
    back = np.asarray(Q.dequantize_pages(cache, scales))
    bound = np.asarray(scales)[1] / 2                     # [Hkv]
    err = np.abs(back[1] - vals[0]).max(axis=(0, 2))
    assert (err <= bound * (1 + 1e-6) + 1e-30).all()
    assert (back[[0, 2, 3]] == 0).all()


def test_scatter_reused_page_equals_fresh_page():
    """Rescale-on-write determinism: scattering a token stream into a page
    whose previous tenant left int8 garbage behind (scale scrubbed to 0 on
    free, contents NOT) produces bit-identical contents to a fresh zero
    page — the first write's factor-0 rescale wipes the garbage."""
    rng = np.random.default_rng(0)
    NP, ps, Hkv, hd = 3, 8, 2, 4
    fresh_c = jnp.zeros((NP, ps, Hkv, hd), jnp.int8)
    dirty_c = jnp.asarray(
        rng.integers(-127, 128, size=(NP, ps, Hkv, hd)), jnp.int8)
    fresh_s = dirty_s = jnp.zeros((NP, Hkv), jnp.float32)
    for i in range(ps):
        # growing magnitudes force a scale-growth rescale on every write
        val = jnp.asarray(rng.normal(size=(1, Hkv, hd)) * (i + 1),
                          jnp.float32)
        page, off = jnp.asarray([1], jnp.int32), jnp.asarray([i], jnp.int32)
        fresh_c, fresh_s = Q.scatter_token(fresh_c, fresh_s, page, off, val)
        dirty_c, dirty_s = Q.scatter_token(dirty_c, dirty_s, page, off, val)
    np.testing.assert_array_equal(np.asarray(fresh_c[1]),
                                  np.asarray(dirty_c[1]))
    np.testing.assert_array_equal(np.asarray(fresh_s), np.asarray(dirty_s))


def test_quantized_attention_bounded_divergence_from_fp32():
    """Gather-path attention over int8 pages vs the same pages in fp32:
    outputs diverge (quantization is real) but stay within a scale-derived
    tolerance — the V half-quantum plus the softmax shift the K error can
    induce."""
    from repro.configs.base import ModelConfig
    from repro.models import attention as ATT
    cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=0, vocab_size=64,
                      dtype="float32", paged_attn="gather")
    hd = cfg.resolved_head_dim()
    params = ATT.attn_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    NP, ps, B = 9, 8, 2
    kp = jnp.asarray(rng.normal(size=(NP, ps, 2, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NP, ps, 2, hd)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    t = jnp.asarray([17, 25], jnp.int32)
    x_t = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    qk, ks = Q.quantize_pages(kp)
    qv, vs = Q.quantize_pages(vp)

    ref, _, _ = ATT.attn_decode(params, x_t, kp, vp, t, cfg=cfg,
                                block_table=bt)
    got, _, _ = ATT.attn_decode(params, x_t, (qk, ks), (qv, vs), t, cfg=cfg,
                                block_table=bt)
    diff = np.abs(np.asarray(got) - np.asarray(ref)).max()
    # V dequant error alone is <= max scale / 2 ~ 0.016 for N(0,1) pages;
    # K error perturbs the softmax weights on top. 10x the V half-quantum
    # is a loose but honest ceiling for these magnitudes.
    tol = 10 * float(np.asarray(vs).max()) / 2
    assert 0 < diff <= tol, f"divergence {diff} outside (0, {tol}]"


# ------------------------------------------------------- validation + stats

def test_typed_validation_fail_fast(monkeypatch):
    """kv_quant="int8" is an API contract: impossible shapes raise typed
    errors NAMING the knob at engine construction, not mid-decode.
    Exercised unforced: the CI force-paged lane would silently upgrade the
    dense-pool case into a valid paged engine."""
    monkeypatch.delenv("REPRO_FORCE_PAGED", raising=False)
    monkeypatch.delenv("REPRO_FORCE_PAGED_KERNEL", raising=False)
    cfg, params = _setup()
    with pytest.raises(ValueError, match="kv_quant"):
        ServingEngine(params, cfg, num_slots=1, max_tokens=MAX_TOKENS,
                      kv_quant="int8")                    # dense pool
    with pytest.raises(ValueError, match="kv_quant"):
        ServingEngine(params, cfg, num_slots=1, max_tokens=MAX_TOKENS,
                      paged=True, page_size=4, kv_quant="int8")  # untileable
    with pytest.raises(ValueError, match="kv_quant"):
        ServingEngine(params, cfg, num_slots=1, max_tokens=MAX_TOKENS,
                      paged=True, page_size=8, kv_quant="fp4")   # unknown
    xl = get_config("xlstm-1.3b", smoke=True)
    xp = model_init(jax.random.PRNGKey(5), xl)
    with pytest.raises(ValueError):                       # recurrent arch
        ServingEngine(xp, xl, num_slots=1, max_tokens=16, paged=True,
                      page_size=8, kv_quant="int8")


def test_env_lane_noops_where_unsupported(monkeypatch):
    """REPRO_KV_QUANT is a CI lane, not a contract: it silently no-ops on
    dense pools and untileable page sizes instead of failing engines that
    are valid unforced."""
    monkeypatch.delenv("REPRO_FORCE_PAGED", raising=False)
    monkeypatch.delenv("REPRO_FORCE_PAGED_KERNEL", raising=False)
    cfg, params = _setup()
    monkeypatch.setenv("REPRO_KV_QUANT", "1")
    dense = ServingEngine(params, cfg, num_slots=1, max_tokens=MAX_TOKENS)
    assert dense.cfg.kv_quant == "none"
    odd = ServingEngine(params, cfg, num_slots=1, max_tokens=MAX_TOKENS,
                        paged=True, page_size=4)
    assert odd.cfg.kv_quant == "none"
    ok = ServingEngine(params, cfg, num_slots=1, max_tokens=MAX_TOKENS,
                       paged=True, page_size=8)
    assert ok.cfg.kv_quant == "int8" and ok.pool.quant


def test_stats_surface_quant_fields():
    cfg, params = _setup()
    rng = np.random.default_rng(30)
    p = rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
    eng = ServingEngine(params, cfg, num_slots=2, max_tokens=MAX_TOKENS,
                        paged=True, page_size=8, kv_quant="int8")
    rid = eng.submit(p, 6)
    eng.run()
    s = eng.stats()
    assert s["kv_quant_dtype"] == "int8"
    assert s["kv_bytes_per_token"] == Q.kv_bytes_per_token(eng.cfg, 8)
    # int8 pages must actually be smaller than the fp32 pool's ("none"
    # pinned explicitly so the REPRO_KV_QUANT lane can't quantize the
    # control engine)
    fp32 = ServingEngine(params, cfg, num_slots=2, max_tokens=MAX_TOKENS,
                         paged=True, page_size=8, kv_quant="none")
    assert s["kv_bytes_per_token"] < fp32.stats()["kv_bytes_per_token"] / 3
    assert fp32.stats()["kv_quant_dtype"] is None
    assert fp32.stats()["dequant_max_abs_err"] is None
    # observed dequant error: nonzero once pages were written, finite, and
    # small at these magnitudes (the exact bound is pinned by the property
    # tests above against each admission's own amax)
    assert 0 < s["dequant_max_abs_err"] < 1.0


# ------------------------------------------------------- engine lifecycle

def test_pooled_streams_equal_solo_quantized(monkeypatch):
    """Staggered arrivals + slot reuse on a 2-slot quantized pool: every
    stream equals the same request alone on a 1-slot quantized engine, and
    reruns are bit-identical (int8 decode is deterministic). The per-tick
    audit checks scale finiteness and freed-page scrubbing throughout."""
    monkeypatch.setenv("REPRO_AUDIT", "1")
    cfg, params = _setup()
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in (12, 12, 16, 12)]
    gens = [8, 5, 7, 6]

    def run():
        eng = ServingEngine(params, cfg, num_slots=2, max_tokens=MAX_TOKENS,
                            paged=True, page_size=8, kv_quant="int8")
        rids = [eng.submit(p, g, arrival_step=a)
                for p, g, a in zip(prompts, gens, [0, 3, 7, 7])]
        fin = eng.run()
        return [fin[r].tokens for r in rids], eng

    got, eng = run()
    got2, _ = run()
    assert got == got2, "quantized decode is not deterministic"
    for t, p, g in zip(got, prompts, gens):
        assert t == _solo_tokens(params, cfg, p, g), \
            "pooled quantized stream diverged from solo"
    assert eng.pool.alloc.pages_in_use == 0
    eng.pool.alloc.check()
    eng.pool.audit()


def test_prefix_share_hit_stays_quantized(monkeypatch):
    """COW prefix sharing on a quantized pool: a full-prefix hit reuses the
    depositor's int8 pages AND their scales — the hit stream equals both the
    cold quantized stream and the solo oracle, bit for bit."""
    monkeypatch.setenv("REPRO_AUDIT", "1")
    cfg, params = _setup()
    rng = np.random.default_rng(32)
    p = rng.integers(0, cfg.vocab_size, size=16, dtype=np.int32)

    eng = ServingEngine(params, cfg, num_slots=2, max_tokens=MAX_TOKENS,
                        paged=True, page_size=8, kv_quant="int8",
                        prefix_share=True)
    r0 = eng.submit(p, 6)
    r1 = eng.submit(p, 6, arrival_step=4)   # same prompt -> cache hit while
    fin = eng.run()                         # the deposit is still pinned
    assert eng.stats()["prefix_hits"] >= 1
    assert eng.stats()["pages_shared"] >= 2           # both full int8 pages
    assert fin[r1].tokens == fin[r0].tokens
    assert fin[r1].tokens == _solo_tokens(params, cfg, p, 6)
    eng.pool.audit()


def test_preemption_resume_bit_identical_quantized(monkeypatch):
    """Preemption snapshot/restore round-trips int8 pages + scales + GO row
    scales: the evicted-then-resumed quantized stream equals running alone."""
    monkeypatch.setenv("REPRO_AUDIT", "1")
    cfg, params = _setup()
    rng = np.random.default_rng(33)
    lo = [rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32)
          for _ in range(2)]
    hi = rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32)
    eng = ServingEngine(params, cfg, num_slots=3, max_tokens=MAX_TOKENS,
                        paged=True, page_size=8, num_pages=9,
                        preemption=True, kv_quant="int8")
    r_lo = [eng.submit(p, 24, priority=5) for p in lo]
    r_hi = eng.submit(hi, 8, priority=0, arrival_step=6)
    fin = eng.run()
    s = eng.stats()
    assert s["preemptions"] >= 1 and s["resumes"] >= 1
    for rid, p, g in [(r_lo[0], lo[0], 24), (r_lo[1], lo[1], 24),
                      (r_hi, hi, 8)]:
        assert fin[rid].status is RequestStatus.DONE
        assert fin[rid].tokens == _solo_tokens(params, cfg, p, g), \
            "quantized stream diverged across preemption churn"
    assert eng.pool.alloc.pages_in_use == 0
    eng.pool.audit()


def test_nan_poison_quarantines_quantized_slot(monkeypatch):
    """NaN cannot live in an int8 page, so poison lands on the page's SCALE
    — the poisoned stream still retires FAILED ("non-finite logits") with
    its pre-poison prefix kept, and the cohabitant is untouched. The audit
    tolerates the in-flight NaN scale on a LIVE page and asserts it is
    scrubbed once the page is freed."""
    monkeypatch.setenv("REPRO_AUDIT", "1")
    cfg, params = _setup()
    rng = np.random.default_rng(34)
    p0, p1 = (rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
              for _ in range(2))
    eng = ServingEngine(params, cfg, num_slots=2, max_tokens=MAX_TOKENS,
                        paged=True, page_size=8, kv_quant="int8")
    r0 = eng.submit(p0, 16)
    r1 = eng.submit(p1, 16)
    for _ in range(40):
        eng.step()
        slot0 = next((s for s, o in enumerate(eng.pool.owner)
                      if o is not None and o.request_id == r0), None)
        if slot0 is not None and len(eng.pool.owner[slot0].tokens) >= 4:
            break
    eng.pool.poison_slot(slot0)
    fin = eng.run()
    assert fin[r0].status is RequestStatus.FAILED
    assert fin[r0].fail_reason == "non-finite logits"
    ref0 = _solo_tokens(params, cfg, p0, 16)
    assert fin[r0].tokens == ref0[:len(fin[r0].tokens)]
    assert fin[r1].tokens == _solo_tokens(params, cfg, p1, 16)
    # quarantine scrubbed the poisoned scale: no NaN survives on free pages
    eng.pool.audit()
    assert np.isfinite(np.asarray(eng.pool.state["k_scales"])).all()


def test_crash_recovery_rebuilds_quantized_engine(tmp_path, monkeypatch):
    """Journal + snapshot durability: abandon a journaled QUANTIZED engine
    mid-decode and recover() — the rebuilt engine is quantized (kv_quant
    rides engine_kw through the snapshot) and every stream finishes exactly
    as the uninterrupted solo quantized run."""
    monkeypatch.setenv("REPRO_AUDIT", "1")
    cfg, params = _setup()
    rng = np.random.default_rng(35)
    prompts = [rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
               for _ in range(3)]
    eng = ServingEngine(params, cfg, num_slots=2, max_tokens=MAX_TOKENS,
                        paged=True, page_size=8, kv_quant="int8",
                        journal_dir=str(tmp_path), snapshot_every=4)
    rids = [eng.submit(p, 12) for p in prompts]
    for _ in range(6):
        eng.step()
    assert eng.pool.num_active() > 0, "crash point must have live slots"

    rec = ServingEngine.recover(str(tmp_path), params, cfg)
    assert rec.cfg.kv_quant == "int8" and rec.pool.quant
    fin = rec.run()
    for rid, p in zip(rids, prompts):
        assert fin[rid].status is RequestStatus.DONE
        assert fin[rid].tokens == _solo_tokens(params, cfg, p, 12), \
            "quantized stream diverged across the crash"
    assert rec.stats()["recoveries"] == 1
    assert rec.pool.alloc.pages_in_use == 0
    rec.pool.audit()


# ------------------------------------------------------------------- meshes

@needs_mesh
@pytest.mark.parametrize("shape", MESHES)
def test_quantized_engine_mesh_stream_parity(shape):
    """Quantized engine under a GSPMD mesh (int8 pages + scales shard along
    the page axis, GO scales along slots/experts — launch/sharding.py):
    every stream equals the unsharded quantized engine's."""
    from repro.launch.serve import serve_continuous
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
               for _ in range(3)]
    kw = dict(num_slots=2, max_tokens=32, arrival_steps=[0, 1, 3],
              paged=True, page_size=8, kv_quant="int8")
    ref = serve_continuous(params, cfg, prompts, 5, **kw)
    mesh = make_mesh(shape, ("data", "model"))
    got = serve_continuous(params, cfg, prompts, 5, mesh=mesh, **kw)
    assert got["stats"]["kv_quant_dtype"] == "int8"
    for rid in ref["tokens"]:
        np.testing.assert_array_equal(ref["tokens"][rid], got["tokens"][rid])


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

"""Mesh-parity suite: sharded-xla vs sharded-pallas vs unsharded-pallas on
all three routing paths — token-choice (EP shard_map, ample AND tight
capacity), grouped C1 with capacity drops, and the expert-choice / GO-cache
decode — plus the continuous-batching engine with slot rows sharded across
data-parallel replicas.

Runs IN-PROCESS when the host already exposes >= 4 devices (the CI mesh job
sets XLA_FLAGS=--xla_force_host_platform_device_count=4 before pytest);
otherwise a single subprocess re-runs this file under that flag, so the
tier-1 suite keeps the coverage on single-device hosts (conftest must not
set XLA_FLAGS globally — the smoke tests need the real device)."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core import moe as MOE
from repro.core.grouping import default_groups, group_of_expert_from_groups
from repro.launch.mesh import make_mesh

MULTI = jax.device_count() >= 4

needs_mesh = pytest.mark.skipif(
    not MULTI, reason="needs >= 4 host devices (mesh CI job / subprocess)")

# model-axis sizes 2 and 4 (E=8 divides both); data axis takes the rest
MESHES = [(2, 2), (1, 4)]


def _mesh(shape):
    return make_mesh(shape, ("data", "model"))


def _pallas(e: MoEConfig, **kw) -> MoEConfig:
    return dataclasses.replace(e, backend="pallas", gmm_block_rows=8, **kw)


@pytest.fixture(scope="module")
def setup():
    e = MoEConfig(num_experts=8, top_k=2, d_expert=32, capacity_factor=8.0)
    p = MOE.moe_init(jax.random.PRNGKey(0), 64, e, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64)) * 0.3
    return e, p, h


# ------------------------------------------------- token-choice (EP shard_map)

@needs_mesh
@pytest.mark.parametrize("shape", MESHES)
def test_ep_token_choice_three_way_parity(setup, shape):
    """Sharded xla == sharded pallas == unsharded pallas (ample capacity:
    nothing drops, so the dropless unsharded plan is comparable too)."""
    e, p, h = setup
    ep = _pallas(e)
    y_uns = jnp.stack(
        [MOE.dispatch_forward(p, h[b], ep)[0] for b in range(h.shape[0])])
    with jax.set_mesh(_mesh(shape)):
        y_x, a_x = jax.jit(lambda p, h: MOE.moe_forward_ep(p, h, e))(p, h)
        y_p, a_p = jax.jit(lambda p, h: MOE.moe_forward_ep(p, h, ep))(p, h)
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_uns),
                               rtol=1e-4, atol=1e-5)
    assert int(a_x["dropped"]) == int(a_p["dropped"]) == 0
    np.testing.assert_array_equal(np.asarray(a_x["counts"]),
                                  np.asarray(a_p["counts"]))
    assert int(a_p["counts"].sum()) == h.shape[0] * h.shape[1] * e.top_k


@needs_mesh
@pytest.mark.parametrize("shape", MESHES)
def test_ep_capacity_drop_parity(setup, shape):
    """Tight per-shard capacity: both backends must evict the SAME pairs
    (pallas realizes a drop as a zero combine weight) and agree on outputs."""
    e, p, h = setup
    et = dataclasses.replace(e, capacity_factor=0.5)
    with jax.set_mesh(_mesh(shape)):
        y_x, a_x = jax.jit(lambda p, h: MOE.moe_forward_ep(p, h, et))(p, h)
        y_p, a_p = jax.jit(
            lambda p, h: MOE.moe_forward_ep(p, h, _pallas(et)))(p, h)
    assert int(a_x["dropped"]) == int(a_p["dropped"]) > 0
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------- grouped C1 (capacity drops)

@needs_mesh
@pytest.mark.parametrize("shape", MESHES)
def test_group_forward_under_mesh_drop_parity(shape):
    """C1 pooled-capacity path under the mesh (GSPMD over row-sharded
    tokens): xla and pallas drop the same pairs and agree with the
    unsharded run."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    e = MoEConfig(num_experts=8, top_k=2, d_expert=32, capacity_factor=1.25,
                  group_size=2)
    p = MOE.moe_init(jax.random.PRNGKey(0), 64, e, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 64)) * 0.3
    goe = jnp.asarray(group_of_expert_from_groups(default_groups(e)))
    y_uns, a_uns = MOE.group_forward(p, x, _pallas(e), goe, pool_factor=0.7)
    mesh = _mesh(shape)
    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        y_x, a_x = jax.jit(
            lambda p, x: MOE.group_forward(p, x, e, goe, pool_factor=0.7)
        )(p, xs)
        y_p, a_p = jax.jit(
            lambda p, x: MOE.group_forward(p, x, _pallas(e), goe,
                                           pool_factor=0.7))(p, xs)
    assert int(a_x["dropped"]) == int(a_p["dropped"]) == int(a_uns["dropped"])
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_uns),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------- expert-choice / GO-cache decode

@needs_mesh
@pytest.mark.parametrize("shape", MESHES)
def test_go_decode_selected_under_mesh(shape):
    """C4 decode under the mesh with batch rows sharded across the data
    axis: the selected-experts grouped GEMM equals the dense fallback and
    the unsharded run, step for step."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.go_cache import go_cache_init, go_cache_step
    from repro.kernels.ops import go_selected_ffn
    e = MoEConfig(num_experts=8, top_k=2, d_expert=32)
    p = MOE.moe_init(jax.random.PRNGKey(0), 64, e, jnp.float32)
    B, E, k, d = 4, e.num_experts, e.top_k, 64
    gate = p["gate"]
    dense_fn = lambda xt: MOE.expert_ffn_all(p, xt)
    sel_fn = lambda xt, sel, g: go_selected_ffn(
        xt, sel, g, p["experts"], E, bn=8)[0]
    mesh = _mesh(shape)

    cache_u = cache_d = cache_s = go_cache_init(B, E, k, d, jnp.float32)
    key = jax.random.PRNGKey(7)
    step_d = jax.jit(lambda c, x, t: go_cache_step(c, x, t, gate, dense_fn))
    step_s = jax.jit(
        lambda c, x, t: go_cache_step(c, x, t, gate, contrib_fn=sel_fn))
    for t in range(k + 4):
        key, sub = jax.random.split(key)
        xt = jax.random.normal(sub, (B, d)) * 0.3
        r_u = step_s(cache_u, xt, t)                       # unsharded ref
        with jax.set_mesh(mesh):
            xs = jax.device_put(xt, NamedSharding(mesh, P("data", None)))
            r_d = step_d(cache_d, xs, t)
            r_s = step_s(cache_s, xs, t)
        np.testing.assert_array_equal(np.asarray(r_d.selected),
                                      np.asarray(r_s.selected))
        for a, b in ((r_d, r_s), (r_u, r_s)):
            np.testing.assert_allclose(np.asarray(a.y), np.asarray(b.y),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(a.cache.outputs),
                                       np.asarray(b.cache.outputs),
                                       rtol=1e-5, atol=1e-6)
        cache_u, cache_d, cache_s = r_u.cache, r_d.cache, r_s.cache


# ------------------------------------------------- sharded serving engine

@needs_mesh
@pytest.mark.parametrize("backend,paged", [("auto", False), ("pallas", False),
                                           ("auto", True)])
def test_sharded_engine_bit_identical(backend, paged):
    """Continuous-batching engine with slot rows sharded across DP replicas:
    every stream equals the unsharded engine bit for bit, on both the dense
    (auto->xla) and the selected-experts pallas decode — and on the PAGED
    pool, whose page dim shards over data-parallel with the page interior
    over "model" (launch/sharding.py page-dim rules)."""
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_continuous
    from repro.models.model import model_init
    cfg = get_config("llama_moe_4_16", smoke=True)
    if backend != "auto":
        cfg = cfg.with_overrides(moe=dataclasses.replace(
            cfg.moe, backend=backend, gmm_block_rows=8))
    params = model_init(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
               for _ in range(3)]
    kw = dict(num_slots=2, max_tokens=32, arrival_steps=[0, 1, 3],
              paged=paged, page_size=8)
    res0 = serve_continuous(params, cfg, prompts, 5, **kw)
    res1 = serve_continuous(params, cfg, prompts, 5, mesh=_mesh((2, 2)), **kw)
    assert res1["stats"]["mesh"] == {"data": 2, "model": 2}
    assert res1["stats"]["paged"] == paged
    for rid in res0["tokens"]:
        np.testing.assert_array_equal(res0["tokens"][rid],
                                      res1["tokens"][rid])


@needs_mesh
def test_sharded_engine_preemption_bit_identical():
    """Page-pressure eviction + block-table-surgery resume with the pool
    sharded over the mesh: snapshots cross host<->device through SHARDED
    page stores, and the preempted-then-resumed streams must still equal
    the unsharded engine bit for bit, with the same preemption schedule."""
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_continuous
    from repro.models.model import model_init
    cfg = get_config("llama_moe_4_16", smoke=True)
    params = model_init(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32)
               for _ in range(3)]
    # two low-priority streams fill the 8 usable pages; the high-priority
    # arrival at step 6 must evict one and finish first
    kw = dict(num_slots=3, max_tokens=48, paged=True, page_size=8,
              num_pages=9, priorities=[5, 5, 0], arrival_steps=[0, 0, 6],
              preemption=True)
    res0 = serve_continuous(params, cfg, prompts, 24, **kw)
    res1 = serve_continuous(params, cfg, prompts, 24, mesh=_mesh((2, 2)),
                            **kw)
    assert res1["stats"]["mesh"] == {"data": 2, "model": 2}
    assert res1["stats"]["preemptions"] >= 1
    assert res0["stats"]["preemptions"] == res1["stats"]["preemptions"]
    assert res0["stats"]["statuses"] == res1["stats"]["statuses"] \
        == {"DONE": 3}
    for rid in res0["tokens"]:
        np.testing.assert_array_equal(res0["tokens"][rid],
                                      res1["tokens"][rid])


@needs_mesh
def test_sharded_prefix_share_bit_identical():
    """Prefix page sharing + expert-aware admission under the mesh: the
    shared-system-prompt workload (one donor prefill, cache-hit admissions
    mapping refcounted pages copy-on-write through SHARDED page stores,
    first tokens replayed from cached prefill logits) must equal the plain
    unsharded FIFO engine bit for bit, with the prefix index drained."""
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_continuous
    from repro.models.model import model_init
    cfg = get_config("llama_moe_4_16", smoke=True)
    params = model_init(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab_size, size=16, dtype=np.int32)
    kw = dict(num_slots=2, max_tokens=48, paged=True, page_size=8,
              arrival_steps=[0, 0, 3, 5])
    res0 = serve_continuous(params, cfg, [prompt] * 4, 8,
                            prefix_share=False, expert_aware=False, **kw)
    res1 = serve_continuous(params, cfg, [prompt] * 4, 8,
                            mesh=_mesh((2, 2)), prefix_share=True,
                            expert_aware=True, **kw)
    assert res1["stats"]["mesh"] == {"data": 2, "model": 2}
    assert res1["stats"]["prefix_hits"] == 3
    assert res1["stats"]["prefill_tokens_skipped"] == 3 * 16
    assert res1["stats"]["pages_in_use"] == 0
    assert res1["stats"]["statuses"] == {"DONE": 4}
    for rid in res0["tokens"]:
        np.testing.assert_array_equal(res0["tokens"][rid],
                                      res1["tokens"][rid])


@needs_mesh
def test_sharded_engine_crash_recovery_bit_identical(tmp_path):
    """Journal + snapshot + recover with the pool sharded over the mesh:
    SlotPool.snapshot crosses SHARDED page stores to host pickles and back,
    and the recovered engine (same mesh) must finish every stream exactly
    as the unsharded, uninterrupted engine would."""
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_continuous
    from repro.models.model import model_init
    from repro.serving import RequestStatus, ServingEngine
    cfg = get_config("llama_moe_4_16", smoke=True)
    params = model_init(jax.random.PRNGKey(5), cfg)
    mesh = _mesh((2, 2))
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
               for _ in range(3)]
    ref = serve_continuous(params, cfg, prompts, 10, num_slots=2,
                           max_tokens=48, paged=True, page_size=8)

    eng = ServingEngine(params, cfg, mesh=mesh, num_slots=2, max_tokens=48,
                        paged=True, page_size=8,
                        journal_dir=str(tmp_path), snapshot_every=4)
    rids = [eng.submit(p, 10) for p in prompts]
    for _ in range(6):
        eng.step()                       # crash point: live sharded slots
    assert eng.pool.num_active() > 0

    rec = ServingEngine.recover(str(tmp_path), params, cfg, mesh=mesh)
    fin = rec.run()
    assert rec.stats()["mesh"] == {"data": 2, "model": 2}
    assert rec.stats()["recoveries"] == 1
    for rid, ref_rid in zip(rids, sorted(ref["tokens"])):
        assert fin[rid].status is RequestStatus.DONE
        np.testing.assert_array_equal(np.asarray(fin[rid].tokens),
                                      ref["tokens"][ref_rid])


# ------------------------------------------------- single-device fallback

def test_mesh_suite_subprocess():
    """Tier-1 fallback: on a single-device host, re-run this file in a
    subprocess with 4 forced host devices so the mesh paths stay covered."""
    if MULTI:
        pytest.skip("mesh suite already ran in-process")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", __file__,
         "-k", "not subprocess"],
        env=env, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]

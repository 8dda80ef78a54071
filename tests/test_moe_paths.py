"""MoE execution-path equivalence: dense oracle == dispatch == grouped ==
Pallas grouped GEMM == expert-parallel shard_map — on BOTH backends
(`MoEConfig.backend`): the xla masked/capacity realization and the pallas
tile-dispatch grouped GEMM engine."""
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


def _pallas(e: MoEConfig, **kw) -> MoEConfig:
    return dataclasses.replace(e, backend="pallas", gmm_block_rows=8, **kw)


@pytest.fixture(scope="module")
def setup():
    e = MoEConfig(num_experts=8, top_k=2, d_expert=32, capacity_factor=8.0,
                  group_size=2)
    key = jax.random.PRNGKey(0)
    p = MOE.moe_init(key, 64, e, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 64)) * 0.3
    return e, p, x


def test_dispatch_matches_dense(setup):
    e, p, x = setup
    y_ref = MOE.dense_forward(p, x, e)
    y, aux = MOE.dispatch_forward(p, x, e)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    assert int(aux["dropped"]) == 0
    assert int(aux["counts"].sum()) == 24 * e.top_k


def test_group_forward_matches_dense(setup):
    e, p, x = setup
    goe = jnp.asarray(group_of_expert_from_groups(default_groups(e)))
    y_ref = MOE.dense_forward(p, x, e)
    y, aux = MOE.group_forward(p, x, e, goe, pool_factor=8.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    assert int(aux["dropped"]) == 0


def test_group_pooling_reduces_slots(setup):
    """C1: pooled group capacity < sum of per-expert capacities (the padding
    economy that multiplexing buys)."""
    e, p, x = setup
    goe = jnp.asarray(group_of_expert_from_groups(default_groups(e)))
    e_tight = MoEConfig(num_experts=8, top_k=2, d_expert=32,
                        capacity_factor=1.25, group_size=2)
    _, aux = MOE.group_forward(p, x, e_tight, goe, pool_factor=0.7)
    import math
    C_exp = max(1, math.ceil(24 * 2 / 8 * 1.25))
    assert int(aux["slots"]) < 8 * C_exp


def test_expert_choice_capacity_and_combine(setup):
    e, p, x = setup
    ec = MoEConfig(num_experts=8, top_k=2, d_expert=32,
                   routing="expert_choice")
    y, aux = MOE.expert_choice_forward(p, x, ec)
    C = MOE.ec_capacity(24, ec)
    assert aux["chosen_tokens"].shape == (8, C)
    y_ref = MOE.dense_forward(p, x, ec)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def test_pallas_moe_matches_dispatch(setup):
    e, p, x = setup
    from repro.core.routing import token_choice
    from repro.kernels.ops import moe_ffn_pallas
    r = token_choice(x, p["gate"], e.top_k)
    y_pallas = moe_ffn_pallas(x, r.expert_idx, r.weights, p["experts"],
                              e.num_experts, bn=8)
    y_ref, _ = MOE.dispatch_forward(p, x, e)
    y_ref = y_ref - MOE._shared_out(p, x)       # pallas path: routed part only
    np.testing.assert_allclose(np.asarray(y_pallas), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------- backend="pallas" engine

def test_backend_pallas_token_choice_matches_dense(setup):
    e, p, x = setup
    y_ref = MOE.dense_forward(p, x, e)
    y, aux = MOE.dispatch_forward(p, x, _pallas(e))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    assert int(aux["dropped"]) == 0
    assert int(aux["counts"].sum()) == 24 * e.top_k


def test_backend_pallas_group_matches_dense(setup):
    e, p, x = setup
    goe = jnp.asarray(group_of_expert_from_groups(default_groups(e)))
    y_ref = MOE.dense_forward(p, x, e)
    y, aux = MOE.group_forward(p, x, _pallas(e), goe, pool_factor=8.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    assert int(aux["dropped"]) == 0


def test_backend_pallas_group_drop_parity_with_xla(setup):
    """Pooled-capacity overflow must drop the SAME pairs on both backends:
    the pallas path realizes a drop as a zero combine weight, bit-equal to
    the xla path's buffer eviction."""
    e, p, x = setup
    goe = jnp.asarray(group_of_expert_from_groups(default_groups(e)))
    e_tight = dataclasses.replace(e, capacity_factor=1.25)
    y_x, a_x = MOE.group_forward(p, x, e_tight, goe, pool_factor=0.7)
    y_p, a_p = MOE.group_forward(p, x, _pallas(e_tight), goe, pool_factor=0.7)
    assert int(a_x["dropped"]) == int(a_p["dropped"]) > 0
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p),
                               rtol=1e-5, atol=1e-6)


def test_backend_pallas_expert_choice_matches_dense(setup):
    e, p, x = setup
    ec = MoEConfig(num_experts=8, top_k=2, d_expert=32,
                   routing="expert_choice")
    y_ref = MOE.dense_forward(p, x, ec)
    y, aux = MOE.expert_choice_forward(p, x, _pallas(ec))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    # GO-prefill aux parity with the xla realization
    _, aux_x = MOE.expert_choice_forward(p, x, ec)
    np.testing.assert_array_equal(np.asarray(aux["chosen_tokens"]),
                                  np.asarray(aux_x["chosen_tokens"]))
    np.testing.assert_allclose(np.asarray(aux["weighted_outputs"]),
                               np.asarray(aux_x["weighted_outputs"]),
                               rtol=1e-4, atol=1e-5)


def test_backend_pallas_non_aligned_dims():
    """Registry-style non-tile-aligned widths (d=48, d_expert=96 vs bn=8,
    bk/bf defaults) must lower cleanly through the padding path."""
    e = MoEConfig(num_experts=6, top_k=2, d_expert=96, capacity_factor=8.0)
    p = MOE.moe_init(jax.random.PRNGKey(3), 48, e, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (17, 48)) * 0.3
    y_ref = MOE.dense_forward(p, x, e)
    y, _ = MOE.dispatch_forward(p, x, _pallas(e))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def test_go_decode_selected_matches_dense_and_counts(setup):
    """C4 decode: the selected-experts grouped GEMM equals the dense
    fallback, and the planner's row counts prove only selected pairs were
    computed (vs B*E on the dense path)."""
    from repro.core.go_cache import go_cache_init, go_cache_step
    from repro.kernels.ops import go_selected_ffn
    e, p, x = setup
    B, E, k, d = 5, e.num_experts, e.top_k, 64
    gate = p["gate"]
    dense_fn = lambda xt: MOE.expert_ffn_all(p, xt)
    sel_fn = lambda xt, sel, g: go_selected_ffn(
        xt, sel, g, p["experts"], E, bn=8)[0]

    cache_d = cache_s = go_cache_init(B, E, k, d, jnp.float32)
    key = jax.random.PRNGKey(7)
    for t in range(k + 6):
        key, sub = jax.random.split(key)
        xt = jax.random.normal(sub, (B, d)) * 0.3
        r_d = go_cache_step(cache_d, xt, t, gate, dense_fn)
        r_s = go_cache_step(cache_s, xt, t, gate, contrib_fn=sel_fn)
        np.testing.assert_array_equal(np.asarray(r_d.selected),
                                      np.asarray(r_s.selected))
        np.testing.assert_allclose(np.asarray(r_d.y), np.asarray(r_s.y),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(r_d.cache.outputs),
                                   np.asarray(r_s.cache.outputs),
                                   rtol=1e-5, atol=1e-6)
        # planner computes exactly the selected rows (cache warm => sparse)
        g = jax.nn.softmax(xt.astype(jnp.float32) @ gate, axis=-1)
        _, plan = go_selected_ffn(xt, r_d.selected, g, p["experts"], E, bn=8)
        assert int(plan.counts[:E].sum()) == int(r_d.selected.sum())
        if t >= k:
            assert int(r_d.selected.sum()) < B * E
        cache_d, cache_s = r_d.cache, r_s.cache


@pytest.mark.parametrize("arch", ["llama_moe_4_16", "deepseek-moe-16b"])
def test_backend_pallas_model_forward_matches_xla(arch):
    """Whole-model parity on dropless MoE configs, B>1: covers the batched
    expert-choice flatten (llama_moe) AND the token-choice/grouped
    batch-flatten branch in blocks._ffn_apply (deepseek: shared experts +
    group_size=2). Dropless so per-sequence (xla) and batch-pooled (pallas)
    capacity semantics coincide."""
    from repro.configs.registry import get_config
    from repro.models.model import model_forward, model_init
    cfg = get_config(arch, smoke=True)
    if cfg.moe.routing == "token_choice":
        cfg = cfg.with_overrides(
            moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    cfgp = cfg.with_overrides(
        moe=dataclasses.replace(cfg.moe, backend="pallas", gmm_block_rows=8))
    params = model_init(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    x_xla, _ = model_forward(params, tokens, cfg, {})
    x_pal, _ = model_forward(params, tokens, cfgp, {})
    np.testing.assert_allclose(np.asarray(x_xla, np.float32),
                               np.asarray(x_pal, np.float32),
                               rtol=1e-4, atol=1e-5)


def test_backend_pallas_fails_fast_under_grad(setup):
    """Explicit backend="pallas" in a grad trace must die with the clear
    no-backward-pass message, not a missing-VJP error deep inside jax."""
    e, p, x = setup
    for fwd in (
        lambda pp: MOE.dispatch_forward(pp, x, _pallas(e))[0],
        lambda pp: MOE.expert_choice_forward(
            pp, x, _pallas(MoEConfig(num_experts=8, top_k=2, d_expert=32,
                                     routing="expert_choice")))[0],
    ):
        with pytest.raises(NotImplementedError, match="no backward pass"):
            jax.grad(lambda pp: fwd(pp).sum())(p)


def test_backend_pallas_grad_guard_via_loss_fn():
    """Whole-model: loss_fn with an explicit pallas backend fails fast under
    value_and_grad; backend="auto" still trains (pinned to xla)."""
    import dataclasses
    from repro.configs.registry import get_config
    from repro.models.model import loss_fn, model_init
    cfg = get_config("llama_moe_4_16", smoke=True)
    params = model_init(jax.random.PRNGKey(2), cfg)
    batch = {
        "tokens": jnp.zeros((1, 8), jnp.int32),
        "labels": jnp.zeros((1, 8), jnp.int32),
    }
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg)                       # auto -> xla: fine
    assert np.isfinite(float(loss))
    cfg_p = cfg.with_overrides(
        moe=dataclasses.replace(cfg.moe, backend="pallas", gmm_block_rows=8))
    with pytest.raises(NotImplementedError, match="no backward pass"):
        jax.value_and_grad(loss_fn, has_aux=True)(params, batch, cfg_p)


def test_backend_pallas_forward_not_blocked_by_guard(setup):
    """The guard must NOT trip on inference traces (plain jit)."""
    e, p, x = setup
    y, _ = jax.jit(lambda pp, xx: MOE.dispatch_forward(pp, xx, _pallas(e)))(
        p, x)
    assert np.all(np.isfinite(np.asarray(y)))


EP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import MoEConfig
from repro.core import moe as MOE
e = MoEConfig(num_experts=8, top_k=2, d_expert=32, capacity_factor=8.0)
key = jax.random.PRNGKey(0)
p = MOE.moe_init(key, 64, e, jnp.float32)
h = jax.random.normal(key, (4, 16, 64)) * 0.3
y_ref = jnp.stack([MOE.dispatch_forward(p, h[b], e)[0] for b in range(4)])
from repro.launch.mesh import make_mesh
with jax.set_mesh(make_mesh((2, 4), ("data", "model"))):
    y, aux = jax.jit(lambda p, h: MOE.moe_forward_ep(p, h, e))(p, h)
assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-4
assert int(aux["counts"].sum()) == 4 * 16 * 2
print("EP-OK")
"""


def test_ep_matches_dispatch_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", EP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "EP-OK" in out.stdout, out.stderr[-2000:]

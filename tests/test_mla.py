"""Latent attention (MLA), the leading dense layer and the sigmoid router of
the DeepSeek-V3 block (moonlight-16b-a3b), checked on the CPU at a smoke
size with seeded float32 weights against the benchmark's plain reference
(bench/references/mla_moe_decoder.py), and the configurations that were
there before kept bit for bit."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import mla_moe_decoder as REF
from repro.configs.registry import get_config, list_archs
from repro.core import moe as MOE
from repro.core import routing as R
from repro.kernels import paged_attn as PA
from repro.models import attention as ATT
from repro.models import model as M
from repro.serving import ServingEngine

# Smoke widths of the DeepSeek-V3 block, in the published keys: 3 layers,
# the first dense; 4 heads of 16 + 8 rotary dims, latent 32, values 16.
CONF = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "vocab_size": 256,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "routed_scaling_factor": 2.446, "rope_theta": 50000.0,
    "rms_norm_eps": 1e-5, "torch_dtype": "float32", "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "tie_word_embeddings": True,
}
SZ = REF.sizes(CONF)
PS, MAXT = 8, 64
SEED = 2**33 + 17


def program_cfg(mode="gather"):
    """The registry's smoke entry at the reference's sizes, tied head,
    dropless routing (capacity for every pair) and the given paged
    realization."""
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    assert (cfg.num_layers, cfg.d_model, cfg.resolved_head_dim(),
            cfg.kv_lora_rank, cfg.v_head_dim) == (3, 64, 24, 32, 16)
    e = cfg.moe
    return cfg.with_overrides(
        tie_embeddings=True, paged_attn=mode,
        moe=dataclasses.replace(e, capacity_factor=e.num_experts / e.top_k))


@pytest.fixture(scope="module")
def weights():
    return REF.make_weights(SZ, SEED)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, 256, 40).astype(np.int32)


def ref_logits(weights, toks):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.logits(weights, SZ, toks))


def serve_logits(params, cfg, toks, prompt_len, chunk):
    """Logits of every position from `prompt_len - 1` on, through the
    serving path on a paged latent pool: one slot, prompt chunks of `chunk`
    tokens into the pool's pages (or a one-shot prefill splatted into them
    when `chunk` is 0), then decode steps over the remaining tokens. Each
    call traces its own programs, so a test that alters the model code sees
    its alteration."""
    P = MAXT // PS
    pool = M.init_decode_state(cfg, 2, MAXT, per_slot_t=True,
                               paged=(1 + 2 * P, PS))
    row = jnp.asarray(1 + P + np.arange(P), jnp.int32)   # slot 1's pages
    if chunk:
        run_chunk = jax.jit(lambda p, st, x, s, v: M.prefill_chunk(
            p, st, x, cfg, s, v))
        state = {"t": jnp.zeros((), jnp.int32),
                 "block_table": row[None], "c_pages": pool["c_pages"]}
        for start in range(0, prompt_len, chunk):
            valid = min(chunk, prompt_len - start)
            part = np.pad(toks[start:start + valid], (0, chunk - valid))
            state, lg = run_chunk(params, state, jnp.asarray(part)[None],
                                  start, valid)
        pool["c_pages"] = state["c_pages"]
        src = {"t": state["t"]}
    else:
        src, lg = jax.jit(lambda p, x: M.prefill(p, x, cfg, max_len=MAXT))(
            params, jnp.asarray(toks[:prompt_len])[None])
    pool = M.write_decode_slot(pool, 1, src, row)
    step = jax.jit(lambda p, st, tok: M.serve_step(p, st, tok, cfg))
    out = [np.asarray(lg)[0]]
    for i in range(prompt_len, len(toks) - 1):
        lg, pool = step(params, pool, jnp.asarray([0, toks[i]], jnp.int32))
        out.append(np.asarray(lg)[1])
    return np.stack(out)


# f32 on both sides: the program and the reference differ by summation
# order alone (absorbed against plain attention, online against one-shot
# softmax, blocked prefill), measured at ~2e-6 on logits of magnitude ~1;
# dropping any part of the mathematics moves them by 1e-2 or more.
TOL = 2e-4


@pytest.mark.parametrize("mode", ["gather", "kernel"])
@pytest.mark.parametrize("chunk", [0, 8, 16], ids=["oneshot", "chunk8",
                                                   "chunk16"])
def test_serving_path_matches_reference(weights, tokens, mode, chunk):
    """Prefill (one-shot, or chunked into the latent pages), then decode
    through the latent pages, against the reference's full forward, on the
    gather and the kernel realization."""
    cfg = program_cfg(mode)
    got = serve_logits(weights, cfg, tokens, 21, chunk)
    want = ref_logits(weights, tokens)[20:len(tokens) - 1]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def _drop_rope_key(monkeypatch):
    real = ATT._mla_project

    def project(params, x, positions, cfg):
        q_nope, q_rope, row = real(params, x, positions, cfg)
        r = cfg.kv_lora_rank
        return q_nope, q_rope, row.at[..., r:].set(0)
    monkeypatch.setattr(ATT, "_mla_project", project)


def _drop_latent_norm(monkeypatch):
    monkeypatch.setattr(ATT, "rmsnorm", lambda p, x, eps: x)


def _drop_router_bias(monkeypatch):
    real = R.sigmoid_choice
    monkeypatch.setattr(R, "sigmoid_choice", lambda x, w, b, k: real(
        x, w, jnp.zeros_like(b), k))


@pytest.mark.parametrize("drop", [_drop_rope_key, _drop_latent_norm,
                                  _drop_router_bias],
                         ids=["rope-key", "latent-norm", "router-bias"])
def test_dropping_a_part_fails_the_comparison(weights, tokens, drop,
                                              monkeypatch):
    drop(monkeypatch)
    got = serve_logits(weights, program_cfg(), tokens, 21, 8)
    want = ref_logits(weights, tokens)[20:len(tokens) - 1]
    assert np.abs(got - want).max() > 50 * TOL


def test_absorbed_decode_equals_plain_form():
    """One decode step in the absorbed form (q W_UK against the latent rows,
    then W_UV) against the published form over the same sequence."""
    cfg = program_cfg()
    p = ATT.mla_init(jax.random.PRNGKey(3), cfg)
    S = 13
    x = jax.random.normal(jax.random.PRNGKey(4), (2, S, cfg.d_model))
    full, rows = ATT.mla_forward(p, x, cfg=cfg, positions=jnp.arange(S))
    cache = jnp.zeros((2, 2, 32, cfg.latent_row_width()))
    cache = cache.at[1, :, :S].set(rows)
    t = jnp.asarray([S - 1, 5])
    got, new = ATT.mla_decode(p, jnp.stack([x[0, S - 1:], x[1, 5:6]]), cache,
                              1, t, cfg=cfg)
    np.testing.assert_allclose(got[0, 0], full[0, S - 1], atol=1e-5)
    np.testing.assert_allclose(new[1, 0, S - 1], rows[0, S - 1], atol=1e-6)
    assert bool((new[0] == 0).all())            # only layer 1 was written
    short, _ = ATT.mla_forward(p, x[1:, :6], cfg=cfg, positions=jnp.arange(6))
    np.testing.assert_allclose(got[1, 0], short[0, 5], atol=1e-5)


def _check_latent_kernel(ps, P, t):
    B, H, W, rank, L = 4, 4, 128, 32, 3
    NP = 4 * P + 3
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    pages = jax.random.normal(k[0], (L, NP, ps, W))
    q = jax.random.normal(k[1], (B, H, W))
    t = jnp.asarray(t, jnp.int32)
    bt = np.zeros((B, P), np.int32)
    ids = iter(np.random.default_rng(1).permutation(np.arange(1, NP)))
    for b, tb in enumerate(np.asarray(t)):
        for j in range(P if b == 2 else tb // ps + 1):  # row 2 maps all
            bt[b, j] = next(ids)
    bt = jnp.asarray(bt)
    for layer in (0, 2):
        got = PA.mla_paged_decode(q, pages, layer, bt, t, rank=rank,
                                  scale=0.3, interpret=True)
        lat = pages[layer, bt].reshape(B, P * ps, W)
        s = jnp.einsum("bhw,bsw->bhs", q, lat) * 0.3
        s = jnp.where(jnp.arange(P * ps)[None, None] <= t[:, None, None], s,
                      -jnp.inf)
        want = jnp.einsum("bhs,bsc->bhc", jax.nn.softmax(s, -1),
                          lat[..., :rank])
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_latent_kernel_matches_gather_over_block_table():
    """The decode kernel (interpret mode) against a gather of each row's
    pages through its block table: ragged positions, a row on its first
    position, pages past each row's position left dead or null."""
    _check_latent_kernel(8, 5, [0, 9, 25, 17])


@pytest.mark.parametrize("ps,P,t", [(8, 20, [0, 9, 150, 130]),
                                    (4, 40, [127, 128, 159, 33]),
                                    (16, 24, [0, 300, 383, 127])],
                         ids=["two-steps-ragged", "step-edges", "chip-page"])
def test_latent_kernel_steps_over_several_pages(ps, P, t):
    """A grid step stages 128 // ps pages: rows whose position falls on
    either side of a step's edge, a last step only partly inside the block
    table, and whole steps past a row's position."""
    _check_latent_kernel(ps, P, t)


def test_sigmoid_router_by_hand():
    """Sigmoid scores choose with the bias and weigh without it,
    renormalised and scaled; a bias that flips the choice changes which
    experts are chosen, not what a chosen expert weighs."""
    e = get_config("moonlight-16b-a3b", smoke=True).moe       # E=8, k=2
    logits = np.array([[2.0, 1.0, 0.5, -1.0, 0.0, -2.0, 0.2, 0.1]])
    x = jnp.asarray(logits, jnp.float32)
    gate = jnp.eye(8, dtype=jnp.float32)
    sig = 1 / (1 + np.exp(-logits[0]))
    for bias, chosen in ((np.zeros(8), [0, 1]),
                         (np.array([0, 0, 0, 0.9, 0, 0, 0, 0]), [0, 3])):
        r = R.route(x, {"gate": gate, "bias": jnp.asarray(bias)}, e)
        assert sorted(np.asarray(r.expert_idx)[0].tolist()) == chosen
        w = dict(zip(np.asarray(r.expert_idx)[0].tolist(),
                     np.asarray(r.weights)[0].tolist()))
        norm = sig[chosen].sum()
        for i in chosen:
            assert w[i] == pytest.approx(2.446 * sig[i] / norm, rel=1e-6)


def test_sigmoid_moe_adds_shared_experts_unscaled():
    """The MoE layer's output is the scaled routed sum plus the shared
    experts, unweighted, as computed by hand."""
    cfg = program_cfg()
    e = cfg.moe
    p = MOE.moe_init(jax.random.PRNGKey(7), cfg.d_model, e, jnp.float32)
    p["bias"] = jax.random.normal(jax.random.PRNGKey(8), (e.num_experts,))
    x = jax.random.normal(jax.random.PRNGKey(9), (5, cfg.d_model))
    y, _ = MOE.dispatch_forward(p, x, e, capacity=5 * e.top_k)

    def ffn(bank, i, v):
        return (jax.nn.silu(v @ bank["wg"][i]) * (v @ bank["wi"][i])) @ \
            bank["wo"][i]
    s = jax.nn.sigmoid(x @ p["gate"])
    want = []
    for t in range(5):
        idx = np.argsort(-np.asarray(s[t] + p["bias"]))[:e.top_k]
        w = s[t, idx] / s[t, idx].sum() * e.routed_scale
        out = sum(w[j] * ffn(p["experts"], i, x[t]) for j, i in enumerate(idx))
        want.append(out + sum(ffn(p["shared"], i, x[t])
                              for i in range(e.num_shared_experts)))
    np.testing.assert_allclose(y, np.stack(want), atol=1e-4, rtol=1e-4)


def test_leading_dense_layer(weights, tokens):
    """Layer 0 is a SwiGLU of width d_ff and no router; the MoE stack holds
    the other layers; the forward runs it (the reference's logits change
    without it) and the parameter counts include it."""
    cfg = program_cfg()
    p = M.model_init(jax.random.PRNGKey(0), cfg)
    dense = p["dense_layers"]
    assert set(dense) == {"ln1", "attn", "ln2", "mlp"}
    assert dense["mlp"]["wi"].shape == (1, cfg.d_model, cfg.d_ff)
    assert jax.tree.leaves(p["layers"])[0].shape[0] == cfg.num_layers - 1
    # every weight but the norm scales and the router's selection bias
    weights_only = sum(
        a.size for path, a in jax.tree_util.tree_leaves_with_path(p)
        if path[-1].key not in ("scale", "bias"))
    assert cfg.param_count() == weights_only
    full = cfg.moe.num_experts + cfg.moe.num_shared_experts
    active = cfg.moe.top_k + cfg.moe.num_shared_experts
    idle = (full - active) * 3 * cfg.d_model * cfg.moe.d_expert * 2
    assert cfg.active_param_count() == cfg.param_count() - idle
    x, _ = M.model_forward(weights, jnp.asarray(tokens)[None], cfg)
    got = np.asarray(M.logits_from_hidden(weights, x, cfg))[0]
    np.testing.assert_allclose(got, ref_logits(weights, tokens), atol=TOL,
                               rtol=0)
    skipped = jax.tree.map(lambda a: a, weights)
    skipped["dense_layers"]["mlp"]["wo"] = jnp.zeros_like(
        skipped["dense_layers"]["mlp"]["wo"])
    assert np.abs(ref_logits(skipped, tokens) - got).max() > 50 * TOL
    with pytest.raises(ValueError, match="latent-attention"):
        M.model_init(jax.random.PRNGKey(0),
                     get_config("deepseek-moe-16b", smoke=True).with_overrides(
                         first_dense_layers=1))


def test_published_parameter_count():
    """moonlight-16b-a3b at its published sizes: about 16B parameters,
    about 3B of them active."""
    cfg = get_config("moonlight-16b-a3b")
    shapes = jax.eval_shape(lambda: M.model_init(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert abs(cfg.param_count() - n) < 1e6           # norm scales, biases
    assert 15.9e9 < cfg.param_count() < 16.0e9
    assert 2.9e9 < cfg.active_param_count() < 3.1e9


# ------------------------------------------------------------ the engine

def engine(cfg, weights, **kw):
    kw = {"num_slots": 3, "max_tokens": MAXT, "paged": True,
          "page_size": PS, "prefill_chunk": 16, "prompt_buckets": True,
          **kw}
    return ServingEngine(weights, cfg, **kw)


def serve(eng, prompts, gen=6, **kw):
    rids = [eng.submit(p, gen, **kw) for p in prompts]
    out = eng.run()
    return [list(out[r].tokens) for r in rids]


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(11)
    return [rng.integers(0, 256, n).astype(np.int32)
            for n in (5, 20, 37, 9, 44)]


def test_engine_serves_latent_pages_and_counts_them(weights, prompts):
    """ServingEngine on the paged latent pool, one-shot and chunked
    prefill: every stream is the reference's greedy choice, and the tick
    counters count latent pages."""
    eng = engine(program_cfg("kernel"), weights)
    rids = [eng.submit(p, 6) for p in prompts[:3]]
    eng.step()
    tick = eng.last_tick
    live = sum(int(t) // PS + 1 for t, o in
               zip(eng.pool.t_host - 1, eng.pool.owner) if o is not None)
    assert tick.decode_rows and (tick.live_pages, tick.grid_pages) == (
        live, 3 * MAXT // PS)
    out = eng.run()
    for p, rid in zip(prompts, rids):
        toks = out[rid].tokens
        ref = ref_logits(weights, np.concatenate([p, toks[:-1]]))[len(p) - 1:]
        gap = ref.max(-1) - ref[np.arange(len(toks)), toks]
        assert gap.max() < 1e-4
    assert eng.pool.alloc.check() is None


def test_preemption_moves_latent_pages(weights, prompts):
    """Under page pressure a higher-priority request evicts a stream; its
    latent pages go to the host and back, and every stream equals the one
    an engine with room for all serves."""
    cfg = program_cfg()
    roomy = serve(engine(cfg, weights), prompts)
    eng = engine(cfg, weights, num_pages=1 + 12, preemption=True)
    rids = [eng.submit(p, 6, priority=1) for p in prompts[:4]]
    for _ in range(3):
        eng.step()
    rids.append(eng.submit(prompts[4], 6, priority=0))
    out = eng.run()
    assert eng.preempted_total >= 1 and eng.resumed_total >= 1
    assert [list(out[r].tokens) for r in rids] == roomy


@pytest.mark.parametrize("kw", [{"kv_quant": "int8"}, {"prefix_share": True},
                                {"journal_dir": "JOURNAL"}],
                         ids=["kv_quant", "prefix_share", "journal"])
def test_lanes_without_latent_pool_refuse_at_build(weights, kw, tmp_path):
    if "journal_dir" in kw:
        kw = {"journal_dir": str(tmp_path / "j")}
    with pytest.raises(ValueError, match="latent"):
        engine(program_cfg(), weights, **kw)


def test_env_lanes_skip_a_latent_engine(weights, monkeypatch, tmp_path):
    for var in ("REPRO_KV_QUANT", "REPRO_PREFIX_SHARE"):
        monkeypatch.setenv(var, "1")
    monkeypatch.setenv("REPRO_JOURNAL_DIR", str(tmp_path))
    eng = engine(program_cfg(), weights)
    assert (eng.cfg.kv_quant, eng.prefix_share, eng.journal) == (
        "none", False, None)


# ------------------------------------------- the configurations before

# sha256 of the logits below, computed on the tree before latent attention
# and the sigmoid router were added: the softmax router and every smoke
# configuration keep their programs' results bit for bit.
LOGIT_SHA256 = {
    "xlstm-1.3b":
        "b8489ca044afe0bac232833e5b8217b71d4e72c0f46e49cb0147a009224ade9e",
    "starcoder2-3b":
        "644ffc80f7b58b91fd0030bfeb2a993fea0807164c169af3d03aefe1970ef904",
    "granite-8b":
        "644ffc80f7b58b91fd0030bfeb2a993fea0807164c169af3d03aefe1970ef904",
    "qwen2-7b":
        "644ffc80f7b58b91fd0030bfeb2a993fea0807164c169af3d03aefe1970ef904",
    "gemma3-27b":
        "b83a0916ccf196cf3e1efffb9a0247ed2d4b9c35b935f07b8459d9b2d1349bd8",
    "deepseek-moe-16b":
        "250edc29fbbc0c4a4984d5e7f6f9adc70fd8b7ff071e9da9868aa20da3cebda0",
    "granite-moe-3b-a800m":
        "e1dfce8a87c24068717309aa96da6c6888f3fbf6a19da83e786650248d2de153",
    "zamba2-1.2b":
        "f947c848b00c00fcffcf5d2176a09177fdba53da2b0c1f81be25047fd9a7af34",
    "llama-3.2-vision-90b":
        "483368f9a2ab906b276db79fffc7ebb504617f4843cbae046ffb2b8b694fc8e1",
    "whisper-base":
        "1f2128ff3c166084383c21beeeea222950b796e93b0b06cd69dbc3912d5349f4",
    "llama_moe_4_16":
        "69d2a79716abf1f6e62df6309ea2b5c7f0fe200edf4ba29d264a321add919392",
}


def _logits_of(arch):
    """Full-sequence logits of the arch's smoke config and, for the
    attention family, a prefill and three decode steps on a paged pool with
    the paged-attention kernel (interpret mode)."""
    cfg = get_config(arch, smoke=True)
    key = jax.random.PRNGKey(0)
    p = M.model_init(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    extras = {}
    if cfg.cross_attn_every:
        extras["image_embeds"] = jax.random.normal(
            key, (2, cfg.num_image_tokens, cfg.d_model)).astype(cfg.dtype)
    if cfg.encoder_layers:
        extras["audio_frames"] = jax.random.normal(
            key, (2, cfg.num_audio_frames, cfg.d_model)).astype(cfg.dtype)
    x, _ = M.model_forward(p, toks, cfg, extras)
    out = [M.logits_from_hidden(p, x, cfg)]
    if M.paged_supported(cfg):
        cfg = cfg.with_overrides(paged_attn="kernel")
        st, lg = M.prefill(p, toks[:1, :12], cfg, max_len=32)
        pool = M.init_decode_state(cfg, 2, 32, per_slot_t=True,
                                   paged=(9, 8))
        pool = M.write_decode_slot(pool, 1, st, jnp.arange(5, 9))
        out.append(lg)
        for i in range(12, 15):
            lg, pool = M.serve_step(p, pool, toks[:, i], cfg)
            out.append(lg)
    return [np.asarray(a, np.float32) for a in out]


def logits_digest(arch) -> str:
    h = hashlib.sha256()
    for a in _logits_of(arch):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("arch", [a for a in list_archs()
                                  if a != "moonlight-16b-a3b"])
def test_existing_configs_keep_their_logits(arch):
    assert logits_digest(arch) == LOGIT_SHA256[arch]

"""Pallas kernels vs pure-jnp oracles (interpret=True), swept over
shapes/dtypes per the kernel contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st
from repro.kernels import ref
from repro.kernels.go_topk import go_topk_update
from repro.kernels.moe_gmm import gmm, gmm_scaled, gmm_swiglu
from repro.launch.mesh import make_mesh

SWEEP = [
    # (N, K, F, E, bn, dtype)
    (128, 256, 128, 2, 64, jnp.float32),
    (256, 512, 256, 4, 128, jnp.float32),
    (256, 512, 384, 8, 64, jnp.float32),
    (512, 1024, 512, 8, 128, jnp.bfloat16),
    (128, 512, 128, 3, 32, jnp.float32),
    # non-tile-aligned K/F (registry d=48/96-style dims + K > bk non-divisible)
    (128, 48, 96, 4, 32, jnp.float32),
    (64, 688, 172, 4, 32, jnp.float32),
    # deepseek's d_expert: K not a multiple of the default bk (a full block)
    (64, 1408, 128, 4, 32, jnp.float32),
]


def _with_layers(cases):
    """Each case on its own [E, K, F] bank (layer None, the case's own id),
    then read from a stacked [L, E, K, F] bank at layer 0 and at L - 1."""
    out = []
    for c in cases:
        cid = "-".join(getattr(v, "__name__", str(v)) for v in c)
        out.append(pytest.param(*c, None, id=cid))
        out += [pytest.param(*c, l, id=f"{cid}-{n}")
                for l, n in ((0, "first"), (2, "last"))]
    return out


def _stack(w, layer, key, L=3):
    """w as layer `layer` of an L-layer stack of random banks (None: w)."""
    if layer is None:
        return w
    stack = jax.random.normal(key, (L,) + w.shape).astype(w.dtype)
    return stack.at[layer].set(w)


def _same_as_bank(fn, banks, layer):
    """fn on the stacked banks at `layer`, required bit-equal to fn on the
    layer's own banks; returns the stacked call's result."""
    out = fn(*banks, layer=layer)
    if layer is not None:
        want = fn(*(w[layer] for w in banks), layer=None)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    return out


@pytest.mark.parametrize("N,K,F,E,bn,dtype,layer", _with_layers(SWEEP))
def test_gmm_sweep(N, K, F, E, bn, dtype, layer):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(N + K), 3)
    x = (jax.random.normal(k1, (N, K)) * 0.1).astype(dtype)
    w = (jax.random.normal(k2, (E, K, F)) * 0.05).astype(dtype)
    te = jax.random.randint(k3, (N // bn,), 0, E)
    y = _same_as_bank(
        lambda w_, layer: gmm(x, w_, te, layer=layer, bn=bn, interpret=True),
        (_stack(w, layer, k1),), layer)
    y_ref = ref.gmm_ref(x, w, te, bn)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("N,K,F,E,bn,dtype,layer", _with_layers(SWEEP))
def test_gmm_swiglu_sweep(N, K, F, E, bn, dtype, layer):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(N + F), 4)
    x = (jax.random.normal(k1, (N, K)) * 0.1).astype(dtype)
    wg = (jax.random.normal(k2, (E, K, F)) * 0.05).astype(dtype)
    wi = (jax.random.normal(k3, (E, K, F)) * 0.05).astype(dtype)
    te = jax.random.randint(k4, (N // bn,), 0, E)
    h = _same_as_bank(
        lambda g, i, layer: gmm_swiglu(x, g, i, te, layer=layer, bn=bn,
                                       interpret=True),
        (_stack(wg, layer, k1), _stack(wi, layer, k2)), layer)
    h_ref = ref.gmm_swiglu_ref(x, wg, wi, te, bn)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,E,k", [(1, 4, 2), (4, 16, 4), (8, 64, 6), (3, 40, 8)])
def test_go_topk_sweep(B, E, k):
    key = jax.random.PRNGKey(B * E + k)
    k1, k2, k3 = jax.random.split(key, 3)
    sp = jax.random.normal(k1, (B, E, k))
    tp = jax.random.randint(k2, (B, E, k), 0, 1000)
    sn = jax.random.normal(k3, (B, E))
    got = go_topk_update(sp, tp, sn, 1001, interpret=True)
    want = ref.go_topk_ref(sp, tp, sn, 1001)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _check_plan(ef, E, bn):
    """Row/tile invariants of plan_tile_dispatch for a given distribution."""
    from repro.kernels.ops import plan_tile_dispatch
    ef = jnp.asarray(ef, jnp.int32)
    N = ef.shape[0]
    plan = plan_tile_dispatch(ef, E, bn)
    dest = np.asarray(plan.dest)
    te = np.asarray(plan.tile_expert)
    tv = np.asarray(plan.tile_valid)
    # all rows land in bounds, no two pairs share a slot
    assert dest.max() < plan.n_pad
    assert len(np.unique(dest)) == len(dest)
    # every tile's rows belong to the tile's expert, and that tile is valid
    e_of_row = np.asarray(ef)
    for r, dst in enumerate(dest):
        assert te[dst // bn] == e_of_row[r]
        assert tv[dst // bn]
    # row_valid marks exactly the occupied slots; counts account every pair
    assert int(np.asarray(plan.row_valid).sum()) == N
    assert int(np.asarray(plan.counts).sum()) == N
    # valid tiles cover exactly the tile-padded runs (skipped tiles = padding)
    padded = (np.asarray(plan.counts) + bn - 1) // bn * bn
    assert int(tv.sum()) == int(padded.sum() // bn)
    return plan


def test_tile_plan_properties():
    key = jax.random.PRNGKey(0)
    ef = jax.random.randint(key, (200,), 0, 8)
    _check_plan(ef, 8, 32)


@pytest.mark.parametrize("case", ["all_one_expert", "empty_experts",
                                  "single_pair", "last_expert_only"])
def test_tile_plan_adversarial(case):
    """Planner invariants under adversarial expert distributions."""
    E, bn = 8, 32
    if case == "all_one_expert":
        ef = np.full(200, 3)
    elif case == "empty_experts":
        ef = np.concatenate([np.full(100, 0), np.full(100, 7)])
    elif case == "single_pair":
        ef = np.array([5])
    else:
        ef = np.full(33, E - 1)
    plan = _check_plan(ef, E, bn)
    if case == "all_one_expert":
        assert int(np.asarray(plan.tile_valid).sum()) == -(-200 // bn)


def test_gmm_scaled_matches_ref():
    """Fused-combine gmm: per-row weights applied in-kernel, fp32 out."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(9), 4)
    N, K, F, E, bn = 128, 96, 80, 4, 32
    x = jax.random.normal(k1, (N, K)) * 0.1
    w = jax.random.normal(k2, (E, K, F)) * 0.05
    te = jax.random.randint(k3, (N // bn,), 0, E)
    s = jax.random.normal(k4, (N, 1))
    y = gmm_scaled(x, w, te, None, s, bn=bn, interpret=True)
    assert y.dtype == jnp.float32
    y_ref = ref.gmm_scaled_ref(x, w, te, s, bn)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("K", [96, 1408])
@pytest.mark.parametrize("kernel,fused", [("scaled", False),
                                          ("scaled", True),
                                          ("swiglu", True)])
def test_gmm_stacked_bank(kernel, fused, K, layer):
    """The combine and lane-pair variants read layer `layer` of a stacked
    [L, E, K, F] bank bit-equal to the same call on that layer's bank,
    including K = 1408, which the default bk (512) does not divide."""
    N, F, E, bn = 64, 128, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(K + layer), 6)
    x = jax.random.normal(ks[0], (N, K)) * 0.1
    wa = jax.random.normal(ks[1], (3, E, K, F)) * 0.05
    wb = jax.random.normal(ks[2], (3, E, K, F)) * 0.05
    te = jnp.sort(jax.random.randint(ks[3], (N // bn,), 0, E))
    kw = dict(bn=bn, interpret=True)
    if fused:
        # a straddle tile wherever the next tile starts on another expert
        kw.update(tile_expert2=jnp.roll(te, -1),
                  row_sel=(jnp.arange(N) % bn < bn // 2)[:, None])
    if kernel == "scaled":
        s = jax.random.normal(ks[4], (N, 1))
        y = _same_as_bank(lambda w, layer: gmm_scaled(
            x, w, te, None, s, layer=layer, **kw), (wa,), layer)
        if not fused:
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(ref.gmm_scaled_ref(
                    x, wa[layer], te, s, bn)), rtol=2e-5, atol=2e-5)
    else:
        _same_as_bank(lambda g, i, layer: gmm_swiglu(
            x, g, i, te, layer=layer, **kw), (wa, wb), layer)


@pytest.mark.parametrize("K,F,mesh,want", [
    (1536, 512, False, (512, 128, True)),     # defaults divide: in place
    (1408, 2048, False, (1408, 128, True)),   # K whole: one 1408 block
    (4096, 688, False, (512, 688, True)),     # F whole: one 688 block
    (5000, 4096, False, (512, 128, False)),   # too big whole: slice, pad
    (1536, 512, True, (512, 128, False)),     # GSPMD mesh: slice
])
def test_bank_layout_rule(K, F, mesh, want):
    """Blocks divide the bank: the default, else the whole dimension; a
    whole-dimension block that would not fit VMEM, or an ambient GSPMD
    mesh, leaves the stacked bank sliced to its layer."""
    from repro.kernels.moe_gmm import _bank_layout
    got = []

    def layout(w):
        banks, layer, bk, bf = _bank_layout((w,), 1, 512, 128)
        got.append((bk, bf, banks[0].ndim == 4 and layer is not None))
        return banks[0]

    w = jax.ShapeDtypeStruct((2, 4, K, F), jnp.bfloat16)
    if mesh:
        with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
            jax.eval_shape(layout, w)
    else:
        jax.eval_shape(layout, w)
    assert got == [want]


def test_gmm_tile_valid_skips_compute():
    """Invalid tiles must produce zero rows (their MXU work is skipped)."""
    from repro.kernels.moe_gmm import gmm
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    N, K, F, E, bn = 64, 32, 32, 2, 16
    x = jax.random.normal(k1, (N, K)) * 0.1
    w = jax.random.normal(k2, (E, K, F)) * 0.05
    te = jnp.array([0, 1, 0, 1])
    tv = jnp.array([1, 0, 1, 0])
    y = gmm(x, w, te, tv, bn=bn, interpret=True)
    y_full = gmm(x, w, te, None, bn=bn, interpret=True)
    np.testing.assert_array_equal(np.asarray(y[bn:2 * bn]), 0.0)
    np.testing.assert_array_equal(np.asarray(y[3 * bn:]), 0.0)
    np.testing.assert_allclose(np.asarray(y[:bn]), np.asarray(y_full[:bn]))


# ------------------------------------------------- local-expert tile plans

@pytest.mark.parametrize("lo,E_loc", [(0, 4), (4, 4), (2, 2), (6, 2)])
def test_tile_plan_local_window(lo, E_loc):
    """plan_tile_dispatch with expert_offset/num_local: local pairs tile up
    against the LOCAL lane index; every non-local pair is ELIDED — it takes
    no buffer row (dest == the n_pad sentinel) and no tile, so the packed
    buffer scales with the shard's local traffic — the per-shard EP plan."""
    from repro.kernels.ops import plan_tile_dispatch
    E, bn = 8, 8
    key = jax.random.PRNGKey(lo * 10 + E_loc)
    ef = jax.random.randint(key, (100,), 0, E).astype(jnp.int32)
    plan = plan_tile_dispatch(ef, E, bn, expert_offset=lo, num_local=E_loc)
    dest = np.asarray(plan.dest)
    te = np.asarray(plan.tile_expert)
    tv = np.asarray(plan.tile_valid)
    ef_np = np.asarray(ef)
    local = (ef_np >= lo) & (ef_np < lo + E_loc)
    assert te.max() < E_loc                    # indexes the LOCAL bank only
    for r in range(100):
        if local[r]:
            tile = dest[r] // bn
            assert tv[tile] and te[tile] == ef_np[r] - lo
        else:
            assert dest[r] == plan.n_pad       # elided: no row, no tile
    # local rows are unique; elided pairs all share the sentinel
    assert len(np.unique(dest[local])) == int(local.sum())
    # counts: planned lanes = local experts, then the drop-lane tally
    cnt = np.asarray(plan.counts)
    assert cnt.shape == (E_loc + 1,)
    for j in range(E_loc):
        assert cnt[j] == int((ef_np == lo + j).sum())
    assert cnt[E_loc] == int((~local).sum())
    # row_valid marks exactly the COMPUTED occupied slots, and the occupied
    # tile count tracks the per-lane padded runs (nothing planned for drops)
    assert int(np.asarray(plan.row_valid).sum()) == int(local.sum())
    padded = (cnt[:E_loc] + bn - 1) // bn * bn
    assert int(np.asarray(plan.occupied)) == int(padded.sum() // bn)


def test_moe_ffn_fused_local_window_psums_to_global():
    """Sharded-plan equivalence without a mesh: running moe_ffn_fused once
    per local-expert window over the SAME pairs and summing the partial
    outputs equals the single global plan (what the EP shard body psums)."""
    from repro.kernels.ops import moe_ffn_fused
    E, T, d, de, k, bn = 8, 12, 16, 24, 2, 4
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 5)
    bank = {
        "wg": jax.random.normal(ks[0], (E, d, de)) * 0.1,
        "wi": jax.random.normal(ks[1], (E, d, de)) * 0.1,
        "wo": jax.random.normal(ks[2], (E, de, d)) * 0.1,
    }
    x = jax.random.normal(ks[3], (T, d)) * 0.3
    ef = jax.random.randint(ks[4], (T * k,), 0, E).astype(jnp.int32)
    wf = jnp.abs(jax.random.normal(ks[4], (T * k,)))
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    y_full, _, _ = moe_ffn_fused(x, tok, ef, wf, bank, E, T, bn=bn)
    for M in (2, 4):
        E_loc = E // M
        y_sum = 0
        for i in range(M):
            loc = jax.tree.map(lambda a: a[i * E_loc:(i + 1) * E_loc], bank)
            y_i, _, plan = moe_ffn_fused(x, tok, ef, wf, loc, E, T, bn=bn,
                                         expert_offset=i * E_loc,
                                         num_local=E_loc)
            assert int(plan.counts[:E_loc].sum()) == int(
                ((np.asarray(ef) // E_loc) == i).sum())
            y_sum = y_sum + y_i
        np.testing.assert_allclose(np.asarray(y_sum), np.asarray(y_full),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------ go_selected_ffn drop-lane masking

def _go_selected_case(selected, bn):
    """Drop-lane masking invariant: unselected pairs must come back as EXACT
    zero rows (no garbage scatter), selected pairs must match the dense
    oracle — regardless of how selection aligns with the tile boundary."""
    from repro.kernels.ops import go_selected_ffn
    B, E = selected.shape
    d, de = 16, 24
    ks = jax.random.split(jax.random.PRNGKey(int(selected.sum()) + bn), 5)
    bank = {
        "wg": jax.random.normal(ks[0], (E, d, de)) * 0.1,
        "wi": jax.random.normal(ks[1], (E, d, de)) * 0.1,
        "wo": jax.random.normal(ks[2], (E, de, d)) * 0.1,
    }
    x = jax.random.normal(ks[3], (B, d)) * 0.3
    g = jax.nn.softmax(jax.random.normal(ks[4], (B, E)), axis=-1)
    contrib, plan = go_selected_ffn(x, jnp.asarray(selected), g, bank, E,
                                    bn=bn)
    got = np.asarray(contrib)
    # dense oracle: per-pair SwiGLU FFN weighted by g
    h = jax.nn.silu(jnp.einsum("bd,edf->bef", x, bank["wg"])) * jnp.einsum(
        "bd,edf->bef", x, bank["wi"])
    eo = jnp.einsum("bef,efd->bed", h, bank["wo"])
    want = np.asarray(g[..., None] * eo)
    np.testing.assert_array_equal(got[~selected], 0.0)
    np.testing.assert_allclose(got[selected], want[selected],
                               rtol=1e-5, atol=1e-6)
    assert int(plan.counts[:E].sum()) == int(selected.sum())


@pytest.mark.parametrize("case", ["tail_tile_all_dropped", "none_selected",
                                  "one_selected", "all_selected_unaligned"])
def test_go_selected_adversarial_tail(case):
    """The all-dropped-tail-tile family: the selected-row count is NOT a
    multiple of bn and every pair of the trailing tile(s) is dropped."""
    B, E, bn = 3, 4, 8
    sel = np.zeros((B, E), bool)
    if case == "tail_tile_all_dropped":
        # 5 selected rows (5 % 8 != 0); the remaining 7 pairs fill the drop
        # lane, so its final tile holds ONLY dropped pairs
        sel[0, :2] = sel[1, :2] = sel[2, 0] = True
    elif case == "one_selected":
        sel[1, 2] = True
    elif case == "all_selected_unaligned":
        sel[:] = True                        # 12 pairs, 12 % 8 != 0
    _go_selected_case(sel, bn)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 12 - 1), st.sampled_from([4, 8]))
def test_go_selected_mask_property(bits, bn):
    """Property sweep over arbitrary selection masks (incl. the empty and
    full masks): zeros where unselected, oracle where selected."""
    sel = np.array([(bits >> i) & 1 for i in range(12)],
                   bool).reshape(3, 4)
    _go_selected_case(sel, bn)


# ------------------------------------------------- interpret-mode resolution

def test_default_interpret_resolves_from_lowering_context(monkeypatch):
    """default_interpret keys off the ACTUAL lowering target: the active
    mesh's devices when inside one, the host default backend otherwise —
    a forced CPU mesh on a (faked) TPU host must pick the interpreter."""
    from repro.kernels import moe_gmm
    assert moe_gmm.default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(moe_gmm.jax, "default_backend", lambda: "tpu")
    assert moe_gmm.default_interpret() is False      # no mesh: host decides
    mesh = make_mesh((1, 1), ("data", "model"))      # CPU devices
    with jax.set_mesh(mesh):
        assert moe_gmm.default_interpret() is True   # mesh devices decide
    assert moe_gmm.default_interpret() is False      # context popped


def test_default_block_rows_follows_lowering_context(monkeypatch):
    from repro.kernels import moe_gmm, ops
    monkeypatch.setattr(moe_gmm.jax, "default_backend", lambda: "tpu")
    assert ops.default_block_rows() == 128
    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        assert ops.default_block_rows() == 8         # CPU mesh: small tiles


@pytest.mark.parametrize("B,S,H,hd", [(1, 16, 2, 8), (2, 24, 4, 16),
                                      (3, 33, 4, 32)])
def test_slstm_seq_kernel(B, S, H, hd):
    """Fused sLSTM sequence kernel vs the model's per-step cell (§Perf Cell A
    consequence: state + recurrent weights VMEM-resident across the scan)."""
    import jax
    from repro.kernels.slstm_cell import slstm_seq
    from repro.models.xlstm import _slstm_cell

    key = jax.random.PRNGKey(B * S)
    u = jax.random.normal(key, (B, S, 4 * H * hd)) * 0.5
    r = jax.random.normal(jax.random.PRNGKey(1), (4, H, hd, hd)) / (hd ** 0.5)
    params = {"r": r}
    st = {k: jnp.zeros((B, H, hd)) for k in ("c", "n", "m", "h")}
    hs = []
    for t in range(S):
        st = _slstm_cell(params, u[:, t], st, H, hd)
        hs.append(st["h"].reshape(B, -1))
    ref = jnp.stack(hs, axis=1)
    got = slstm_seq(u, r, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)

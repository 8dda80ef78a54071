"""A cell over several chips, at a smoke size on the CPU's host devices: the
weights made already laid out by the program's serve-mode rules with the
same bits, the engine over the program's mesh, the reference's expert sum
split alike, `correct` true and the control's false; and a one-chip cell
taking the path it took before cells could span chips.

A test that needs four devices runs in this process when it has them, and
otherwise in a child process with four forced host devices
(`smoke.ran_on_more_devices`)."""
import hashlib
import os
import time

import jax
import numpy as np
import pytest

from bench import serve as SV
from bench.references import moe_decoder as REF
from bench.run import run_cell
from bench.spec import ROOT, load_cell, load_json
from bench.tests.smoke import (PEAKS, ran_on_more_devices, smoke_config,
                               smoke_of)

BENCHMARK = load_json(os.path.join(ROOT, "BENCHMARK.json"))
ONE_CHIP = [w["name"] for w in BENCHMARK["workloads"] if w["chips"] == 1]
FOUR_CHIPS = [w["name"] for w in BENCHMARK["workloads"] if w["chips"] == 4]
SEED = 2 ** 40 + 12345

# sha256 of the bfloat16 smoke weights' bytes, leaf by leaf, at SEED, as
# make_weights made them before a cell could span chips
WEIGHTS_SHA256 = {
    "granite-moe-3b-a800m":
        "301169dd036795c00f43aefc4998cb1c25f17a0f59fcc5e195ffcc13c7f0f5eb",
    "deepseek-moe-16b-7l":
        "d2a667416d1e0f6ad336116c51954e73f4c35cffc43d342d4b1bfb2a9953e493",
}

# sha256 of the float32 reference logits (and of the float8 control's) of
# 48 seeded tokens over those weights, as the reference computed them before
# it could split its expert sum over a mesh
LOGITS_SHA256 = {
    ("granite-moe-3b-a800m", False):
        "444cd28751dd9981d45684e2999c1713693e3872dd47721abc264351bad2c866",
    ("granite-moe-3b-a800m", True):
        "7b5792b9dd1d47d90f629bc1b4046deb58cc7b6a4632ed6c55a42057f882faf6",
    ("deepseek-moe-16b-7l", False):
        "a3bedffb1b01ae0c8aa88166e09574a6b48938b3757e964b7d107e80b50e1954",
    ("deepseek-moe-16b-7l", True):
        "21beb233a436983394a4776b44fb2b2f41102ec2943700ed3b4c3cb5e1a8b980",
}


def engines(monkeypatch) -> list:
    """The engines the harness builds, in order."""
    built, make = [], SV.make_engine

    def keep(*a, **kw):
        built.append(make(*a, **kw))
        return built[-1]
    monkeypatch.setattr(SV, "make_engine", keep)
    return built


def run(cell, trace=False, **kw):
    return run_cell(cell, SEED, 2.0, trace, peaks=PEAKS,
                    t_proc=time.monotonic(), chip=False, **kw)


# ------------------------------------------------------------- one chip

@pytest.mark.parametrize("name", sorted(WEIGHTS_SHA256))
def test_one_chip_weights_keep_their_bits(name):
    sz = REF.sizes(smoke_config(name, dtype="bfloat16"))
    h = hashlib.sha256()
    for a in jax.tree.leaves(REF.make_weights(sz, SEED)):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[name]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_one_chip_cell_builds_its_engine_without_a_mesh(name, monkeypatch):
    built = engines(monkeypatch)
    out = run(smoke_of(load_cell(name)))
    assert out["correct"] is True
    assert [e.mesh for e in built] == [None]
    assert all(len(a.devices()) == 1
               for a in jax.tree.leaves(built[0].params))


@pytest.mark.parametrize("name,fp8", sorted(LOGITS_SHA256),
                         ids=lambda v: str(v))
def test_one_chip_reference_keeps_its_logits(name, fp8):
    """The one-chip cells' gaps are read from the same sums, bit for bit."""
    sz = REF.sizes(smoke_config(name, dtype="bfloat16"))
    w = REF.make_weights(sz, SEED)
    toks = np.random.default_rng(0).integers(0, sz["vocab"], 48)
    got = np.asarray(REF.logits(w, sz, toks, fp8=fp8))
    assert got.dtype == np.float32
    assert hashlib.sha256(got.tobytes()).hexdigest() == \
        LOGITS_SHA256[name, fp8]


def test_one_chip_reference_runs_the_whole_layer():
    """Weights on one device go through `_layer` layer by layer, the path
    the one-chip cells' gaps were read on."""
    sz = REF.sizes(smoke_config("deepseek-moe-16b-7l"))
    w = REF.make_weights(sz, SEED)
    assert REF._expert_split(w) is None
    toks = np.arange(20, dtype=np.int32) % sz["vocab"]
    frozen = tuple(sorted(sz.items()))
    x = w["embed"][toks].astype(np.float32) * sz["emb_mult"]
    for i in range(sz["layers"]):
        x = REF._layer(x, jax.tree.map(lambda a: a[i], w["layers"]),
                       frozen=frozen, fp8=False)
    want = REF._head(x, w["final_norm"]["scale"], w["embed"], frozen=frozen,
                     fp8=False)
    np.testing.assert_array_equal(np.asarray(REF.logits(w, sz, toks)),
                                  np.asarray(want))


# ----------------------------------------------------------- four chips

@pytest.mark.parametrize("name", FOUR_CHIPS)
def test_four_chip_cell_end_to_end(name, monkeypatch, request):
    """The expert bank split over "model" on four devices, `correct` true,
    nothing failed, and the float8 control not correct; traced, as the
    run that reads the per-layer metrics is."""
    if ran_on_more_devices(4, request):
        return
    built = engines(monkeypatch)
    out = run(smoke_of(load_cell(name)), trace=True, control=True)
    eng, = built
    assert dict(eng.mesh.shape) == {"data": 1, "model": 4}
    wg = eng.params["layers"]["moe"]["experts"]["wg"]
    assert wg.sharding.spec[1] == "model" and len(wg.devices()) == 4
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0
    assert out["control"]["correct"] is False, out["control"]["compared"]
    assert out["device"]["count"] == 4


@pytest.mark.parametrize("name", FOUR_CHIPS)
def test_weights_made_laid_out_keep_their_bits(name, request):
    """The same key gives the same bits laid out over the mesh as on one
    device, and no leaf is whole on one device."""
    if ran_on_more_devices(4, request):
        return
    cell = smoke_of(load_cell(name), dtype="bfloat16")
    sz = REF.sizes(cell.config)
    cfg = SV.program_config(cell.config)
    mesh = SV.make_mesh(cell.chips)
    shapes = jax.eval_shape(lambda: REF.make_weights(sz, SEED))
    split = REF.make_weights(sz, SEED, SV.weight_shardings(shapes, cfg, mesh))
    whole = REF.make_weights(sz, SEED)
    for a, b in zip(jax.tree.leaves(split), jax.tree.leaves(whole)):
        assert a.addressable_shards[0].data.size < a.size
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", FOUR_CHIPS)
def test_split_reference_sums_what_the_whole_one_does(name, request):
    """The reference over weights split by experts gives the logits of the
    reference over whole weights, to float32 rounding (the expert sum is
    added in another order)."""
    if ran_on_more_devices(4, request):
        return
    cell = smoke_of(load_cell(name), dtype="bfloat16")
    sz = REF.sizes(cell.config)
    cfg = SV.program_config(cell.config)
    mesh = SV.make_mesh(cell.chips)
    shapes = jax.eval_shape(lambda: REF.make_weights(sz, SEED))
    split = REF.make_weights(sz, SEED, SV.weight_shardings(shapes, cfg, mesh))
    whole = REF.make_weights(sz, SEED)
    assert REF._expert_split(split)[1] == "model"
    toks = np.random.default_rng(0).integers(0, sz["vocab"], 48)
    for fp8 in (False, True):
        got = np.asarray(REF.logits(split, sz, toks, fp8=fp8))
        want = np.asarray(REF.logits(whole, sz, toks, fp8=fp8))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

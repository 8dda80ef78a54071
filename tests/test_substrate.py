"""Substrate: data pipeline, optimizer, checkpointing, fault runtime,
elastic re-meshing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.optim.adamw import (adamw_init, adamw_update, clip_by_global_norm,
                               cosine_lr)
from repro.runtime.elastic import remesh_plan
from repro.runtime.fault import RestartRequired, StepSupervisor


# ----------------------------------------------------------------- pipeline

def test_data_deterministic_and_seekable():
    cfg = DataConfig(vocab_size=128, seq_len=32, global_batch=4, seed=7)
    c1, c2 = SyntheticCorpus(cfg), SyntheticCorpus(cfg)
    b_a = c1.batch(5)
    b_b = c2.batch(5)                       # fresh instance, same step
    np.testing.assert_array_equal(b_a["tokens"], b_b["tokens"])
    assert not np.array_equal(c1.batch(6)["tokens"], b_a["tokens"])


def test_data_shards_disjoint_and_cover():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=8, seed=0)
    c = SyntheticCorpus(cfg)
    full = c.batch(3)["tokens"]
    parts = [c.batch(3, shard=i, num_shards=4)["tokens"] for i in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), full)


def test_labels_are_next_tokens():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=2, seed=0)
    b = SyntheticCorpus(cfg).batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ---------------------------------------------------------------- optimizer

def test_adamw_converges_on_quadratic():
    params = {"w": jnp.array([3.0, -2.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(params, grads, state, lr=0.05,
                                        weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_grad_clip():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    total = jnp.sqrt(jnp.sum(jnp.square(clipped["a"])))
    np.testing.assert_allclose(float(total), 1.0, rtol=1e-5)


def test_cosine_schedule_shape():
    assert float(cosine_lr(0, base_lr=1.0, warmup=10, total=100)) < 0.2
    peak = float(cosine_lr(10, base_lr=1.0, warmup=10, total=100))
    end = float(cosine_lr(99, base_lr=1.0, warmup=10, total=100))
    assert peak > 0.9 and end < peak


# --------------------------------------------------------------- checkpoint

def test_ckpt_roundtrip_and_latest(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    ckpt.save(str(tmp_path), 10, tree)
    ckpt.save(str(tmp_path), 20, tree)
    assert ckpt.latest_step(str(tmp_path)) == 20
    back = ckpt.restore(str(tmp_path), 10, tree)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))


def test_uncommitted_ckpt_ignored(tmp_path):
    tree = {"a": jnp.ones(3)}
    ckpt.save(str(tmp_path), 1, tree)
    # fake a crashed write: directory without COMMITTED marker
    os.makedirs(tmp_path / "step_00000002")
    assert ckpt.latest_step(str(tmp_path)) == 1


def test_ckpt_async_writer(tmp_path):
    tree = {"a": jnp.arange(1000)}
    w = ckpt.save(str(tmp_path), 5, tree, async_=True)
    w.wait()
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_ckpt_gc_keeps_last(tmp_path):
    tree = {"a": jnp.ones(2)}
    for s in range(6):
        ckpt.save(str(tmp_path), s, tree)
    assert ckpt.committed_steps(str(tmp_path)) == [3, 4, 5]


def test_ckpt_truncated_leaf_raises_typed_error(tmp_path):
    """The classic crash corruption — a leaf file cut short — must raise
    CorruptCheckpoint NAMING the leaf, before anything is device_put."""
    tree = {"a": jnp.arange(64, dtype=jnp.float32), "b": jnp.ones(4)}
    ckpt.save(str(tmp_path), 1, tree)
    f = tmp_path / "step_00000001" / "arr_0.npy"
    f.write_bytes(f.read_bytes()[:-16])
    with pytest.raises(ckpt.CorruptCheckpoint, match="arr_0.npy.*truncated"):
        ckpt.restore(str(tmp_path), 1, tree)


def test_ckpt_garbage_header_and_shape_mismatch_raise(tmp_path):
    tree = {"a": jnp.arange(12).reshape(3, 4)}
    ckpt.save(str(tmp_path), 1, tree)
    with pytest.raises(ckpt.CorruptCheckpoint, match="leaf 0.*ckpt shape"):
        ckpt.restore(str(tmp_path), 1, {"a": jnp.zeros((2, 2))})
    (tmp_path / "step_00000001" / "arr_0.npy").write_bytes(b"not an npy")
    with pytest.raises(ckpt.CorruptCheckpoint, match="arr_0.npy.*header"):
        ckpt.restore(str(tmp_path), 1, tree)
    os.unlink(tmp_path / "step_00000001" / "arr_0.npy")
    with pytest.raises(ckpt.CorruptCheckpoint, match="missing"):
        ckpt.restore(str(tmp_path), 1, tree)


def test_ckpt_orphan_dirs_swept_on_next_save(tmp_path):
    """Crash leftovers — uncommitted step dirs and stale .tmp dirs — are
    swept by the NEXT save; committed steps are untouched."""
    tree = {"a": jnp.ones(3)}
    ckpt.save(str(tmp_path), 1, tree)
    os.makedirs(tmp_path / "step_00000007")          # crashed before marker
    os.makedirs(tmp_path / "step_00000003.tmp")      # crashed mid-write
    ckpt.save(str(tmp_path), 2, tree)
    assert not (tmp_path / "step_00000007").exists()
    assert not (tmp_path / "step_00000003.tmp").exists()
    assert ckpt.committed_steps(str(tmp_path)) == [1, 2]


# -------------------------------------------------------------------- fault

def test_supervisor_retries_then_restart():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise RuntimeError("transient")

    sup = StepSupervisor(max_retries=2)
    with pytest.raises(RestartRequired):
        sup.run(flaky, step=3)
    assert calls["n"] == 3


def test_supervisor_straggler_flag():
    import time
    sup = StepSupervisor(straggler_factor=5.0)
    for _ in range(5):
        sup.run(lambda: time.sleep(0.01))
    sup.run(lambda: time.sleep(0.2))
    assert len(sup.stats.stragglers) == 1


# ------------------------------------------------------------ compile cache

_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    """An entry point's compile cache lives in JAX_COMPILATION_CACHE_DIR
    when that is set (and compiles land there), otherwise at the fixed
    `.jax_cache/` of the checkout. Run in a child: importing the helper
    must not set anything in this test process."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        want = str(tmp_path)
        probe = _CACHE_PROBE
    else:
        want = os.path.realpath(os.path.join(src, "..", ".jax_cache"))
        probe = _CACHE_PROBE.rsplit("\n", 2)[0]     # nothing compiled
    out = subprocess.run([sys.executable, "-c", probe], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
    if from_env:
        assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())


# ------------------------------------------------------------------ elastic

@pytest.mark.parametrize("n,expect", [
    (512, ((2, 16, 16), ("pod", "data", "model"))),
    (256, ((16, 16), ("data", "model"))),
    (96, ((6, 16), ("data", "model"))),
    (24, ((3, 8), ("data", "model"))),
    (7, ((7, 1), ("data", "model"))),
])
def test_remesh_plan(n, expect):
    assert remesh_plan(n) == expect
    shape, _ = remesh_plan(n)
    assert int(np.prod(shape)) == n

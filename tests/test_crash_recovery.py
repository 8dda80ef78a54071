"""Kill–recover–resume: the crash-tolerance contract end to end.

A journaled engine must come back from ANY crash point with every live
stream bit-identical to the uninterrupted run — greedy and sampled, across
all three crash classes (clean SIGKILL, torn journal write, snapshot
interrupted before its COMMITTED marker). The in-process tests simulate the
crash by ABANDONING the engine object mid-run (everything durable is
already fsync'd, exactly as after a SIGKILL) and recovering into a second
engine in the same process; the REPRO_CRASH=1 lane adds real SIGKILLs — a
child process chaos-killed mid-decode, and the full supervisor loop
(launch/serve.py --supervise) restarting through recover().

The journal byte format and torn-tail property live in tests/test_journal.py;
the checkpoint-file analogue (CorruptCheckpoint) in tests/test_substrate.py."""
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.launch.serve import generate, serve_continuous
from repro.models.model import model_init
from repro.runtime.fault import ProcessSupervisor, RestartRequired
from repro.serving import (Chaos, EngineJournal, JournalError, RequestStatus,
                           ServingEngine)

MAX_TOKENS = 48

_CRASH_LANE = os.environ.get("REPRO_CRASH", "") not in ("", "0")
needs_crash_lane = pytest.mark.skipif(
    not _CRASH_LANE, reason="real-SIGKILL lane (set REPRO_CRASH=1)")


def _setup(arch="llama_moe_4_16"):
    cfg = get_config(arch, smoke=True)
    params = model_init(jax.random.PRNGKey(5), cfg)
    return cfg, params


def _static_tokens(params, cfg, prompt, gen):
    res = generate(params, cfg, jnp.asarray(prompt)[None, :], gen,
                   max_len=MAX_TOKENS)
    return np.asarray(res["tokens"][0]).tolist()


def _engine(params, cfg, jdir, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_tokens", MAX_TOKENS)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    kw.setdefault("snapshot_every", 4)
    return ServingEngine(params, cfg, journal_dir=str(jdir), **kw)


def _prompts(seed, n, size=12):
    rng = np.random.default_rng(seed)
    cfg = get_config("llama_moe_4_16", smoke=True)
    return [rng.integers(0, cfg.vocab_size, size=size, dtype=np.int32)
            for _ in range(n)]


# ------------------------------------------------- in-process crash classes


def test_recover_greedy_bit_identical(tmp_path):
    """Abandon a journaled engine mid-decode (slots live, requests queued,
    events past the last snapshot); recover() must finish every stream
    exactly as the solo static-batch oracle would."""
    cfg, params = _setup()
    prompts = _prompts(0, 4)
    eng = _engine(params, cfg, tmp_path)
    rids = [eng.submit(p, 12) for p in prompts]
    for _ in range(6):
        eng.step()
    assert eng.pool.num_active() > 0, "crash point must have live slots"

    rec = ServingEngine.recover(str(tmp_path), params, cfg)
    assert rec.recovered_info is not None
    assert rec.recovered_info["events"] == rec.replayed_events
    fin = rec.run()
    for rid, p in zip(rids, prompts):
        assert fin[rid].status is RequestStatus.DONE
        assert fin[rid].tokens == _static_tokens(params, cfg, p, 12), \
            f"request {rid} diverged across the crash"
    s = rec.stats()
    assert s["recoveries"] == 1
    assert s["journal_bytes"] > 0 and s["snapshots"] >= 1
    assert s["snapshot_age_ticks"] is not None
    assert rec.pool.alloc.pages_in_use == 0


def test_recover_sampled_streams_bit_identical(tmp_path):
    """Sampled streams resume from the journaled per-slot PRNG keys: the
    recovered run must equal an uninterrupted engine token for token even
    at temperature > 0 (where one resampled token would cascade)."""
    cfg, params = _setup()
    prompts = _prompts(1, 4)
    kw = dict(temperature=0.8, top_p=0.9)
    ref_eng = ServingEngine(params, cfg, num_slots=2, max_tokens=MAX_TOKENS,
                            paged=True, page_size=8)
    ref_rids = [ref_eng.submit(p, 12, seed=100 + i, **kw)
                for i, p in enumerate(prompts)]
    ref = ref_eng.run()

    eng = _engine(params, cfg, tmp_path)
    rids = [eng.submit(p, 12, seed=100 + i, **kw)
            for i, p in enumerate(prompts)]
    for _ in range(6):
        eng.step()
    fin = ServingEngine.recover(str(tmp_path), params, cfg).run()
    for rid, ref_rid in zip(rids, ref_rids):
        assert fin[rid].status is RequestStatus.DONE
        assert fin[rid].tokens == ref[ref_rid].tokens, \
            f"sampled request {rid} diverged across the crash"


def test_recover_from_torn_journal_tail(tmp_path):
    """The torn-write crash class: the last journal record is cut mid-write
    before the kill. Replay drops the torn record (a watermark the dead
    process never durably emitted) and the streams still finish exactly."""
    cfg, params = _setup()
    prompts = _prompts(2, 3)
    eng = _engine(params, cfg, tmp_path, snapshot_every=64)
    rids = [eng.submit(p, 12) for p in prompts]
    for _ in range(5):
        eng.step()
    intact = eng.journal.events_written
    eng.journal.tear_tail(eng.journal._last_record_bytes)

    rec = ServingEngine.recover(str(tmp_path), params, cfg)
    assert rec.replayed_events == intact - 1
    fin = rec.run()
    for rid, p in zip(rids, prompts):
        assert fin[rid].status is RequestStatus.DONE
        assert fin[rid].tokens == _static_tokens(params, cfg, p, 12)


def test_uncommitted_snapshot_skipped_at_recovery(tmp_path):
    """The snapshot-interrupted crash class: state.pkl fully written but no
    COMMITTED marker. Recovery must fall back to the PREVIOUS committed
    snapshot + its journal tail — and still resume bit-identically."""
    cfg, params = _setup()
    prompts = _prompts(3, 3)
    eng = _engine(params, cfg, tmp_path, snapshot_every=4)
    rids = [eng.submit(p, 12) for p in prompts]
    for _ in range(6):
        eng.step()
    committed = eng.journal._seq
    eng.journal.write_uncommitted_snapshot(eng._snapshot_payload())

    rec = ServingEngine.recover(str(tmp_path), params, cfg)
    assert rec.recovered_info["snapshot_seq"] == committed
    fin = rec.run()
    for rid, p in zip(rids, prompts):
        assert fin[rid].status is RequestStatus.DONE
        assert fin[rid].tokens == _static_tokens(params, cfg, p, 12)


def test_replay_oracle_trips_on_divergence(tmp_path):
    """The prefix-assertion oracle is live: recovery that would re-emit a
    DIFFERENT token than the dead process journaled must fail loudly, not
    silently serve a forked stream."""
    cfg, params = _setup()
    eng = _engine(params, cfg, tmp_path, snapshot_every=64)
    eng.submit(_prompts(4, 1)[0], 12)
    for _ in range(4):
        eng.step()

    rec = ServingEngine.recover(str(tmp_path), params, cfg)
    assert rec._replay_expect, "crash point left no watermarks to check"
    rid = next(iter(rec._replay_expect))
    rec._replay_expect[rid][-1] ^= 1          # forge a wrong watermark
    with pytest.raises(AssertionError, match="recovery divergence"):
        rec.run()


def test_repeated_crashes_are_idempotent(tmp_path):
    """Crashing AGAIN right after recovery (before any new tick) re-runs
    from the fresh post-recovery snapshot — recover(recover(x)) == recover(x)
    all the way to completion."""
    cfg, params = _setup()
    prompts = _prompts(5, 3)
    eng = _engine(params, cfg, tmp_path)
    rids = [eng.submit(p, 12) for p in prompts]
    for _ in range(5):
        eng.step()
    rec1 = ServingEngine.recover(str(tmp_path), params, cfg)
    for _ in range(2):
        rec1.step()                            # advance, then die again
    rec2 = ServingEngine.recover(str(tmp_path), params, cfg)
    assert rec2.recoveries == 2
    fin = rec2.run()
    for rid, p in zip(rids, prompts):
        assert fin[rid].status is RequestStatus.DONE
        assert fin[rid].tokens == _static_tokens(params, cfg, p, 12)


def test_cancel_replays_but_outcomes_recompute(tmp_path):
    """Terminal-event replay policy: CANCELLED is an external decision and
    must survive the crash; DONE outcomes are recomputed by resuming."""
    cfg, params = _setup()
    prompts = _prompts(6, 3)
    eng = _engine(params, cfg, tmp_path, snapshot_every=64)
    rids = [eng.submit(p, 12) for p in prompts]
    for _ in range(2):
        eng.step()
    eng.cancel(rids[2])

    fin = ServingEngine.recover(str(tmp_path), params, cfg).run()
    assert fin[rids[2]].status is RequestStatus.CANCELLED
    for rid, p in zip(rids[:2], prompts[:2]):
        assert fin[rid].status is RequestStatus.DONE
        assert fin[rid].tokens == _static_tokens(params, cfg, p, 12)


def test_recover_preserves_prefix_cache(tmp_path):
    """The prefix index is part of the snapshot: requests admitted AFTER
    recovery still hit the cache warmed BEFORE the crash (shared pages were
    re-materialized from the snapshot's page contents)."""
    cfg, params = _setup()
    prompt = _prompts(7, 1, size=16)[0]
    eng = _engine(params, cfg, tmp_path, prefix_share=True,
                  snapshot_every=64)
    eng.submit(prompt, 8)
    while eng.has_work():           # run() would flush the cache at drain
        eng.step()
    assert eng.prefix_index.node_pages()
    eng.journal.commit_snapshot(eng._snapshot_payload(), eng.step_count)

    rec = ServingEngine.recover(str(tmp_path), params, cfg)
    assert rec.prefix_share and rec.prefix_index.node_pages()
    rid = rec.submit(prompt, 8)
    fin = rec.run()
    assert rec.prefix_hits == 1
    assert rec.prefill_tokens_skipped == 16
    assert fin[rid].tokens == _static_tokens(params, cfg, prompt, 8)


# ------------------------------------------------------- contract refusals


def test_recover_without_snapshot_raises(tmp_path):
    cfg, params = _setup()
    with pytest.raises(JournalError, match="no committed snapshot"):
        ServingEngine.recover(str(tmp_path / "absent"), params, cfg)


def test_journal_requires_paged_pool_and_rejects_extras(tmp_path):
    cfg, params = _setup()
    # max_tokens with no page-size divisor >= 4 stays dense even under the
    # REPRO_FORCE_PAGED lane, so the refusal is observable everywhere
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(params, cfg, num_slots=1, max_tokens=45,
                      journal_dir=str(tmp_path))
    eng = _engine(params, cfg, tmp_path)
    with pytest.raises(ValueError, match="extras"):
        eng.submit(_prompts(8, 1)[0], 4, extras={"memory": None})


def test_env_journal_lane(tmp_path, monkeypatch):
    """REPRO_JOURNAL_DIR attaches a journal to engines that can support it
    and silently no-ops on those that can't (the CI-lane pattern)."""
    cfg, params = _setup()
    monkeypatch.setenv("REPRO_JOURNAL_DIR", str(tmp_path))
    dense = ServingEngine(params, cfg, num_slots=1, max_tokens=45)
    assert dense.journal is None
    eng = ServingEngine(params, cfg, num_slots=2, max_tokens=MAX_TOKENS,
                        paged=True, page_size=8)
    assert eng.journal is not None
    assert os.path.dirname(eng.journal.dir) == str(tmp_path)
    p = _prompts(9, 1)[0]
    rid = eng.submit(p, 6)
    fin = eng.run()
    assert fin[rid].tokens == _static_tokens(params, cfg, p, 6)
    assert eng.stats()["journal_bytes"] > 0


# ------------------------------------------------------- process supervisor


def _gen_script(body0, body1):
    """A child that branches on its supervision generation."""
    return ("import os, sys, time\n"
            "gen = int(os.environ.get('REPRO_SUPERVISE_GENERATION', '0'))\n"
            f"if gen == 0:\n    {body0}\nelse:\n    {body1}\n")


def test_supervisor_restarts_until_clean_exit():
    sup = ProcessSupervisor(
        [sys.executable, "-c", _gen_script("os._exit(3)", "os._exit(0)")],
        backoff_s=0.01)
    assert sup.run() == 0
    assert sup.stats.restarts == 1
    assert sup.stats.exit_codes == [3, 0]


def test_supervisor_budget_exhausted_raises():
    sup = ProcessSupervisor(
        [sys.executable, "-c", "import os; os._exit(2)"],
        max_restarts=1, backoff_s=0.01)
    with pytest.raises(RestartRequired, match="restart budget"):
        sup.run()
    assert sup.stats.exit_codes == [2, 2]


def test_supervisor_kills_on_stale_heartbeat(tmp_path):
    """A hung child (alive but never ticking) is SIGKILLed on heartbeat
    staleness and restarted through the same path as a crash."""
    hb = str(tmp_path / "hb")
    sup = ProcessSupervisor(
        [sys.executable, "-c",
         _gen_script("time.sleep(120)", "os._exit(0)")],
        heartbeat_file=hb, heartbeat_timeout_s=0.5, poll_s=0.05,
        backoff_s=0.01)
    assert sup.run() == 0
    assert sup.stats.heartbeat_kills == 1
    assert sup.stats.exit_codes == [-9, 0]


# --------------------------------------------------- real-SIGKILL CI lane


def _serve_cmd(jdir, *extra):
    return [sys.executable, "-m", "repro.launch.serve",
            "--arch", "llama_moe_4_16", "--smoke", "--requests", "4",
            "--slots", "2", "--prompt", "12", "--gen", "12",
            "--paged", "--page-size", "8",
            "--journal-dir", str(jdir), "--snapshot-every", "4",
            *extra]


def _serve_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.pop("REPRO_SUPERVISE_GENERATION", None)
    env.pop("REPRO_JOURNAL_DIR", None)
    return env


_RECORD_CHILD = """
import sys
from repro.runtime import fault

class Recorder(fault.ProcessSupervisor):
    def run(self):
        print("CHILD", " ".join(self.cmd[1:]))
        return 0

fault.ProcessSupervisor = Recorder
from repro.launch import serve
sys.argv = ["serve"] + sys.argv[1:]
serve.main()
"""


def test_supervise_parent_never_initialises_a_backend(tmp_path):
    """The --supervise parent only watches its child, so it must reach the
    child's launch without initialising a JAX backend: on an accelerator
    host a parent that did would hold the device its child needs. Here
    JAX_PLATFORMS names no real platform, so any backend initialisation
    raises; the supervisor is replaced by a recorder of the child command.
    The same CLI without --supervise fails on that trap."""
    env = dict(_serve_env(), JAX_PLATFORMS="no-such-platform")
    args = _serve_cmd(tmp_path / "jnl", "--supervise")[3:]
    out = subprocess.run([sys.executable, "-c", _RECORD_CHILD, *args],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    child = [ln for ln in out.stdout.splitlines() if ln.startswith("CHILD")]
    assert child == ["CHILD -m repro.launch.serve " + " ".join(args[:-1])]
    assert "supervised serve exited 0" in out.stdout

    trap = subprocess.run([sys.executable, "-c", _RECORD_CHILD, *args[:-1]],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert trap.returncode != 0
    assert "Unable to initialize backend" in trap.stderr


@needs_crash_lane
def test_sigkill_mid_decode_then_recover(tmp_path):
    """A real `kill -9` at a chaos-chosen decode tick: the child dies with
    SIGKILL (no cleanup, no atexit), and recovering in THIS process finishes
    every stream exactly as an uninterrupted engine would."""
    jdir = tmp_path / "jnl"
    out = subprocess.run(_serve_cmd(jdir, "--crash-step", "6"),
                         env=_serve_env(), capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == -signal.SIGKILL, \
        f"expected SIGKILL, got {out.returncode}: {out.stderr[-2000:]}"
    assert EngineJournal.recoverable(str(jdir))

    # the CLI's workload, reproduced in-process (serve.py uses PRNGKey(0)
    # and default_rng(0) prompts with staggered arrivals)
    cfg = get_config("llama_moe_4_16", smoke=True)
    params = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
               for _ in range(4)]
    ref = serve_continuous(params, cfg, prompts, 12, num_slots=2,
                           arrival_steps=[0, 2, 4, 6], paged=True,
                           page_size=8)
    rec = ServingEngine.recover(str(jdir), params, cfg)
    fin = rec.run()
    assert rec.stats()["statuses"] == {"DONE": 4}
    for rid in ref["tokens"]:
        assert fin[rid].tokens == ref["tokens"][rid].tolist(), \
            f"request {rid} diverged across the SIGKILL"


@needs_crash_lane
def test_supervised_serve_survives_crash(tmp_path):
    """The full loop: --supervise re-execs the CLI as a watched child,
    chaos SIGKILLs generation 0 mid-decode, the supervisor restarts it, and
    generation 1 recovers from the journal and drains to exit 0."""
    jdir = tmp_path / "jnl"
    out = subprocess.run(
        _serve_cmd(jdir, "--supervise", "--crash-step", "6"),
        env=_serve_env(), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "supervised serve exited 0 after 1 restart(s)" in out.stdout
    assert "recovered from" in out.stdout
    assert "'DONE': 4" in out.stdout

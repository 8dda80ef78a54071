"""Durable request journal + atomic engine snapshots (crash-tolerant serving).

The GO cache is the paper's thesis made literal — expert-choice GO rows are
TopKUpdate HISTORY, not recomputable from the prompt — so a process crash
without durability silently destroys every in-flight stream and forgets
which requests were ever admitted. This module is the durability layer the
recovery path (`ServingEngine.recover`) replays:

  Journal        an fsync'd append-only log of length-prefixed, CRC-guarded
                 records. A crash mid-write leaves a TORN TAIL (short header,
                 short payload, or CRC mismatch); `read_records` stops at the
                 first bad record and returns the valid prefix — replay never
                 crashes on a torn journal (pinned byte-by-byte by the
                 hypothesis property test in tests/test_journal.py).

  EngineJournal  the engine-facing layer: one journal SEGMENT per snapshot
                 generation plus periodic whole-engine snapshots committed
                 with the checkpoint/ckpt.py pattern — write everything into
                 `snap_<seq>.tmp/`, fsync, drop an empty COMMITTED marker
                 LAST, rename into place. A snapshot without COMMITTED is a
                 crash artifact and recovery skips it in favor of the
                 previous committed one. Committing a snapshot opens segment
                 `journal_<seq>.log`, so recovery = latest committed snapshot
                 + replay of exactly one segment's tail.

Event kinds written by the engine (serving/engine.py):

  submit    full request record (prompt, budgets, sampling seed, priority,
            submit order) — everything needed to rebuild the Request
  install   a request's FIRST token, emitted at admission from the prefill
            logits (cold, cached, prefix-extension, or chunk completion)
  tick      the per-tick token watermark: {request id: token} for every slot
            that decoded this tick
  terminal  a request reached a terminal status (DONE/TIMEOUT/CANCELLED/
            FAILED) — replay re-applies CANCELLED; the rest are recomputed
            bit-identically by resuming decode from the restored state

What is durable: request identity/parameters, admission watermarks, emitted
tokens, terminal statuses, and (via snapshots) the live KV pages + GO rows +
decode cursors + per-slot PRNG keys + scheduler EWMAs/skip counters + the
prefix-index tree with its shared page contents. What is NOT durable:
wall-clock anchors (deadlines re-anchor at recovery), chaos RNG position,
and per-request extras (cross-attn memory is rejected at submit when
journaling). See docs/architecture.md "Durability & crash recovery".
"""
from __future__ import annotations

import os
import pickle
import re
import shutil
import struct
import time
import zlib

import numpy as np

from repro.serving.scheduler import Request, RequestStatus

# one record = HEADER (payload length + CRC32 of payload) + pickle payload.
# The length field is what makes torn tails DETECTABLE (a short read can
# never parse as a record); the CRC is what makes them UNAMBIGUOUS (a
# truncation landing inside the next record's bytes cannot fake a record).
_HEADER = struct.Struct("<II")
_SEGMENT_MAGIC = b"REPROJNL"
_SNAP_RE = re.compile(r"^snap_(\d{8})$")
_SEG_RE = re.compile(r"^journal_(\d{8})\.log$")


class JournalError(RuntimeError):
    """A journal directory is unusable for recovery (no committed snapshot
    at all — distinct from a torn tail, which replay tolerates)."""


# --------------------------------------------------------------- record log


def append_record(f, obj) -> int:
    """Append one durable record to open file `f`: length + CRC + payload,
    flushed and fsync'd so a SIGKILL after return can never lose it.
    Returns the record's full on-disk size in bytes."""
    payload = pickle.dumps(obj, protocol=4)
    f.write(_HEADER.pack(len(payload), zlib.crc32(payload)))
    f.write(payload)
    f.flush()
    os.fsync(f.fileno())
    return _HEADER.size + len(payload)


def read_records(path: str) -> list:
    """Replay a journal segment, tolerating a torn tail: records are yielded
    until the first short header, short payload, or CRC mismatch — whatever
    a crash mid-append left behind is silently dropped, and everything
    BEFORE it is returned intact (a valid prefix, never garbage)."""
    out = []
    if not os.path.exists(path):
        return out
    with open(path, "rb") as f:
        if f.read(len(_SEGMENT_MAGIC)) != _SEGMENT_MAGIC:
            return out                       # foreign or torn-at-birth file
        while True:
            head = f.read(_HEADER.size)
            if len(head) < _HEADER.size:
                return out                   # torn tail: short header
            length, crc = _HEADER.unpack(head)
            payload = f.read(length)
            if len(payload) < length or zlib.crc32(payload) != crc:
                return out                   # torn tail: short/corrupt payload
            try:
                out.append(pickle.loads(payload))
            except Exception:
                return out                   # CRC-valid but unloadable: stop


# ------------------------------------------------------- request (de)serde


def request_record(req: Request, *, runtime: bool = False) -> dict:
    """Pickle-friendly snapshot of a Request. `runtime=True` additionally
    captures lifecycle state (emitted tokens, status, admission steps) for
    engine snapshots; submit events only need the identity fields."""
    rec = {
        "rid": req.request_id,
        "prompt": np.asarray(req.prompt, np.int32),
        "max_new_tokens": req.max_new_tokens,
        "eos_id": req.eos_id,
        "arrival_step": req.arrival_step,
        "priority": req.priority,
        "temperature": req.temperature,
        "top_p": req.top_p,
        "seed": req.seed,
        "deadline_s": req.deadline_s,
        "max_wall_s": req.max_wall_s,
        "seq": req.seq,
        "times_skipped": req.times_skipped,
        "expert_sig": (None if req.expert_sig is None
                       else np.asarray(req.expert_sig, bool)),
    }
    if runtime:
        rec.update(tokens=list(req.tokens), status=req.status.value,
                   fail_reason=req.fail_reason, admit_step=req.admit_step,
                   finish_step=req.finish_step, preemptions=req.preemptions,
                   slot=req.slot)
    return rec


def request_from_record(rec: dict) -> Request:
    """Rebuild a Request from `request_record`. Wall-clock anchors re-anchor
    at NOW — deadline budgets are wall time, which a dead process cannot
    have been spending; restarting them is the only non-lying option (the
    alternative, expiring everything that out-waited the outage, would turn
    every recovery into a mass TIMEOUT)."""
    req = Request(
        request_id=rec["rid"],
        prompt=np.asarray(rec["prompt"], np.int32),
        max_new_tokens=rec["max_new_tokens"],
        eos_id=rec["eos_id"],
        arrival_step=rec["arrival_step"],
        priority=rec["priority"],
        temperature=rec["temperature"],
        top_p=rec["top_p"],
        seed=rec["seed"],
        deadline_s=rec["deadline_s"],
        max_wall_s=rec["max_wall_s"],
    )
    req.seq = rec["seq"]
    req.times_skipped = rec["times_skipped"]
    req.expert_sig = rec["expert_sig"]
    now = time.monotonic()
    req.arrival_time = req.submit_time = now
    if "status" in rec:
        req.status = RequestStatus(rec["status"])
        req.fail_reason = rec["fail_reason"]
        req.tokens = list(rec["tokens"])
        req.admit_step = rec["admit_step"]
        req.finish_step = rec["finish_step"]
        req.preemptions = rec["preemptions"]
        req.slot = rec["slot"]
        if req.admit_step >= 0:
            # max_wall_s re-anchors too; start_time with it
            req.start_time = req.admit_time = now
    return req


# ----------------------------------------------------------- engine journal


class EngineJournal:
    """Snapshot-segmented write-ahead journal for one ServingEngine.

    Layout under `directory`:
        snap_<seq>/state.pkl + COMMITTED   atomic engine snapshot
        journal_<seq>.log                  events SINCE snapshot <seq>

    `commit_snapshot` is the generation boundary: snapshot seq N commits
    (ckpt.py pattern — marker last, rename into place), THEN segment N opens
    and subsequent events land there. A crash between the two leaves a
    committed snapshot with a missing segment, which replays as an empty
    tail — never a stale one. Old generations are pruned to `keep`
    committed snapshots; uncommitted crash leftovers older than the newest
    committed snapshot are swept on the next commit."""

    def __init__(self, directory: str, *, snapshot_every: int = 32,
                 keep: int = 2):
        if snapshot_every < 1:
            raise ValueError("snapshot_every must be >= 1")
        self.dir = directory
        self.snapshot_every = int(snapshot_every)
        self.keep = int(keep)
        os.makedirs(directory, exist_ok=True)
        self._seq = -1
        self._f = None
        self._last_record_bytes = 0
        self.bytes_written = 0
        self.events_written = 0
        self.snapshots_committed = 0
        self.last_snapshot_step = 0

    # ------------------------------------------------------------- appending

    def append(self, kind: str, **payload) -> None:
        """Durably append one event to the current segment."""
        assert self._f is not None, "no open segment — commit_snapshot first"
        self._last_record_bytes = append_record(self._f, (kind, payload))
        self.bytes_written += self._last_record_bytes
        self.events_written += 1

    # ------------------------------------------------------------- snapshots

    def _snap_dir(self, seq: int) -> str:
        return os.path.join(self.dir, f"snap_{seq:08d}")

    def _seg_path(self, seq: int) -> str:
        return os.path.join(self.dir, f"journal_{seq:08d}.log")

    def _write_snapshot_files(self, target: str, payload: dict,
                              committed: bool) -> None:
        tmp = target + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        with open(os.path.join(tmp, "state.pkl"), "wb") as f:
            pickle.dump(payload, f, protocol=4)
            f.flush()
            os.fsync(f.fileno())
        if committed:
            with open(os.path.join(tmp, "COMMITTED"), "w") as f:
                f.flush()
                os.fsync(f.fileno())
        os.replace(tmp, target)
        self._fsync_dir(self.dir)

    @staticmethod
    def _fsync_dir(path: str) -> None:
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError:                       # platforms without dir fds
            return
        try:
            os.fsync(fd)
        finally:
            os.close(fd)

    def commit_snapshot(self, payload: dict, step: int) -> int:
        """Atomically commit an engine snapshot (marker written LAST) and
        open the next journal segment. Returns the new generation seq."""
        seq = self._seq + 1 if self._seq >= 0 else _next_seq(self.dir)
        self._write_snapshot_files(self._snap_dir(seq), payload,
                                   committed=True)
        if self._f is not None:
            self._f.close()
        self._f = open(self._seg_path(seq), "wb")
        self._f.write(_SEGMENT_MAGIC)
        self._f.flush()
        os.fsync(self._f.fileno())
        self._seq = seq
        self.snapshots_committed += 1
        self.last_snapshot_step = int(step)
        self._last_record_bytes = 0
        self._prune(seq)
        return seq

    def write_uncommitted_snapshot(self, payload: dict) -> None:
        """Chaos hook: materialize the NEXT snapshot's files WITHOUT the
        COMMITTED marker — exactly what a crash between the data write and
        the marker leaves behind. Recovery must skip it (pinned in
        tests/test_crash_recovery.py)."""
        self._write_snapshot_files(self._snap_dir(self._seq + 1), payload,
                                   committed=False)

    def tear_tail(self, cut_bytes: int) -> None:
        """Chaos hook: truncate the current segment `cut_bytes` into its
        LAST record — the torn-write crash class. The cut is clamped so at
        least one byte of the record is lost and the preceding records stay
        intact (replay must recover exactly them)."""
        if self._f is None or self._last_record_bytes == 0:
            return
        cut = max(1, min(int(cut_bytes), self._last_record_bytes))
        self._f.flush()
        size = self._f.tell()
        self._f.truncate(size - cut)
        self._f.flush()
        os.fsync(self._f.fileno())

    def _prune(self, newest: int) -> None:
        """Keep the last `keep` committed generations; sweep everything
        older, plus uncommitted snapshot leftovers and stale .tmp dirs from
        crashed commits (any generation < newest that never committed is an
        orphan by construction)."""
        committed = sorted(s for s in _snapshot_seqs(self.dir)
                           if os.path.exists(
                               os.path.join(self._snap_dir(s), "COMMITTED")))
        drop = set(committed[:-self.keep])
        for name in os.listdir(self.dir):
            if name.endswith(".tmp"):
                shutil.rmtree(os.path.join(self.dir, name),
                              ignore_errors=True)
                continue
            m = _SNAP_RE.match(name)
            if m:
                seq = int(m.group(1))
                uncommitted = not os.path.exists(
                    os.path.join(self.dir, name, "COMMITTED"))
                if seq in drop or (uncommitted and seq < newest):
                    shutil.rmtree(os.path.join(self.dir, name),
                                  ignore_errors=True)
                continue
            m = _SEG_RE.match(name)
            if m and int(m.group(1)) in drop:
                try:
                    os.unlink(os.path.join(self.dir, name))
                except OSError:
                    pass

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    # -------------------------------------------------------------- recovery

    @staticmethod
    def recoverable(directory: str) -> bool:
        """Does `directory` hold at least one committed snapshot?"""
        return EngineJournal.latest_committed(directory) is not None

    @staticmethod
    def latest_committed(directory: str):
        """(seq, snapshot payload) of the newest COMMITTED and loadable
        snapshot, or None. A snapshot missing its marker is a crash artifact
        and is skipped; a committed-but-unloadable one (disk corruption) is
        also skipped in favor of the previous generation — recovery prefers
        older-but-consistent over newer-but-broken."""
        if not os.path.isdir(directory):
            return None
        for seq in sorted(_snapshot_seqs(directory), reverse=True):
            d = os.path.join(directory, f"snap_{seq:08d}")
            if not os.path.exists(os.path.join(d, "COMMITTED")):
                continue
            try:
                with open(os.path.join(d, "state.pkl"), "rb") as f:
                    return seq, pickle.load(f)
            except Exception:
                continue
        return None

    @staticmethod
    def read_tail(directory: str, seq: int) -> list:
        """The events journaled since snapshot `seq` (torn tail dropped).
        A missing segment (crash between snapshot commit and segment open)
        is an empty tail, not an error."""
        return read_records(os.path.join(directory, f"journal_{seq:08d}.log"))


def _snapshot_seqs(directory: str) -> list[int]:
    out = []
    for name in os.listdir(directory):
        m = _SNAP_RE.match(name)
        if m:
            out.append(int(m.group(1)))
    return out


def _next_seq(directory: str) -> int:
    seqs = _snapshot_seqs(directory)
    return max(seqs) + 1 if seqs else 0

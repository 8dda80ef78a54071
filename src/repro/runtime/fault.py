"""Fault tolerance: step supervision, retry policy, straggler detection,
and process-level supervision for the serving path.

At 1000+ nodes the failure model is: transient device/step errors (retry),
hard node loss (restart from checkpoint, possibly re-meshed — see elastic.py),
and stragglers (slow steps that stall the synchronous collective).

`StepSupervisor` wraps the train step:
  * retries transient failures up to `max_retries` (with the same inputs —
    steps are deterministic given (params, batch), so retry is safe);
  * raises `RestartRequired` after exhausting retries — the launcher catches
    it, restores the latest committed checkpoint, and resumes (train.py);
  * records per-step wall times and flags stragglers at straggler_factor
    times the median of the last STEP_WINDOW steps; the hook is where a
    production deployment would trigger hot-spare swap / re-sharding. At
    the MoE layer the C2 load-aware placement is itself the straggler
    *prevention* mechanism.

`ProcessSupervisor` is the serving analogue one level up: the engine runs
in a CHILD process (launch/serve.py --supervise re-execs itself) that
journals every request lifecycle event (serving/journal.py); the parent
watches for exits and missed heartbeats, SIGKILLs a hung child, restarts
with exponential backoff, and each restarted generation re-dispatches
through `ServingEngine.recover()` — the same restore-from-committed-state
contract the training launcher has, extended across the process boundary.
Heartbeats are file mtimes (the engine touches REPRO_HEARTBEAT once per
tick): no pipes to deadlock on, works under SIGKILL, and the staleness
threshold can stay generous because jit compiles legitimately stall early
ticks for tens of seconds."""
from __future__ import annotations

import os
import subprocess
import time
from collections import deque
from dataclasses import dataclass, field

# step wall times kept for the straggler median: a rolling window, so a
# long-running server pays a bounded sort per step
STEP_WINDOW = 1024


class RestartRequired(RuntimeError):
    """Raised when a step cannot be completed in-place; the launcher must
    restore from the latest committed checkpoint."""


@dataclass
class StepStats:
    times: deque = field(default_factory=lambda: deque(maxlen=STEP_WINDOW))
    retries: int = 0
    stragglers: list = field(default_factory=list)

    def median(self) -> float:
        """Median wall time of the last STEP_WINDOW steps."""
        if not self.times:
            return 0.0
        s = sorted(self.times)
        return s[len(s) // 2]


class StepSupervisor:
    """Shared by the training loop AND the serving engine's decode tick
    (serving/engine.py): both steps are deterministic given their inputs,
    so retrying with the same inputs is always safe. `retry_on` narrows or
    widens the transient-error classes (RestartRequired is never retried —
    it IS the give-up signal)."""

    def __init__(self, max_retries: int = 2, straggler_factor: float = 3.0,
                 on_straggler=None,
                 retry_on: tuple = (RuntimeError, ValueError)):
        self.max_retries = max_retries
        self.straggler_factor = straggler_factor
        self.on_straggler = on_straggler
        self.retry_on = tuple(retry_on)
        self.stats = StepStats()

    def run(self, step_fn, *args, step: int = -1, **kw):
        """Execute step_fn with retry + timing. Returns its result."""
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                out = step_fn(*args, **kw)
                out = _block(out)
                break
            except self.retry_on as e:
                if isinstance(e, RestartRequired):
                    raise
                attempt += 1
                self.stats.retries += 1
                if attempt > self.max_retries:
                    raise RestartRequired(
                        f"step {step} failed {attempt} times: {e}") from e
        dt = time.perf_counter() - t0
        med = self.stats.median()
        self.stats.times.append(dt)
        if med > 0 and dt > med * self.straggler_factor:
            self.stats.stragglers.append((step, dt, med))
            if self.on_straggler is not None:
                self.on_straggler(step, dt, med)
        return out


def _block(x):
    """Force async dispatch errors to surface inside the supervised region."""
    import jax
    return jax.block_until_ready(x)


@dataclass
class SupervisorStats:
    restarts: int = 0
    heartbeat_kills: int = 0
    exit_codes: list = field(default_factory=list)


class ProcessSupervisor:
    """Run a child process under restart supervision with file-mtime
    heartbeats.

    Each generation gets REPRO_SUPERVISE_GENERATION=<n> in its environment
    (generation 0 is the first launch) and, when a heartbeat file is
    configured, REPRO_HEARTBEAT=<path> — the serving engine touches that
    file every tick. A child that exits 0 ends supervision; any other exit
    (including SIGKILL from a chaos crash) restarts it after an
    exponentially backed-off delay, up to `max_restarts` restarts, after
    which RestartRequired propagates to the caller. A child whose heartbeat
    goes stale past `heartbeat_timeout_s` is SIGKILLed and restarted
    through the same path — a hang and a crash are the same failure to the
    recovery contract.

    The child decides WHAT to do differently per generation (the serve CLI
    recovers from the journal when one exists); the supervisor only decides
    WHETHER it runs. `heartbeat_timeout_s` defaults generous because jit
    compilation legitimately stalls the first ticks for tens of seconds."""

    def __init__(self, cmd: list, *, env: dict | None = None,
                 heartbeat_file: str | None = None,
                 heartbeat_timeout_s: float = 120.0,
                 max_restarts: int = 3, backoff_s: float = 0.25,
                 backoff_factor: float = 2.0, poll_s: float = 0.1,
                 on_restart=None):
        self.cmd = list(cmd)
        self.env = env
        self.heartbeat_file = heartbeat_file
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.max_restarts = max_restarts
        self.backoff_s = backoff_s
        self.backoff_factor = backoff_factor
        self.poll_s = poll_s
        self.on_restart = on_restart
        self.stats = SupervisorStats()

    def run(self) -> int:
        """Supervise until a generation exits 0 (returns 0) or the restart
        budget is exhausted (raises RestartRequired)."""
        generation = 0
        backoff = self.backoff_s
        while True:
            env = dict(os.environ if self.env is None else self.env)
            env["REPRO_SUPERVISE_GENERATION"] = str(generation)
            if self.heartbeat_file:
                env["REPRO_HEARTBEAT"] = self.heartbeat_file
                # prime the mtime so staleness counts from LAUNCH, not from
                # whenever a previous generation last ticked
                with open(self.heartbeat_file, "a"):
                    os.utime(self.heartbeat_file, None)
            proc = subprocess.Popen(self.cmd, env=env)
            code = self._watch(proc)
            self.stats.exit_codes.append(code)
            if code == 0:
                return 0
            if self.stats.restarts >= self.max_restarts:
                raise RestartRequired(
                    f"child failed {self.stats.restarts + 1} times "
                    f"(exit codes {self.stats.exit_codes}) — restart budget "
                    f"of {self.max_restarts} exhausted")
            self.stats.restarts += 1
            generation += 1
            if self.on_restart is not None:
                self.on_restart(generation, code)
            time.sleep(backoff)
            backoff *= self.backoff_factor

    def _watch(self, proc) -> int:
        """Poll one generation to exit, SIGKILLing it on heartbeat
        staleness. Returns its exit code."""
        while True:
            code = proc.poll()
            if code is not None:
                return code
            if self.heartbeat_file:
                try:
                    age = time.time() - os.path.getmtime(self.heartbeat_file)
                except OSError:
                    age = 0.0
                if age > self.heartbeat_timeout_s:
                    proc.kill()
                    proc.wait()
                    self.stats.heartbeat_kills += 1
                    return -9
            time.sleep(self.poll_s)

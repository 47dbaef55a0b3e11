"""Resilience layer: checkpoint integrity, restart policy, anomaly
rollback, and a deterministic chaos harness (SURVEY.md §5).

The paper's promise is *automatic* distributed training; "TPU slices
fail whole; recovery = resume elsewhere" makes recovery a first-class
subsystem, not an afterthought.  Four pieces live here:

- **Integrity manifest**: every ``CheckpointManager.save`` writes a
  per-leaf sha256 manifest next to the step (``manifest-<step>.json``);
  restore re-hashes the restored leaves against it, so silent
  corruption (bit rot, a torn write that orbax happens to parse) is
  caught before training resumes on garbage.  ``restore_or_init`` walks
  the **fallback chain** latest→older, quarantining bad steps
  (``<step>.corrupt`` rename + ``ckpt.corrupt`` journal event) instead
  of dying — a partial write during preemption never bricks the run.
- **RestartPolicy**: exponential backoff with *deterministic* jitter
  (hash of seed×attempt, so multi-host restarts stay in lockstep and
  tests can assert the schedule) and a restart budget over a rolling
  window, consumed by ``elastic.run_with_recovery``.
- **AnomalyGuard**: rolling loss statistics; on NaN/Inf or a spike the
  Trainer restores the last *verified* checkpoint and skips the
  offending batch window — deterministic under step-indexed data.
- **ChaosPlan**: seeded fault-injection harness (the FaultInjector
  generalization): injected step exceptions, torn checkpoint writes,
  NaN batches, stalled steps — every recovery path above gets a
  kill-and-resume test on the CPU sim.  ``tadnn doctor`` exposes
  :func:`verify_directory` on the command line.

Orbax is imported lazily (only the directory-verification paths need
it) so elastic/trainer can import this module without the checkpoint
dependency.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import time
from collections import deque
from typing import Any, Callable, Iterator

import numpy as np

from ..obs import journal as obs_journal

MANIFEST_VERSION = 1


class CheckpointCorruptError(RuntimeError):
    """A checkpoint step failed integrity verification."""


class StallError(RuntimeError):
    """Raised (asynchronously) when the watchdog escalates a stall —
    a RuntimeError so the default ``run_with_recovery`` retriable set
    treats it like any other wedged-runtime failure."""


# -- per-leaf integrity manifest ---------------------------------------------


def _norm_keypath(kp: tuple) -> str:
    """Normalize a jax key path to a structure-agnostic string.

    The same TrainState flattens to ``.params['w']`` at save time
    (attribute access on the struct dataclass) but ``['params']['w']``
    when orbax restores it as a raw dict; both become ``params/w``.
    """
    parts = []
    for k in kp:
        for attr in ("name", "key", "idx"):
            v = getattr(k, attr, None)
            if v is not None:
                parts.append(str(v))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def leaf_checksums(tree: Any) -> dict[str, dict]:
    """``{path: {sha256, shape, dtype}}`` for every array leaf.

    Hashes the host representation (devices are fetched), so the digest
    is layout/sharding independent — a resharded restore of identical
    values verifies clean.
    """
    import jax

    out: dict[str, dict] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for kp, leaf in flat:
        arr = np.ascontiguousarray(np.asarray(leaf))
        out[_norm_keypath(kp)] = {
            "sha256": hashlib.sha256(arr.tobytes()).hexdigest(),
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        }
    return out


def manifest_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"manifest-{int(step)}.json")


def write_manifest(directory: str, step: int, tree: Any,
                   extra: dict | None = None, *,
                   leaves: dict | None = None) -> str:
    """Atomically (tmp+fsync+rename) write the integrity manifest for
    ``step``.  ``leaves`` short-circuits the checksum pass with values
    computed earlier — the async-save finalizer hashes on the training
    thread (while the arrays are still live) but writes here later."""
    path = manifest_path(directory, step)
    doc = {
        "version": MANIFEST_VERSION,
        "step": int(step),
        "written_at": time.time(),
        "leaves": leaf_checksums(tree) if leaves is None else leaves,
        **(extra or {}),
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def read_manifest(directory: str, step: int) -> dict | None:
    """The manifest for ``step``, or None (missing / unparseable — a
    torn manifest must not block the fallback chain, the step itself
    just restores unverified)."""
    try:
        with open(manifest_path(directory, step)) as f:
            doc = json.load(f)
        if not isinstance(doc.get("leaves"), dict):
            return None
        return doc
    except (OSError, ValueError):
        return None


def verify_tree(tree: Any, manifest: dict) -> list[str]:
    """Problems (empty = verified) comparing ``tree``'s leaves against a
    manifest from :func:`write_manifest`."""
    want = manifest.get("leaves", {})
    got = leaf_checksums(tree)
    problems = []
    for path in sorted(set(want) - set(got)):
        problems.append(f"missing leaf {path}")
    for path in sorted(set(got) - set(want)):
        problems.append(f"unexpected leaf {path}")
    for path in sorted(set(want) & set(got)):
        if want[path]["sha256"] != got[path]["sha256"]:
            problems.append(f"checksum mismatch at {path}")
    return problems


# -- fallback chain / quarantine ---------------------------------------------


def list_steps(directory: str) -> list[int]:
    """Committed step numbers in a checkpoint directory, ascending.
    Quarantined (``<step>.corrupt``) and orbax tmp dirs are excluded."""
    if not os.path.isdir(directory):
        return []
    steps = []
    for name in os.listdir(directory):
        if name.isdigit() and os.path.isdir(os.path.join(directory, name)):
            steps.append(int(name))
    return sorted(steps)


def quarantine_step(directory: str, step: int, reason: str = "") -> str:
    """Rename a corrupt/torn step (and its manifest) out of the chain.

    ``<dir>/<step>`` -> ``<dir>/<step>.corrupt`` (``.corrupt2``... if a
    previous quarantine of the same step exists), so the evidence
    survives for `tadnn doctor` forensics but latest-step scans and the
    fallback walk never pick it up again.
    """
    src = os.path.join(directory, str(int(step)))
    dst = src + ".corrupt"
    n = 1
    while os.path.exists(dst):
        n += 1
        dst = f"{src}.corrupt{n}"
    if os.path.exists(src):
        os.replace(src, dst)
    man = manifest_path(directory, step)
    if os.path.exists(man):
        os.replace(man, man + ".corrupt")
    obs_journal.event("ckpt.corrupt", step=int(step), reason=reason,
                      quarantined=os.path.basename(dst))
    return dst


# -- doctor: directory verification ------------------------------------------


def _raw_restore_state(directory: str, step: int) -> Any:
    """Restore a step's ``state`` item as a raw host tree — the doctor
    path, independent of any model code.

    The abstract target comes from the checkpoint's own metadata
    (shapes/dtypes), placed on the current first device: a targetless
    restore would try to reconstruct the *saved* mesh, so a doctor
    process with a different device count (the common case — a 1-CPU
    CLI inspecting an 8-device run's checkpoints) would misreport every
    healthy step as corrupt."""
    import jax
    import orbax.checkpoint as ocp

    path = os.path.join(directory, str(int(step)), "state")
    ckptr = ocp.StandardCheckpointer()
    try:
        meta = ckptr.metadata(path)
        # orbax >= 0.11 wraps the item's tree in a StepMetadata
        meta = getattr(meta, "item_metadata", meta)
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        abstract = jax.tree.map(
            lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype,
                                           sharding=sharding),
            meta,
        )
        return ckptr.restore(path, target=abstract)
    finally:
        ckptr.close()


def verify_step(directory: str, step: int) -> dict:
    """Verdict dict for one step: ``{step, ok, verified, problems}``.

    ``ok`` = the step restores (and matches its manifest when one
    exists); ``verified`` = a manifest was present and every leaf
    checksum matched (``ok`` without ``verified`` is a legacy step
    saved before integrity manifests).
    """
    manifest = read_manifest(directory, step)
    problems: list[str] = []
    try:
        tree = _raw_restore_state(directory, step)
    except Exception as e:  # orbax raises OSError/ValueError/KeyError/...
        return {"step": int(step), "ok": False, "verified": False,
                "problems": [f"restore failed: {type(e).__name__}: {e}"]}
    if manifest is not None:
        problems = verify_tree(tree, manifest)
    return {
        "step": int(step),
        "ok": not problems,
        "verified": manifest is not None and not problems,
        "problems": problems,
    }


def verify_directory(directory: str) -> dict:
    """Walk the fallback chain (latest → oldest) and verify every step.

    Returns ``{directory, steps: [verdicts newest-first], quarantined,
    healthy, best_step}`` — ``healthy`` means at least one step is
    restorable, ``best_step`` is the newest such step (what
    ``restore_or_init`` would resume from).
    """
    steps = list_steps(directory)
    chain = [verify_step(directory, s) for s in reversed(steps)]
    quarantined = sorted(
        name for name in (os.listdir(directory)
                          if os.path.isdir(directory) else [])
        if ".corrupt" in name and os.path.isdir(os.path.join(directory, name))
    )
    best = next((v["step"] for v in chain if v["ok"]), None)
    return {
        "directory": os.path.abspath(directory),
        "steps": chain,
        "quarantined": quarantined,
        "healthy": best is not None,
        "best_step": best,
    }


def format_doctor(report: dict) -> str:
    """Human rendering of :func:`verify_directory` (the `tadnn doctor`
    output): the fallback chain newest-first with per-step verdicts."""
    lines = [f"checkpoint directory: {report['directory']}"]
    if not report["steps"] and not report["quarantined"]:
        lines.append("no checkpoint steps found")
        return "\n".join(lines)
    lines.append("fallback chain (newest first):")
    for v in report["steps"]:
        mark = ("ok, verified" if v["verified"]
                else "ok, no manifest" if v["ok"] else "CORRUPT")
        lines.append(f"  step {v['step']:>8}  [{mark}]")
        for p in v["problems"][:4]:
            lines.append(f"      - {p}")
        if len(v["problems"]) > 4:
            lines.append(f"      - ... {len(v['problems']) - 4} more")
    for q in report["quarantined"]:
        lines.append(f"  quarantined: {q}")
    lines.append(
        f"restore would resume from step {report['best_step']}"
        if report["healthy"]
        else "NO restorable step — restore_or_init would fall back to "
             "fresh init"
    )
    return "\n".join(lines)


# -- restart policy -----------------------------------------------------------


@dataclasses.dataclass
class RestartPolicy:
    """Backoff + budget for ``run_with_recovery``.

    Delay before retry ``n`` (1-based) is ``base * factor**(n-1)``
    clamped to ``max_s``, then jittered by ±``jitter`` — the jitter is
    a pure hash of ``(seed, n)``, so every host of a slice computes the
    same schedule (restarts stay collective-aligned) and tests can
    assert it exactly.  The budget is a rolling window: more than
    ``max_restarts`` failures inside ``window_s`` seconds gives up —
    a crash loop burns the budget fast, one failure a day never does.

    ``sleep``/``clock`` are injectable for deterministic tests.
    """

    max_restarts: int = 2
    window_s: float = 3600.0
    backoff_base_s: float = 1.0
    backoff_factor: float = 2.0
    backoff_max_s: float = 60.0
    jitter: float = 0.1
    seed: int = 0
    sleep: Callable[[float], None] = time.sleep
    clock: Callable[[], float] = time.monotonic

    def __post_init__(self):
        self._failures: deque[float] = deque()

    def delay_s(self, attempt: int) -> float:
        """Deterministic backoff delay before retry ``attempt`` (>=1)."""
        if self.backoff_base_s <= 0:
            return 0.0
        base = min(
            self.backoff_base_s * self.backoff_factor ** max(attempt - 1, 0),
            self.backoff_max_s,
        )
        if not self.jitter:
            return base
        h = hashlib.blake2b(
            f"{self.seed}:{attempt}".encode(), digest_size=8
        ).digest()
        frac = int.from_bytes(h, "big") / 2**64  # [0, 1)
        return base * (1.0 + self.jitter * (2.0 * frac - 1.0))

    def note_failure(self, now: float | None = None) -> bool:
        """Record a failure; True when the rolling-window budget is
        exhausted (the caller should re-raise instead of retrying)."""
        now = self.clock() if now is None else now
        self._failures.append(now)
        while self._failures and now - self._failures[0] > self.window_s:
            self._failures.popleft()
        return len(self._failures) > self.max_restarts

    @property
    def recent_failures(self) -> int:
        return len(self._failures)


def window_budget_exhausted(failure_times_s: "list[float]",
                            max_restarts: int = 2,
                            window_s: float = 3600.0) -> bool:
    """Pure replay of :meth:`RestartPolicy.note_failure` over a whole
    failure history: True when ANY failure exhausts the rolling-window
    budget (more than ``max_restarts`` failures inside ``window_s``).
    The what-if simulator uses this to score hypothetical preemption
    traces against the exact policy ``run_with_recovery`` enforces."""
    window: deque[float] = deque()
    for now in sorted(failure_times_s):
        window.append(now)
        while window and now - window[0] > window_s:
            window.popleft()
        if len(window) > max_restarts:
            return True
    return False


def survival_probability(*, rate_per_hour: float, mission_hours: float,
                         max_restarts: int = 2, window_s: float = 3600.0,
                         samples: int = 2048, seed: int = 0) -> float:
    """P(a run survives ``mission_hours`` of Poisson preemptions at
    ``rate_per_hour`` without exhausting the restart budget).

    When the window covers the whole mission the budget degenerates to
    a plain failure count and the answer is the exact Poisson CDF
    ``P(N <= max_restarts)``.  Otherwise the rolling window forgives
    spread-out failures and the probability comes from a seeded
    Monte-Carlo replay of the window math (deterministic per seed)."""
    if rate_per_hour <= 0 or mission_hours <= 0:
        return 1.0
    mission_s = mission_hours * 3600.0
    lam = rate_per_hour * mission_hours
    if window_s >= mission_s:
        # every failure stays in-window for the whole mission: exact
        return float(sum(math.exp(-lam) * lam**i / math.factorial(i)
                         for i in range(max_restarts + 1)))
    rng = np.random.RandomState(seed)
    survived = 0
    for n in rng.poisson(lam, size=samples):
        if n <= max_restarts:
            survived += 1  # too few failures to exhaust any window
            continue
        times = np.sort(rng.uniform(0.0, mission_s, size=int(n)))
        if not window_budget_exhausted(
                times.tolist(), max_restarts, window_s):
            survived += 1
    return survived / samples


# -- anomaly rollback ---------------------------------------------------------


@dataclasses.dataclass
class AnomalyConfig:
    """Loss-anomaly guard knobs (Trainer ``cfg.anomaly``).

    A loss is anomalous when it is non-finite, or exceeds the rolling
    mean by ``spike_sigma`` rolling standard deviations (with an
    ``abs(mean) * spike_rel_floor`` floor on the deviation, so a noisy
    flat-ish curve doesn't trip on normal variance).  At least
    ``min_history`` healthy losses must be seen before spike detection
    arms; NaN/Inf always triggers.
    """

    window: int = 32
    spike_sigma: float = 6.0
    spike_rel_floor: float = 0.05
    min_history: int = 8
    max_rollbacks: int = 2  # per fit(); beyond this the anomaly raises


class AnomalyGuard:
    """Rolling loss statistics + anomaly verdicts (pure host math)."""

    def __init__(self, cfg: AnomalyConfig):
        self.cfg = cfg
        self._window: deque[float] = deque(maxlen=cfg.window)
        self.rollbacks = 0

    def check(self, loss: float) -> str | None:
        """``None`` when healthy (the loss joins the rolling window),
        else the anomaly reason (``'non-finite'`` / ``'spike'``) — the
        anomalous value is NOT admitted to the window, so the stats a
        rollback replays against are untainted."""
        if not math.isfinite(loss):
            return "non-finite"
        n = len(self._window)
        if n >= max(self.cfg.min_history, 2):
            mean = sum(self._window) / n
            var = sum((x - mean) ** 2 for x in self._window) / n
            floor = abs(mean) * self.cfg.spike_rel_floor
            threshold = mean + self.cfg.spike_sigma * max(
                math.sqrt(var), floor, 1e-12
            )
            if loss > threshold:
                return "spike"
        self._window.append(loss)
        return None


# -- chaos harness ------------------------------------------------------------


def _fires(seed: int, kind: str, step: int, p: float) -> bool:
    """Deterministic per-(seed, kind, step) Bernoulli draw — stable
    across processes/hosts (no Python hash randomization)."""
    if p <= 0:
        return False
    if p >= 1:
        return True
    h = hashlib.blake2b(f"{seed}:{kind}:{step}".encode(),
                        digest_size=8).digest()
    return int.from_bytes(h, "big") / 2**64 < p


class ChaosFault(RuntimeError):
    """Raised by the chaos harness's injected step exceptions (a
    RuntimeError: retriable under the default run_with_recovery set)."""


@dataclasses.dataclass
class ChaosPlan:
    """Seeded fault schedule — the FaultInjector generalization.

    Faults fire either at the explicit ``*_at`` steps or with
    per-step probability ``p_*`` drawn deterministically from ``seed``
    (same plan -> same faults, every run, every host).  Kinds:

    - ``exception``: the step callback raises :class:`ChaosFault`
      (kill-and-resume path, like FaultInjector);
    - ``torn_ckpt``: the newest committed checkpoint step is torn
      (files truncated) right after it lands — the integrity/fallback
      path;
    - ``nan``: ``ChaosData`` poisons that step's batch with NaNs — the
      anomaly-rollback path;
    - ``stall``: the step callback sleeps ``stall_s`` — the watchdog /
      escalation path.

    Orchestrator-level kinds (fired by ``training.launch``, not by an
    in-process callback — faults a worker cannot inject on itself):

    - ``sigkill``: SIGKILL the ``chaos_host`` worker when its heartbeat
      reaches the step (no drain, no atexit — the hard-preemption path);
    - ``journal_partition``: the ``chaos_host`` journal file is renamed
      aside mid-run, simulating a network-partitioned host whose events
      go dark (the merge/report side must degrade, not crash);
    - ``shard_tear``: one host's shard file of the newest committed
      sharded checkpoint is truncated — the cross-host integrity path.
    """

    seed: int = 0
    exception_at: tuple[int, ...] = ()
    torn_ckpt_at: tuple[int, ...] = ()
    nan_at: tuple[int, ...] = ()
    stall_at: tuple[int, ...] = ()
    sigkill_at: tuple[int, ...] = ()
    journal_partition_at: tuple[int, ...] = ()
    shard_tear_at: tuple[int, ...] = ()
    p_exception: float = 0.0
    p_torn_ckpt: float = 0.0
    p_nan: float = 0.0
    p_stall: float = 0.0
    p_sigkill: float = 0.0
    p_journal_partition: float = 0.0
    p_shard_tear: float = 0.0
    stall_s: float = 0.0
    chaos_host: int = 0  # which host orchestrator faults target

    def fires(self, kind: str, step: int) -> bool:
        at = {
            "exception": self.exception_at,
            "torn_ckpt": self.torn_ckpt_at,
            "nan": self.nan_at,
            "stall": self.stall_at,
            "sigkill": self.sigkill_at,
            "journal_partition": self.journal_partition_at,
            "shard_tear": self.shard_tear_at,
        }[kind]
        p = {
            "exception": self.p_exception,
            "torn_ckpt": self.p_torn_ckpt,
            "nan": self.p_nan,
            "stall": self.p_stall,
            "sigkill": self.p_sigkill,
            "journal_partition": self.p_journal_partition,
            "shard_tear": self.p_shard_tear,
        }[kind]
        return step in at or _fires(self.seed, kind, step, p)

    ORCHESTRATOR_KINDS = ("sigkill", "journal_partition", "shard_tear")


def tear_checkpoint(directory: str, step: int, *, seed: int = 0,
                    fraction: float = 1.0) -> int:
    """Simulate a torn/partial checkpoint write: truncate (a seeded
    subset of) the files under ``<directory>/<step>`` in place.  The
    step directory stays committed — exactly what a crash between the
    data write and a durable flush leaves behind.  Returns the number
    of files torn."""
    root = os.path.join(directory, str(int(step)))
    targets = []
    for dirpath, _, files in os.walk(root):
        for name in files:
            targets.append(os.path.join(dirpath, name))
    targets.sort()  # os.walk order is fs-dependent; the tear must not be —
    # a seeded partial tear has to hit the same files on every run
    torn = 0
    for i, path in enumerate(targets):
        if fraction < 1.0 and not _fires(seed, f"tear:{i}", step, fraction):
            continue
        try:
            size = os.path.getsize(path)
            with open(path, "r+b") as f:
                f.truncate(size // 3)
            torn += 1
        except OSError:
            continue
    return torn


class ChaosInjector:
    """Trainer callback driving a :class:`ChaosPlan`'s exception /
    stall / torn-checkpoint faults (NaN faults live in ChaosData —
    they must enter through the batch, not the host loop).

    Each (kind, step) fault fires at most once per process so a
    restarted run replaying the same step doesn't loop forever on the
    same injected failure — mirroring FaultInjector's ``fired`` latch.
    """

    def __init__(self, plan: ChaosPlan, *, ckpt: Any = None):
        self.plan = plan
        self.ckpt = ckpt  # CheckpointManager, for torn_ckpt faults
        self.fired: set[tuple[str, int]] = set()

    def _once(self, kind: str, step: int) -> bool:
        if (kind, step) in self.fired or not self.plan.fires(kind, step):
            return False
        self.fired.add((kind, step))
        obs_journal.event("resilience.chaos", kind=kind, step=step)
        return True

    def __call__(self, step: int, state: Any, metrics: dict) -> None:
        if self.ckpt is not None and self._once("torn_ckpt", step):
            self.ckpt.wait()  # the async save must land before we tear it
            latest = self.ckpt.latest_step()
            if latest is not None:
                tear_checkpoint(self.ckpt.directory, latest,
                                seed=self.plan.seed)
        if self._once("stall", step) and self.plan.stall_s > 0:
            time.sleep(self.plan.stall_s)
        if self._once("exception", step):
            raise ChaosFault(f"chaos: injected exception at step {step}")


class ChaosData:
    """Step-indexed data wrapper that poisons scheduled batches with
    NaNs (every float leaf) — downstream the loss goes NaN and the
    anomaly guard's rollback path gets exercised end-to-end.

    Skip-aware: the Trainer's anomaly rollback shifts batch indices
    past a poisoned window, so the replayed steps see clean batches.
    """

    step_indexed = True

    def __init__(self, data: Any, plan: ChaosPlan):
        if not getattr(data, "step_indexed", False):
            raise ValueError("ChaosData needs a step-indexed source "
                             "(deterministic chaos requires batch(i))")
        self.data = data
        self.plan = plan

    def batch(self, step: int) -> Any:
        import jax

        b = self.data.batch(step)
        if not self.plan.fires("nan", step):
            return b
        return jax.tree.map(
            lambda x: np.full_like(x, np.nan)
            if isinstance(x, np.ndarray) and np.issubdtype(x.dtype,
                                                           np.floating)
            else x,
            b,
        )

    def __iter__(self) -> Iterator[Any]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1

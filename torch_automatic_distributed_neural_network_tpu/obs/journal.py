"""Span/event journal — the run-wide observability spine (SURVEY.md §5).

Every phase of a run (compile, step, checkpoint, eval, elastic events,
bench probe status) lands here as one JSON line with BOTH clocks:

- ``t``: seconds on the process monotonic clock relative to journal
  creation — durations and ordering survive wall-clock jumps;
- ``wall``: unix time — joinable against MetricsLogger records and logs.

Zero-dep (json/time/os only; jax is touched lazily and optionally, for
host-0 gating).  Usable three ways::

    j = Journal("run/journal.jsonl")
    j.event("elastic.resize", hosts=4)           # point event
    with j.span("compile", fn="train_step"):     # timed span
        ...
    obs.set_default(j)                           # process-global sink:
    obs.event("watchdog.stall", age_s=12.0)      # library code logs here

Work too fine for a record of its own is timed as a ``phase``: its
seconds go into a dict the caller owns and ride on the parent record
(``serve.step``'s ``phases``, the ``goodput`` buckets), and the same
interval is a ``jax.profiler.TraceAnnotation``, so under any profiler
capture it sits on the device trace's clock::

    # the step dispatched a call ago; this call's is queued behind it
    with phase(seconds, "decode_wait", "serve.decode_wait", step=n):
        tokens = jax.device_get(previous_output)

With no default installed, module-level ``span``/``event`` are cheap
no-ops (a null journal).  A ``phase`` runs observed or not: its two
clock reads and its annotation object cost ``ServeEngine.step`` 50-70
us (0.2% of a 30 ms step on a v5e, PERF.md) with no journal and no
capture.  ``TADNN_JOURNAL=<path>`` in the environment installs
a default sink automatically on first use.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import warnings
from typing import Any, IO, Iterator


_TraceAnnotation = None  # jax.profiler.TraceAnnotation, on first phase


class phase:
    """Timed phase: adds its ``time.monotonic`` duration to
    ``seconds[key]`` and is a profiler annotation called ``name``
    carrying ``ids``, over the same interval.
    Writes no journal record.  Outside a profiler capture the
    annotation is one flag test; no fence, nothing on the device."""

    __slots__ = ("_seconds", "_key", "_annotation", "_t0")

    def __init__(self, seconds: dict, key: str, name: str, **ids: Any):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        self._seconds, self._key = seconds, key
        self._annotation = _TraceAnnotation(name, **ids)

    def __enter__(self) -> None:
        self._annotation.__enter__()
        self._t0 = time.monotonic()

    def __exit__(self, *exc) -> None:
        dur = time.monotonic() - self._t0
        self._seconds[self._key] = self._seconds.get(self._key, 0.0) + dur
        self._annotation.__exit__(*exc)


class CompileCounter:
    """What loading programs cost this process, by part, summed by one
    pair of ``jax.monitoring`` listeners (they fire only while JAX traces,
    lowers, compiles or reads its cache: nothing on a hot path).
    ``compile_counter()``.

    ``n`` counts the programs XLA built or read from the persistent cache
    (``backend_compile_duration`` events); beside it the four sums of
    ``PARTS`` (``backend_s`` is those programs' time).  What fires when, for a
    ``jax.jit`` call (JAX 0.9.0; printed on the CPU and on a v5e, PR 52,
    the same on both):

    - first call, persistent-cache MISS: a ``jaxpr_trace_duration`` for the
      program and one for every jitted function it calls (``jnp.tanh``
      ...: 121 events for a function of 30 lines), NESTED: the program's
      own contains them; one ``jaxpr_to_mlir_module_duration`` (a lowering
      rule may trace again inside it: the Pallas interpreter's does); one
      ``backend_compile_duration`` (1.09 s on the chip).  No
      ``cache_retrieval_time_sec``: the failed lookup is inside the
      backend's time, unnamed.
    - first call, persistent-cache HIT (another process): the same traces
      and lowering, then ``cache_retrieval_time_sec`` and AFTER it a
      ``backend_compile_duration`` that CONTAINS it (0.0603 s round
      0.0596 s on the chip, 0.0081 round 0.0072 on the CPU): the backend's
      time less the cache's is what XLA compiled.
    - second call: nothing.

    JAX announces a trace, a lowering and a build as each BEGINS (a
    ``record_scalar`` under the event's name, from
    ``dispatch.LogElapsedTimeContextManager.__enter__``), so the counter
    keeps a depth, a thread its own, and adds an interval's seconds only
    where no other of that thread is open round it: ``trace_s`` is the
    outermost traces', a trace inside a lowering is the lowering's, and
    ``loads()``'s ``load_s`` = ``trace_s + lower_s + backend_s`` counts no
    second twice (it is no more than the call took;
    ``test_phases.py::test_a_nested_jit_loads_in_no_more_than_its_call`` holds
    a JAX that stops announcing to that).  ``backend_s`` contains
    ``cache_read_s``.

    The sums are the process's: a reader that diffs them over an interval
    (``serve.step``'s ``compiles``) also sees what another engine or
    thread loaded in that interval; diffed round ONE call on one thread
    (``ServeEngine``'s first call of a program) they are that program's."""

    PARTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
             "/jax/core/compile/backend_compile_duration": "backend_s",
             "/jax/compilation_cache/cache_retrieval_time_sec":
                 "cache_read_s"}

    def __init__(self):
        self.n = 0
        self.trace_s = self.lower_s = self.backend_s = 0.0
        self.cache_read_s = 0.0
        # .open: this thread's traces, lowerings and builds begun and not
        # ended (threads load programs side by side)
        self._depth = threading.local()

    def _on_scalar(self, event: str, _value, **_kw) -> None:
        if event in self.PARTS:
            self._depth.open = getattr(self._depth, "open", 0) + 1

    def _on(self, event: str, dur_s: float, **_kw) -> None:
        part = self.PARTS.get(event)
        if part is None:
            return
        depth = getattr(self._depth, "open", 0)
        if part == "cache_read_s":
            # (JAX announces no read: it lies inside its backend's interval)
            outermost = depth == 1
        else:
            self.n += part == "backend_s"
            # (never below 0: a counter registered inside a trace hears
            # that trace end and not begin)
            self._depth.open = depth = max(0, depth - 1)
            outermost = not depth
        if outermost:  # else an outer interval's seconds hold these
            setattr(self, part, getattr(self, part) + dur_s)

    def loads(self, since: dict | None = None) -> dict:
        """``n``, the four sums and ``load_s`` (trace + lowering + backend:
        the cache's read lies inside the backend's) as they stand, or
        their growth since an earlier reading ``since``."""
        now = {"n": self.n, "trace_s": self.trace_s, "lower_s": self.lower_s,
               "backend_s": self.backend_s, "cache_read_s": self.cache_read_s,
               "load_s": self.trace_s + self.lower_s + self.backend_s}
        if since is None:
            return now
        return {k: v - since[k] for k, v in now.items()}


_compile_counter: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """The process's one compile counter, registered on first call."""
    global _compile_counter
    if _compile_counter is None:
        import jax

        _compile_counter = CompileCounter()
        jax.monitoring.register_scalar_listener(_compile_counter._on_scalar)
        jax.monitoring.register_event_duration_secs_listener(
            _compile_counter._on)
    return _compile_counter


class GcCounter:
    """Seconds and passes of Python's cyclic collector in this process, by
    generation, counted on ``gc.callbacks`` (two clock reads a pass, nothing
    when no pass runs).  ``gc_counter()``.  A full (generation-2) pass is a
    ``gc.full`` profiler annotation from its ``start`` to its ``stop`` as
    well, so under a capture it lies on the host thread's lane of the
    timeline, on the device trace's clock, inside whichever ``serve.*`` or
    ``train.*`` phase it held up.  Like the compile count it is the
    process's: a reader that diffs it over an interval (``serve.step``'s
    ``gc_s``, ``gc_full``) also sees a pass that another thread's
    allocations set off in that interval."""

    FULL = 2  # the generation whose pass walks every tracked object

    def __init__(self):
        self.seconds = [0.0, 0.0, 0.0]
        self.passes = [0, 0, 0]
        self._t0 = 0.0
        self._annotation = None

    @property
    def total_s(self) -> float:
        return sum(self.seconds)

    def _on(self, when: str, info: dict) -> None:
        global _TraceAnnotation
        gen = info["generation"]
        if when == "start":
            if gen == self.FULL:
                if _TraceAnnotation is None:
                    from jax.profiler import \
                        TraceAnnotation as _TraceAnnotation
                self._annotation = _TraceAnnotation("gc.full")
                self._annotation.__enter__()
            self._t0 = time.monotonic()
            return
        self.seconds[gen] += time.monotonic() - self._t0
        self.passes[gen] += 1
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None


_gc_counter: GcCounter | None = None


def gc_counter() -> GcCounter:
    """The process's one collector counter, registered on first call."""
    global _gc_counter
    if _gc_counter is None:
        import gc

        _gc_counter = GcCounter()
        gc.callbacks.append(_gc_counter._on)
    return _gc_counter


def _process_index() -> int:
    """Host index, without forcing jax (or its backend) to load."""
    try:
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            return 0
        return jax.process_index()
    except Exception:
        return 0


class Journal:
    """Monotonic-timestamped JSONL event/span sink.

    ``path=None`` keeps records in memory only (``self.records``) — the
    test/tooling mode.  ``host0_only=True`` (default) makes non-zero
    hosts' journals silent no-ops so multi-host runs produce one file.

    ``max_bytes`` (or ``TADNN_JOURNAL_MAX_BYTES`` in the environment)
    caps the file: when a write crosses the cap the file rotates to
    ``<path>.1`` (one generation, overwritten) and the journal keeps
    appending to a fresh file — a long-running server's journal can
    never eat the disk.

    ``validate=True`` (or ``TADNN_JOURNAL_VALIDATE=1``) checks every
    record against the event schema registry (:mod:`.schema`) at emit
    time and raises :class:`~.schema.JournalContractError` on drift —
    the runtime half of the telemetry contract, on for CI smoke legs.
    """

    def __init__(self, path: str | None = None, *,
                 host0_only: bool = True, meta: dict | None = None,
                 max_bytes: int | None = None, validate: bool | None = None,
                 clock=time.monotonic):
        self.path = path
        if validate is None:
            validate = os.environ.get(
                "TADNN_JOURNAL_VALIDATE", "").strip() not in ("", "0")
        self.validate = validate
        self.enabled = (not host0_only) or _process_index() == 0
        # ``t`` stamps come from here: inject a virtual clock and every
        # record's event-time is replayable (the gateway's chaos test
        # journals byte-identical sequences across runs this way)
        self._clock = clock
        self._t0 = clock()
        self._depth = 0
        self._file: IO | None = None
        self.records: list[dict] = []  # in-memory sink when path is None
        self.counts: dict[str, int] = {}
        # live taps: called with each record as it is written (the
        # gateway's fleet controller folds windows from here without
        # re-reading the file)
        self._subscribers: list = []
        if max_bytes is None:
            try:
                max_bytes = int(
                    os.environ.get("TADNN_JOURNAL_MAX_BYTES", "0")) or None
            except ValueError:
                max_bytes = None
        self._max_bytes = max_bytes
        self.rotations = 0
        if self.enabled and path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._file = open(path, "a")
        if self.enabled:
            self.event("journal.start", **(meta or {}))

    # -- sinks --------------------------------------------------------------

    def _write(self, rec: dict) -> None:
        if not self.enabled:
            return
        if self.validate:
            # runtime contract enforcement (opt-in; CI smoke legs run
            # with TADNN_JOURNAL_VALIDATE=1): every record must honor
            # its declared schema or the producer fails loudly here,
            # at the drifting emission site
            from . import schema as _schema

            problems = _schema.validate_record(rec)
            if problems:
                detail = "; ".join(f"{c}: {m}" for c, m in problems)
                raise _schema.JournalContractError(
                    f"journal record violates its event schema "
                    f"({detail})")
        self.counts[rec.get("name", "?")] = (
            self.counts.get(rec.get("name", "?"), 0) + 1
        )
        if self._file is not None:
            self._file.write(json.dumps(rec, default=str) + "\n")
            self._file.flush()
            if (self._max_bytes and not getattr(self, "_rotating", False)
                    and self._file.tell() >= self._max_bytes):
                self._rotate()
        else:
            self.records.append(rec)
        for fn in self._subscribers:
            fn(rec)

    def subscribe(self, fn) -> None:
        """Register a live tap: ``fn(rec)`` runs for every record this
        journal writes, file-backed or in-memory — the streaming
        consumer path (LiveAggregator in-process) that doesn't re-read
        the file it is itself producing."""
        self._subscribers.append(fn)

    def _rotate(self) -> None:
        """Move the full file to ``<path>.1`` (replacing any previous
        generation) and reopen fresh.  The rotated event lands first in
        the new file so a reader knows records were shed."""
        self._file.close()
        try:
            os.replace(self.path, self.path + ".1")
        except OSError:
            # rotation is best-effort (read-only fs mid-run): keep
            # appending rather than lose the sink entirely
            self._file = open(self.path, "a")
            return
        self._file = open(self.path, "a")
        self.rotations += 1
        # _rotating guards the rotated event's own write: with a cap
        # smaller than one record it would otherwise recurse forever
        self._rotating = True
        try:
            self.event("journal.rotated", rotations=self.rotations,
                       max_bytes=self._max_bytes)
        finally:
            self._rotating = False

    def event(self, name: str, **fields: Any) -> dict | None:
        """One point-in-time record: ``{"kind": "event", "name": ...}``."""
        if not self.enabled:
            return None
        rec = {"kind": "event", "name": name,
               "t": self._clock() - self._t0, "wall": time.time(),
               "depth": self._depth, **fields}
        self._write(rec)
        return rec

    @contextlib.contextmanager
    def span(self, name: str, **fields: Any) -> Iterator[dict]:
        """Timed region.  Yields the record-in-progress so callers can
        attach result fields before it is written on exit; exceptions are
        recorded (``error`` field) and re-raised."""
        rec: dict[str, Any] = {"kind": "span", "name": name, **fields}
        if not self.enabled:
            yield rec
            return
        t_start = self._clock()
        rec["t"] = t_start - self._t0
        rec["wall"] = time.time()
        rec["depth"] = self._depth
        self._depth += 1
        try:
            yield rec
        except BaseException as e:
            rec["error"] = f"{type(e).__name__}: {e}"
            raise
        finally:
            self._depth -= 1
            rec["dur_s"] = self._clock() - t_start
            self._write(rec)

    def named(self, prefix: str) -> list[dict]:
        """In-memory records (``path=None`` mode) whose name is
        ``prefix`` or lives under it as a dotted namespace — ``'lint'``
        matches ``lint.finding`` and ``lint.summary``."""
        return [
            rec for rec in self.records
            if rec.get("name", "") == prefix
            or rec.get("name", "").startswith(prefix + ".")
        ]

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None

    def __enter__(self) -> "Journal":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- reading ------------------------------------------------------------

    @staticmethod
    def read(path: str) -> list[dict]:
        """Parse a journal file, skipping torn/partial JSONL lines.

        A crashed writer leaves a torn final line; a concurrent writer
        can be seen mid-record.  Neither may take down ``tadnn report``,
        so bad lines are skipped — with ONE warning per file (not one
        per line, not silence: a silently-shrinking journal is the
        observability failure mode this layer exists to prevent).
        Non-dict JSON lines (bare numbers/strings) are torn too.
        """
        out: list[dict] = []
        bad = 0
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    bad += 1
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
                else:
                    bad += 1
        if bad and path not in _warned_corrupt:
            _warned_corrupt.add(path)
            warnings.warn(
                f"journal {path}: skipped {bad} torn/corrupt line(s) "
                f"({len(out)} readable records kept)",
                stacklevel=2,
            )
        return out


    @staticmethod
    def follow(path: str, *, poll_s: float = 0.2,
               idle_timeout: float | None = None,
               stop=None, sleep=time.sleep) -> Iterator[dict]:
        """Tail a journal file as a concurrent writer appends to it.

        Yields each record as soon as its line is complete.  A torn
        final line — the writer seen mid-record — is buffered until its
        newline arrives, so a live reader never drops the record a
        crash-time reader would have skipped; interior corrupt lines
        are skipped with the same once-per-file warning as ``read``.

        Stops when ``stop()`` returns true (checked between polls) or
        after ``idle_timeout`` seconds with no new bytes (None = follow
        forever).  ``sleep`` is injectable so tests can drive the tail
        loop without real waiting.

        The path may not exist yet — a monitor is routinely started
        before the engine's first event (the gateway does exactly
        this): creation is polled for under the same ``idle_timeout``
        budget instead of raising.

        Size-capped rotation is survived: when the writer rotates the
        file out from under the tail (``os.replace`` to ``<path>.1`` —
        the open fd now points at the OLD generation) or truncates it,
        the follower detects the inode swap / size shrink, reopens the
        fresh file from the top, and warns once per rotation; a torn
        buffer from the old generation is dropped (its tail lives in
        ``<path>.1``, not the stream)."""
        buf = ""
        idle = 0.0
        while not os.path.exists(path):
            if stop is not None and stop():
                return
            if idle_timeout is not None and idle >= idle_timeout:
                return
            sleep(poll_s)
            idle += poll_s
        idle = 0.0
        f = open(path)
        try:
            while True:
                chunk = f.read()
                if chunk:
                    idle = 0.0
                    buf += chunk
                    while "\n" in buf:
                        line, _, buf = buf.partition("\n")
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            rec = json.loads(line)
                        except ValueError:
                            rec = None
                        if isinstance(rec, dict):
                            yield rec
                            continue
                        if path not in _warned_corrupt:
                            _warned_corrupt.add(path)
                            warnings.warn(
                                f"journal {path}: skipping torn/corrupt "
                                f"line(s) while following", stacklevel=2)
                    continue
                # no new bytes on the open fd: check whether the file
                # was rotated (replaced: different inode at the path)
                # or truncated (shrunk below our read position) and
                # re-attach to the live generation if so
                rotated = False
                try:
                    disk = os.stat(path)
                    here = os.fstat(f.fileno())
                    if disk.st_ino != here.st_ino:
                        rotated = True
                    elif disk.st_size < f.tell():
                        rotated = True
                except OSError:
                    # path briefly absent mid-replace: treat as idle,
                    # the next poll sees the new file
                    pass
                if rotated:
                    warnings.warn(
                        f"journal {path}: rotated mid-follow, "
                        f"re-attached to the new generation"
                        + (" (dropped a torn partial line)"
                           if buf.strip() else ""), stacklevel=2)
                    f.close()
                    f = open(path)
                    buf = ""
                    idle = 0.0
                    continue
                if stop is not None and stop():
                    return
                if idle_timeout is not None and idle >= idle_timeout:
                    return
                sleep(poll_s)
                idle += poll_s
        finally:
            f.close()


# paths already warned about corrupt lines (once-per-file, process-wide)
_warned_corrupt: set[str] = set()


class _NullJournal(Journal):
    """Sink of last resort: every call is a no-op."""

    def __init__(self):  # noqa: D401 — deliberately skips Journal.__init__
        self.path = None
        self.enabled = False
        self.validate = False
        self._file = None
        self.records = []
        self.counts = {}
        self._subscribers = []
        self._depth = 0
        self._clock = time.monotonic
        self._t0 = time.monotonic()


_NULL = _NullJournal()
_default: Journal | None = None


def set_default(journal: Journal | None) -> Journal | None:
    """Install (or clear, with None) the process-global journal."""
    global _default
    _default = journal
    return journal


def get_default() -> Journal:
    """The process-global journal; honors ``TADNN_JOURNAL`` env on first
    call; a silent null sink when nothing is configured."""
    global _default
    if _default is None:
        env = os.environ.get("TADNN_JOURNAL")
        if env:
            _default = Journal(env)
    return _default if _default is not None else _NULL


@contextlib.contextmanager
def as_default(journal: Journal | None) -> Iterator[Journal]:
    """Temporarily install ``journal`` as the process default (restores
    the previous default on exit).  ``None`` is a pass-through."""
    global _default
    if journal is None:
        yield get_default()
        return
    prev = _default
    _default = journal
    try:
        yield journal
    finally:
        _default = prev


def event(name: str, **fields: Any) -> dict | None:
    """Module-level event on the default journal (no-op when unset)."""
    return get_default().event(name, **fields)


def span(name: str, **fields: Any):
    """Module-level span on the default journal (no-op when unset)."""
    return get_default().span(name, **fields)

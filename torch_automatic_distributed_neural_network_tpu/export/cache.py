"""Content-addressed on-disk cache of serialized XLA executables.

Layout (root = ``TADNN_EXPORT_CACHE`` or ``~/.cache/tadnn/executables``)::

    <root>/index.jsonl     append-only keyed records (tune-cache format:
                           {"key": ..., "record": {...}}, last match wins)
    <root>/<key>.aotx      pickled (payload, in_tree, out_tree) from
                           jax.experimental.serialize_executable

Keys reuse the tuning cache's machinery (``tune.cache.cache_key`` over
params signature x topology fingerprint x a program blob), so a tuner
decision and the executable it produced share one fingerprint.  The
jax/jaxlib/XLA versions and the device fingerprint are deliberately NOT
part of the key: they live in the index record and are VALIDATED at
load time, so a version bump or hardware change surfaces as a loud
``export.stale`` (skip + recompile + overwrite) instead of a silent
key miss that leaves dead payloads behind forever.

The index shares the tune cache's size-capped compaction
(``tune.cache.compact_jsonl``): over the cap, the file is rewritten
last-record-per-key and orphaned payload files are deleted.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Any, Mapping

import jax

from .. import planner as planner_mod
from ..tune import cache as tune_cache

_ENV = "TADNN_EXPORT_CACHE"
_ENV_MAX = "TADNN_EXPORT_CACHE_MAX_BYTES"
_DEFAULT_DIR = "~/.cache/tadnn/executables"
_DEFAULT_INDEX_MAX = 8 * 2**20
_PAYLOAD_EXT = ".aotx"

# index-record env fields validated (not keyed) at load time
_ENV_FIELDS = ("jax", "jaxlib", "platform", "platform_version",
               "device_kind", "num_devices")


def cache_dir(spec: Any = None) -> str | None:
    """Resolve a cache-root spec to a directory path (or None = off).

    - a string: that directory;
    - ``True``: ``TADNN_EXPORT_CACHE`` or the default user cache dir;
    - ``None``: ``TADNN_EXPORT_CACHE`` if set, else off (the opt-in
      default — existing runs see no new files unless asked);
    - ``False``: off, even with the env var set.
    """
    if spec is False:
        return None
    if isinstance(spec, str):
        return os.path.expanduser(spec)
    env = os.environ.get(_ENV)
    if env:
        return os.path.expanduser(env)
    if spec is True:
        return os.path.expanduser(_DEFAULT_DIR)
    return None


def resolve(spec: Any = None) -> "ExecutableCache | None":
    """An :class:`ExecutableCache` for the spec, or None when disabled."""
    if isinstance(spec, ExecutableCache):
        return spec
    root = cache_dir(spec)
    return ExecutableCache(root) if root else None


def env_fingerprint() -> dict:
    """What must match for a serialized executable to be loadable:
    jax/jaxlib versions, the backend and its (XLA) platform version,
    and the device kind/count the program was compiled against."""
    fp: dict[str, Any] = {"jax": jax.__version__}
    try:
        import jaxlib

        fp["jaxlib"] = getattr(jaxlib, "__version__", None) or \
            jaxlib.version.__version__
    except Exception:
        fp["jaxlib"] = None
    try:
        devices = jax.devices()
        d = devices[0]
        fp["platform"] = d.platform
        fp["device_kind"] = d.device_kind
        fp["num_devices"] = len(devices)
        fp["platform_version"] = getattr(d.client, "platform_version", None)
    except Exception:
        pass
    return fp


def plan_blob(plan: Any) -> dict:
    """JSON-able identity of a ShardPlan for the cache key: strategy,
    mesh factorization, remat/zero1, and a digest of the full per-param
    spec tree (two plans that shard even one tensor differently must
    compile separately)."""
    specs = planner_mod._flatten_with_paths(plan.param_specs)
    opt = (planner_mod._flatten_with_paths(plan.opt_spec_tree)
           if plan.opt_spec_tree is not None else [])
    digest = hashlib.sha256(json.dumps(
        [[p, str(s)] for p, s in specs + opt]).encode()).hexdigest()[:16]
    return {
        "strategy": plan.strategy,
        "mesh": {a: int(n) for a, n in
                 zip(plan.mesh.axis_names, plan.mesh.devices.shape)},
        "batch_spec": str(plan.batch_spec),
        "remat": bool(plan.remat),
        "zero1": bool(plan.zero1),
        "specs": digest,
    }


def executable_key(kind: str, signature: str, topo_fp: Mapping,
                   program: Mapping, tags: Mapping | None = None) -> str:
    """Cache key for one executable: the tune-cache key over (params
    signature, topology fingerprint, {kind, program, tags})."""
    return tune_cache.cache_key(
        signature, topo_fp,
        {"kind": kind, "program": dict(program), "tags": dict(tags or {})})


class ExecutableCache:
    """The on-disk cache: index + payload files under one root."""

    def __init__(self, root: str, *, max_index_bytes: int | None = None):
        self.root = os.path.expanduser(root)
        self.index_path = os.path.join(self.root, "index.jsonl")
        if max_index_bytes is None:
            try:
                max_index_bytes = int(os.environ.get(
                    _ENV_MAX, str(_DEFAULT_INDEX_MAX)))
            except ValueError:
                max_index_bytes = _DEFAULT_INDEX_MAX
        self.max_index_bytes = max_index_bytes

    # -- records -------------------------------------------------------------

    def payload_path(self, key: str) -> str:
        return os.path.join(self.root, key + _PAYLOAD_EXT)

    def lookup(self, key: str) -> dict | None:
        """Latest index record for ``key`` (no liveness check)."""
        return tune_cache.lookup(key, path=self.index_path)

    def entries(self) -> dict[str, dict]:
        """key -> latest record, for every key in the index."""
        out: dict[str, dict] = {}
        if not os.path.isfile(self.index_path):
            return out
        with open(self.index_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and rec.get("key") is not None:
                    out.pop(rec["key"], None)  # move to last occurrence
                    out[rec["key"]] = rec.get("record") or {}
        return out

    def check_live(self, rec: Mapping) -> str | None:
        """None when the entry is loadable here/now; else the mismatch
        reason (the ``export.stale`` payload)."""
        now = env_fingerprint()
        stored = rec.get("env") or {}
        for field in _ENV_FIELDS:
            a, b = stored.get(field), now.get(field)
            if a != b:
                return f"{field}: cached {a!r} != current {b!r}"
        f = rec.get("file")
        if f and not os.path.isfile(os.path.join(self.root, f)):
            return f"payload file missing: {f}"
        return None

    # -- executables ---------------------------------------------------------

    def load(self, key: str, rec: Mapping) -> Any:
        """Deserialize+load the executable for an already-validated
        record.  Raises on torn payloads — callers treat that as stale."""
        from jax.experimental import serialize_executable

        path = os.path.join(self.root, rec.get("file") or
                            (key + _PAYLOAD_EXT))
        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        # onto the devices it was compiled for: the default is every
        # device of the backend, and a one-device program loaded so in a
        # process that sees several rejects its arguments at the first call
        devices = None
        if rec.get("device_ids") is not None:
            import jax

            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in rec["device_ids"]]
        return serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree, execution_devices=devices)

    def store(self, key: str, compiled: Any, *, kind: str,
              meta: Mapping | None = None) -> dict:
        """Serialize an executable, write its payload atomically, and
        append the index record.  Returns the record."""
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        os.makedirs(self.root, exist_ok=True)
        path = self.payload_path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        rec = {
            "kind": kind,
            "file": os.path.basename(path),
            "env": env_fingerprint(),
            "created": time.time(),
            "payload_bytes": len(blob),
            "device_ids": [
                d.id for d in compiled.runtime_executable().local_devices()],
            "meta": dict(meta or {}),
        }
        tune_cache.store(key, rec, path=self.index_path, max_bytes=0)
        self._maybe_compact()
        return rec

    def put_record(self, key: str, rec: Mapping) -> None:
        """Append a JSON-only record (no payload) — e.g. cached
        ``cost_analysis`` results riding in the same index."""
        os.makedirs(self.root, exist_ok=True)
        tune_cache.store(key, rec, path=self.index_path, max_bytes=0)
        self._maybe_compact()

    def touch(self, key: str) -> None:
        """Record a cache hit: re-append the entry's record with a
        fresh ``last_hit`` timestamp.  GC evicts by last-hit age, so a
        hot executable stays resident however old its compile is."""
        rec = self.lookup(key)
        if rec is None:
            return
        rec = dict(rec)
        rec["last_hit"] = time.time()
        tune_cache.store(key, rec, path=self.index_path, max_bytes=0)
        self._maybe_compact()

    # -- maintenance ---------------------------------------------------------

    def _maybe_compact(self) -> None:
        if not self.max_index_bytes:
            return
        try:
            if os.path.getsize(self.index_path) < self.max_index_bytes:
                return
        except OSError:
            return
        self.compact()

    def compact(self) -> dict:
        """Dedup-compact the index (tune-cache contract) and delete
        payload files no surviving record references."""
        stats = tune_cache.compact_jsonl(
            self.index_path, max_bytes=self.max_index_bytes)
        live_files = {rec.get("file") for rec in self.entries().values()}
        orphans = 0
        try:
            names = os.listdir(self.root)
        except OSError:
            names = []
        for name in names:
            if name.endswith(_PAYLOAD_EXT) and name not in live_files:
                try:
                    os.remove(os.path.join(self.root, name))
                    orphans += 1
                except OSError:
                    pass
        stats["orphan_payloads_removed"] = orphans
        from ..obs import journal as obs_journal

        obs_journal.event("export.compact", path=self.index_path, **stats)
        return stats

    def gc(self, max_age_s: float) -> dict:
        """Drop every entry neither hit nor created within
        ``max_age_s``: delete its payload file and rewrite the index
        without it (``tadnn export --gc``).  Age is measured from the
        latest ``last_hit`` (``touch`` on every deserialize) falling
        back to ``created``, so anything still being loaded survives
        indefinitely while one-off experiments age out.  Journals
        ``export.gc``; returns the stats dict."""
        now = time.time()
        entries = self.entries()
        keep: dict[str, dict] = {}
        dropped = 0
        freed = 0
        for key, rec in entries.items():
            ts = rec.get("last_hit") or rec.get("created") or 0.0
            if now - float(ts) <= max_age_s:
                keep[key] = rec
                continue
            dropped += 1
            f = rec.get("file")
            path = (os.path.join(self.root, f) if f
                    else self.payload_path(key))
            try:
                freed += os.path.getsize(path)
                os.remove(path)
            except OSError:
                pass
        if dropped and os.path.isfile(self.index_path):
            tmp = f"{self.index_path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                for key, rec in keep.items():
                    f.write(json.dumps({"key": key, "record": rec}) + "\n")
            os.replace(tmp, self.index_path)
        stats = {"scanned": len(entries), "dropped": dropped,
                 "kept": len(keep), "payload_bytes_freed": freed,
                 "max_age_s": max_age_s}
        from ..obs import journal as obs_journal

        obs_journal.event("export.gc", path=self.index_path, **stats)
        return stats

    def verify(self) -> list[dict]:
        """Liveness report for every entry: which would load here/now
        and which are stale (``tadnn export --verify``)."""
        out = []
        for key, rec in self.entries().items():
            reason = self.check_live(rec)
            out.append({
                "key": key,
                "kind": rec.get("kind"),
                "created": rec.get("created"),
                "payload_bytes": rec.get("payload_bytes"),
                "live": reason is None,
                "reason": reason,
            })
        return out

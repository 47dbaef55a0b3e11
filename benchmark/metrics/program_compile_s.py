"""What XLA COMPILED of the engine's programs, in seconds: over
``programs`` of the engine's newest ``serve.engine`` event, the backend's
time that was not a read of the compile cache (``backend_s`` less
``cache_read_s``).  About 0 where every program was in the cache, a
minute or more after an eviction: a pair of runs whose ``setup_s`` differ
and whose ``program_compile_s`` differ is the cache, not the tree.  A
program that does not record its first calls gives ``None``."""


def read(rec):
    programs = (rec.get("serve_engine") or {}).get("programs")
    if not programs:
        return None
    return sum(p["backend_s"] - p["cache_read_s"] for p in programs.values())

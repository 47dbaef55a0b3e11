"""What the engine's programs cost to load, in seconds: the sum of
``load_s`` over ``programs`` of the engine's newest ``serve.engine`` event.
A program's ``load_s`` is what the process's compile counter heard during
its FIRST call (and any later call that loaded it again): the outermost
trace, the lowering and the backend's time (which contains the read of the
compile cache).  It lies inside the
benchmark's ``prompt_lengths_warmed`` mark less ``engine_built``.  A pair
of runs whose ``program_load_s`` differ at equal ``program_compile_s`` is
the tree.  A program that does not record its first calls gives ``None``."""
import json


def read(rec):
    programs = (rec.get("serve_engine") or {}).get("programs")
    if not programs:
        return None
    print(json.dumps({"program_loads": programs}), flush=True)
    return sum(p["load_s"] for p in programs.values())

"""What the tree's constructor costs a restart, in seconds: ``build_s`` of
the engine's newest ``serve.engine`` event, the host time of all of
``ServeEngine.__init__`` (``build_phases`` says which part took it,
``build_loads`` what XLA loaded meanwhile).  It lies inside the benchmark's
``engine_built`` mark less ``weights_from_seed``.  A program that does not
time its construction gives ``None``."""
import json


def read(rec):
    ev = rec.get("serve_engine") or {}
    if ev.get("build_s") is None:
        return None
    print(json.dumps({"engine_build": {
        k: ev.get(k) for k in ("build_s", "build_phases", "build_loads")}}),
          flush=True)
    return ev["build_s"]

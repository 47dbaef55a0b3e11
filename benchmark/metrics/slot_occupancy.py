"""Mean share of slots holding a request over the window's engine steps
(``occupancy`` of the ``serve.step`` journal events), in %."""


def read(rec):
    steps = rec.get("serve_steps")
    if not steps:
        return None
    return 100.0 * sum(s["occupancy"] for s in steps) / len(steps)

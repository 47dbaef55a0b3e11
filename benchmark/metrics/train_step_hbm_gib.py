"""Per-device bytes XLA sizes the compiled train step at (arguments + temps
+ outputs not aliased to a donated argument, from ``memory_analysis()``),
in GiB.  The allocator's ``peak_bytes_in_use`` read under this on the v5e."""


def read(rec):
    b = rec.get("step_hbm_bytes")
    return b / 2**30 if b else None

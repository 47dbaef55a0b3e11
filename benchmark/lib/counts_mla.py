"""Operations and bytes of absorbed latent attention (a ``latent_attention``
layer's decode), as functions of shapes: the numerators of
``latent_attn_roofline``.

A decode token's query, taken into the latent space, attends ONE row a key:
``row`` numbers (the key-value latent and the rotated key part behind it:
512 + 64), of which the first ``value`` (512) are the value as well.

- Operations: a (head, key) pair is a score over ``row`` numbers and a
  weighted sum over ``value``: ``2 (row + value)``.
- Bytes: every live key's ``row`` numbers read ONCE, for scores and values
  and for all heads.  A page may store a row wider (whole tiles of 128
  lanes: 640); the padding is not work the arithmetic needs, so a kernel
  that reads it shows a lower share.  Queries and outputs are a few
  kilobytes a slot and are not counted.
"""

from __future__ import annotations


def latent_layers(model_keys: dict) -> tuple[int, int, int, int]:
    """(latent layers, heads, a key's row, its value part) of a
    configuration's ``model`` keys; (0, 0, 0, 0) for a model without such
    layers."""
    n = list(model_keys.get("layer_types") or ()).count("latent_attention")
    if not n:
        return 0, 0, 0, 0
    rank = model_keys["latent_kv_rank"]
    return (n, model_keys["n_heads"],
            rank + model_keys["latent_rope_head_dim"], rank)


def latent_attention_flops(keys: float, heads: int, row: int,
                           value: int) -> float:
    return float(2 * keys * heads * (row + value))


def latent_attention_bytes(keys: float, row: int, *, itemsize: int) -> float:
    return float(keys * row * itemsize)


KERNEL = "tadnn_paged_decode_latent"


def kernel_seconds(rec) -> tuple[float, int]:
    """(device seconds, events) of the ops named ``KERNEL`` over the traced
    part, in whatever program ran them; (0.0, 0) without a trace."""
    t = rec.get("trace")
    if not t or not t.get("n_devices"):
        return 0.0, 0
    calls = [e - s for ops in t["ops"].values() for nm, s, e in ops
             if KERNEL in nm.split(" = ", 1)[0]]
    return sum(calls) / 1e9, len(calls)

"""Operations and bytes of the decode attention of a model whose attention
is DIFFERENTIAL and whose full cache is SHARED: the numerators of
``diff_attn_roofline``.

One decode token at context ``c`` (keys visible on a full layer) reads, a
layer that attends pages: on a ``sliding_attention`` layer the last ``min(c,
window)`` keys and values of its own ring; on the ``full_attention`` layer
all ``c`` of its pages; and on every ``shared_attention`` layer those SAME
``c`` again (stored once, read once a layer: nothing keeps them on the chip
between two layers' calls).  So the keys a token reads are ``(n_full +
n_shared) c + n_window min(c, window)``: neither ``paged_attn_roofline``,
which counts ``n_layers`` times the context, nor its ``.by_kind``, which
knows two kinds, is right for it.

- Bytes: a key and a value of every KV head a key read, in the pages'
  dtype (``counts.paged_attention_bytes``).
- Operations: a query head scores ITS key head (2 hd a key) and reads the
  pair's TWO value heads (2 x 2 hd): 6 hd a (head, key) pair where plain
  grouped attention does 4 hd.
"""

from __future__ import annotations

from lib import counts

KERNEL = "tadnn_paged_decode"


def layers(model_keys: dict) -> tuple[int, int, int]:
    """(layers that read all of a context: the full one and those that
    share its pages; window layers; the window) of a configuration's
    ``model`` keys; zeros for a model without differential attention."""
    kinds = list(model_keys.get("layer_types") or ())
    if not model_keys.get("diff_attention"):
        return 0, 0, 0
    return (kinds.count("full_attention") + kinds.count("shared_attention"),
            kinds.count("sliding_attention"),
            model_keys.get("sliding_window") or 0)


def keys_read(contexts: list[int], model_keys: dict) -> int:
    """Keys one decode token at each of ``contexts`` reads, over the
    layers."""
    n_all, n_window, window = layers(model_keys)
    return (n_all * sum(contexts)
            + n_window * sum(min(c, window) for c in contexts))


def decode_flops(keys: float, heads: int, head_dim: int) -> float:
    return float(6 * keys * heads * head_dim)


def decode_bytes(keys: float, kv_heads: int, head_dim: int, *,
                 itemsize: int) -> float:
    return counts.paged_attention_bytes(keys, kv_heads, head_dim,
                                        itemsize=itemsize)


def kernel_seconds(rec) -> tuple[float, int]:
    """(device seconds, calls) of the ops named ``tadnn_paged_decode*`` over
    the traced part, in whichever program they ran."""
    t = rec.get("trace")
    if not t or not t.get("n_devices"):
        return 0.0, 0
    calls = [e - s for ops in t["ops"].values() for n, s, e in ops
             if KERNEL in n.split(" = ", 1)[0]]
    return sum(calls) / 1e9, len(calls)


def traced_contexts(rec) -> list[int]:
    """The context of every decode token whose wall time lies inside the
    traced part: output token j (j >= 1; token 0 comes from prefill) of a
    request attends prompt + j keys."""
    span = rec.get("trace_mono")
    if not span:
        return []
    return [len(q["prompt"]) + j for q in rec.get("requests") or ()
            for j, w in enumerate(q["walls"])
            if j >= 1 and span[0] <= w <= span[1]]

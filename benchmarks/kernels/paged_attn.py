"""Decode attention over the paged KV pool (``paged_attention`` in the trace):
one query a slot reads every cached key and value of that slot once, so the
bound is bytes/s: context tokens x 2 (K and V) x KV heads x head size x bytes
an element, per layer. The query, the output and the block table are noise
beside that and are not counted."""

TRACE_PATTERN = r"^paged_attention"


def kv_bytes(context_tokens: int, layers: int, kv_heads: int, head_dim: int,
             bytes_per_element: int = 2) -> float:
    return float(context_tokens) * layers * 2 * kv_heads * head_dim * bytes_per_element

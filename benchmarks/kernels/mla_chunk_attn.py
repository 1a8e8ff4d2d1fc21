"""A prompt chunk's latent attention against the prefix its sequence already
holds (the program's ``mla_prefix_expand`` + ``mla_chunk_attn`` scopes): what
the ALGORITHM needs, whatever implements it.

A chunk of ``n`` queries whose first sits at position ``start`` attends, causally,
``n x start + n (n + 1) / 2`` (query, key) pairs; a pair and head costs the
q/k width (128 + 64) MACs on the score and the v width (128) on the value sum,
2 ops a MAC: ``kernels/mla_attn.py``'s law on a chunk. Reading the prefix back
and expanding it through ``W_kvb``, or absorbing ``W_kvb`` into the queries
instead, is how an implementation gets keys and values out of a latent cache:
either counts as TIME under those scopes and not as work. The bound is FLOP/s
at the bf16 peak."""

SCOPES = ("mla_prefix_expand", "mla_chunk_attn")


def pairs(n: int, start: int) -> float:
    return float(n) * start + n * (n + 1) / 2


def forward_flops(n: int, start: int, heads: int, qk_dim: int, v_dim: int) -> float:
    return pairs(n, start) * heads * (qk_dim + v_dim) * 2

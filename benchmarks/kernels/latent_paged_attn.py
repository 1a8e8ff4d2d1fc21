"""Decode attention over a paged pool of LATENT rows (the program's
``mla_latent_attn`` scope): what the ALGORITHM needs, whatever implements it.

A cached token of a latent layer is ONE row of ``width`` numbers (the normed
compression beside the rotated shared key: 512 + 64) that every query head
reads: a decode step must move each row it attends once, ``width`` x bytes an
element (1,152 B in bf16; a pool the chip tiles to 640 lanes moves 1,280, which
is the implementation's cost and not the need), and spend, a row and query
head, ``width`` MACs on the score and ``rank`` MACs on the value sum (absorbed:
the values are the compression itself), 2 ops a MAC. The query, the output, the
block table and the two small expansions around the kernel are noise beside
that and are not counted. 121 FLOP a byte at 64 heads: under a v5e's 240, so
the bound is bytes/s there; ``bound`` says which, from the peaks it is given."""

SCOPE = "mla_latent_attn"


def row_bytes(rows: float, width: int, bytes_per_element: int = 2) -> float:
    return float(rows) * width * bytes_per_element


def flops(rows: float, q_heads: int, width: int, rank: int) -> float:
    return float(rows) * q_heads * (width + rank) * 2


def bound(rows: float, q_heads: int, width: int, rank: int, peaks: dict) -> tuple[float, str]:
    """(the least seconds the chip could take, which peak gives them)."""
    by_bytes = row_bytes(rows, width) / peaks["hbm_bytes_per_s"]
    by_flops = flops(rows, q_heads, width, rank) / peaks["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")

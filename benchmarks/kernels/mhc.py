"""The residual path of a hyper-connection sublayer (the program's ``mhc_pre``
and ``mhc_post`` scopes): the bytes the ALGORITHM must move, whatever
implements it.

A token's stream is ``n`` rows of ``C`` channels in bfloat16. A sublayer's
forward reads the stream ``X`` once and the sublayer's output ``y`` once and
writes the next stream ``X'`` and the sublayer's input ``u`` once: ``(2n + 2) C``
elements. Its backward reads ``X``, ``dX'``, ``y`` and ``du`` and writes ``dX``
and ``dy``: ``(3n + 3) C``. The 24 coefficients a token are not counted (their
norm and product read ``X`` once more in practice: that read is the
implementation's, a fused path takes it from the pre-mix's), nor is the
backward's recomputation. No arithmetic to speak of (``n^2 + 2n`` multiply-adds
a channel): the bound is bytes/s at the HBM peak, and a path that makes more
passes over the stream, or pads it, shows as lost share.
"""

SCOPES = ("mhc_pre", "mhc_post")
STREAM_BYTES = 2  # bfloat16


def forward_bytes(tokens: int, streams: int, hidden: int) -> float:
    return float(tokens) * (2 * streams + 2) * hidden * STREAM_BYTES


def train_bytes(tokens: int, streams: int, hidden: int) -> float:
    """Forward plus backward of one sublayer (recomputation not counted)."""
    return forward_bytes(tokens, streams, hidden) + float(tokens) * (3 * streams + 3) * hidden * STREAM_BYTES

"""Device self time one paged-decode program spends under the program's
``mla`` scope: the latent-attention branches of all layers whole (the input
norm, the q and kv_a projections, the latent norm, rotary, the row's write,
the query's absorption, the decode kernel, the value expansion, the output
projection), median over the traced ``jit_step`` programs, in milliseconds.
The notes split it by segment and give the chunk program's (``jit_chunk``)
beside it. A program without the scope gives None. Moves tpot_p50_s."""

from benchmarks.harness import loader
from benchmarks.metrics._common import DECODE_MODULE, PREFILL_MODULE, say

_segments = loader.load_module("metrics", "_scope_segments")

SEGMENTS = ("mla_q_absorb", "mla_latent_attn", "mla_v_expand", "mla_prefix_expand",
            "mla_chunk_attn", "latent_write")


def read(run: dict):
    if run["artefacts"]["kind"] != "serve":
        return None
    whole = _segments.median_ms(run, DECODE_MODULE, "mla")
    if whole is None:
        return None
    note = {"chunk_mla_ms": _segments.median_ms(run, PREFILL_MODULE, "mla")}
    for module, tag in ((DECODE_MODULE, "step"), (PREFILL_MODULE, "chunk")):
        for seg in SEGMENTS:
            ms = _segments.median_ms(run, module, seg)
            if ms:
                note[f"{tag}_{seg}_ms"] = ms
    say(program_trace="mla", **note)
    return whole

"""A prompt chunk's latent attention, as a share of its roofline: the causal
(query, key) pairs of the traced chunks (``serve.prefill_dispatch`` spans give
each chunk's first position and its real tokens) under
``kernels/mla_chunk_attn.py``'s law at the widths the reference's ``shapes``
state, all latent layers, at the bf16 peak, over the device self time a
``jit_chunk`` spends under ``mla_prefix_expand`` + ``mla_chunk_attn``; both as
means a chunk. Reading the prefix back and expanding it (or absorbing instead)
is time there and not work, whichever path the program keeps. Bound: FLOP/s.
A program without the scopes gives None. Moves tpot_p50_s."""

from benchmarks.harness import loader, program_trace
from benchmarks.metrics._common import PREFILL_MODULE, kernel, say, shapes

_directions = loader.load_module("metrics", "_segment_directions")


def read(run: dict):
    if run["artefacts"]["kind"] != "serve" or not run["peaks"]:
        return None
    k = kernel("mla_chunk_attn")
    per_scope = [_directions.per_run(run, PREFILL_MODULE, s) for s in k.SCOPES]
    spans = program_trace.spans(run, "serve.prefill_dispatch")
    if not all(per_scope) or not spans:
        return None
    chunks = [(int(s["stats"]["tokens"]), int(s["stats"]["pos"])) for s in spans
              if "tokens" in s["stats"] and "pos" in s["stats"]]
    if not chunks:
        return None
    c = shapes(run)
    need = c["latent_layers"] * sum(
        k.forward_flops(n, start, c["q_heads"], c["qk_dim"], c["v_dim"]) for n, start in chunks
    ) / len(chunks)
    runs = len(per_scope[0])
    by_scope = {s: sum(sum(r.values()) for r in rows) / runs for s, rows in zip(k.SCOPES, per_scope)}
    seconds = sum(by_scope.values())
    if not seconds:
        return None
    say(roofline="mla_attn_roofline.serve", bound="flops", needed_flops_per_chunk=need,
        scope_seconds_per_chunk=by_scope, traced_chunks=runs, dispatched_chunks=len(chunks),
        mean_start=sum(s for _, s in chunks) / len(chunks))
    return 100.0 * (need / run["peaks"]["bf16_flops_per_s"]) / seconds

"""Where a serve cell's token gaps go, from the loop's own account: no profiler.

    python3 tools/serve_loop_account.py --workload serve-chat-minimax-m2 \
        --seed 7 --seconds 51 [--check-trace] [--rehearse]

Drives one of the benchmark's serve cells as ``benchmarks/run.py`` does
(its engine, weights, traffic and submit-when-due loop; no reference run),
then prints, from the ``decode_account`` of every finished window request
(``serving/loop_account.py``; the benchmark's readers take the same sums
over the requests that finished before ITS profiler started), one JSON line:
ms a token by bucket, the groups PERF.md prints, the mean gap they sum to,
``tpot_p50_s`` as the benchmark computes it, and the engine's totals.

``--check-trace`` also runs ``jax.profiler`` for 4 s from a quarter into the
window with the account read at the entry of the first traced ``step()`` and at
the return of the last, and prints a bucket at a time the account's seconds
beside the summed SELF durations of the same-named spans in the xplane (a span
less its direct children; ``outside_step`` against the gaps between
consecutive ``serve.step`` spans): one primitive, two clocks. The split of
such a run is the traced regime's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# PERF.md's groups of buckets
GROUPS = {
    "device_wait": ("decode_wait",),
    "decode_dispatch": ("decode_plan", "decode_dispatch", "decode_h2d", "decode_launch"),
    "prefill_dispatch": ("prefill_dispatch",),
    "first_token_wait": ("first_token_wait",),
    "record_admit_step": ("record", "admit", "step"),
    "outside_step": ("outside_step",),
    "speculative": ("spec_propose", "spec_verify"),
}


def span_self_seconds(spans: list) -> dict:
    """{bucket: summed self seconds of the ``serve.<bucket>`` spans}, and
    ``outside_step``: the gaps between consecutive ``serve.step`` spans."""
    inside = [0.0] * len(spans)
    for sp in spans:
        # the zero-length `serve.counts` is no bucket: what writing it takes is `step`'s
        if sp["parent"] is not None and sp["name"] != "serve.counts":
            inside[sp["parent"]] += sp["end_s"] - sp["start_s"]
    out: dict[str, float] = {}
    for sp, kids in zip(spans, inside):
        if sp["name"].startswith("serve.") and sp["name"] != "serve.counts":
            name = sp["name"].removeprefix("serve.")
            out[name] = out.get(name, 0.0) + sp["end_s"] - sp["start_s"] - kids
    steps = sorted((s["start_s"], s["end_s"]) for s in spans if s["name"] == "serve.step")
    out["outside_step"] = sum(b[0] - a[1] for a, b in zip(steps, steps[1:]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--check-trace", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, as benchmarks/run.py --rehearse")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmarks import run as bench
    from benchmarks.harness import loader, program_trace, serve, trace
    from benchmarks.harness import traffic as T
    from benchmarks.harness.spans import Spans
    from benchmarks.metrics import _token_gap_account as A

    cell = loader.load_cell(loader.load_benchmark(), args.workload)
    if cell["traffic"]["kind"] != "serve":
        raise SystemExit(f"{args.workload} is not a serve cell")
    if args.rehearse:
        cell = bench.rehearse_overrides(cell)

    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    engine, _ = serve.build_engine(cell, args.seed)
    vocab = int(cell["config"]["vocab_size"])
    reqs = T.open_loop_requests(cell["traffic"], args.seed, args.seconds, vocab)
    loop = serve.Loop(engine, Spans())
    loop.warm(vocab, args.seed)
    t_ramp = time.perf_counter()
    loop.drive(reqs["ramp"], t_ramp, "r", until=t_ramp + float(cell["traffic"].get("ramp_s", 0.0)))

    t_start = time.perf_counter()
    trace_dir = ROOT / ".benchmark_out" / "serve_loop_account" / args.workload
    traced: dict = {}

    def traced_step():
        traced.setdefault("first", engine._account.snapshot())
        done = type(engine).step(engine)
        traced["last"] = engine._account.snapshot()
        return done

    def on_tick(now: float) -> None:
        if not args.check_trace or "t1" in traced:
            return
        if "t0" not in traced and now >= t_start + 0.25 * args.seconds:
            jax.profiler.start_trace(str(trace_dir))
            traced["t0"], engine.step = time.perf_counter(), traced_step
        elif "t0" in traced and now >= traced["t0"] + min(4.0, 0.25 * args.seconds):
            del engine.step
            jax.profiler.stop_trace()
            traced["t1"] = time.perf_counter()

    loop.drive(reqs["window"], t_start, "w", until=t_start + args.seconds, on_tick=on_tick)
    if "t0" in traced and "t1" not in traced:
        jax.profiler.stop_trace()
    loop.drain(time.perf_counter() + float(cell["traffic"].get("drain_s", 30.0)))
    engine.stop_watchdog()

    red = serve.reduce_window(loop, reqs["window"], t_start, t_start + args.seconds)
    run = {"artefacts": {"kind": "serve", "records": list(loop.records.values())}}
    requests = A.clean_requests(run) or []
    line = {
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "platform": jax.devices()[0].platform, "traced": bool(traced),
        "requests_due": len(reqs["window"]), "failed": red["failed"],
        "tpot_p50_s": T.percentile(red["tpot"], 0.5),
        "engine_steps_in_window": sum(t_start <= s[1] < t_start + args.seconds
                                      for s in loop.steps),
        "loop_account": engine.loop_account(),
    }
    if requests:
        split = A.ms_per_token(requests)
        A.note("whole_window", requests)
        line.update(
            requests_with_account=len(requests),
            mean_gap_ms=A.mean_gap_ms(requests),
            ms_per_token_by_group={
                g: sum(split.get(k, 0.0) for k in keys) for g, keys in GROUPS.items()},
            chunks_per_token_gap=A.total(requests, "n", "chunks") / A.gaps(requests),
            rows_per_launch=A.total(requests, "n", "decoded")
            / max(A.total(requests, "n", "decode_launched"), 1),
        )
    if traced:
        spans = program_trace.read_spans(trace.find_xplane(trace_dir))
        on_trace = span_self_seconds(spans)
        first, last = traced["first"], traced["last"]
        rows = {}
        for k in last.ns:
            account_s = (last.ns[k] - first.ns[k]) / 1e9
            if account_s or on_trace.get(k):
                span_s = on_trace.get(k, 0.0)
                rows[k] = {"account_s": account_s, "span_self_s": span_s,
                           "off_pct": 100.0 * (account_s - span_s) / span_s if span_s else None}
        line["account_against_trace"] = {
            "seconds": last.t - first.t, "buckets": rows,
            "iterations": last.n["iterations"] - first.n["iterations"],
            "serve_step_spans": sum(s["name"] == "serve.step" for s in spans)}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # the watchdog's threads must not keep the process alive

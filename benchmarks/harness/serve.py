"""Drive one serving cell: the program's ``ServingEngine`` under an open loop.

The engine is built as ``automodel_tpu serve`` builds it (mesh, model,
``ServeConfig``, ``GenerationConfig``, watchdog), with the benchmark's
weights. The loop is the benchmark's own copy of ``run_workload``: requests
are submitted when they are DUE, stamped with the time they were due (so a
stalled loop's lateness counts against the server, not for it), the engine
steps in between, and every record is stamped when ``step()`` hands it back.
A ramp at the cell's rate runs before the window and counts as set-up.
After the window no request arrives; the loop drains for ``drain_s`` and what
is still unfinished counts as failed and as the worst in every tail.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.harness import stalls
from benchmarks.harness import traffic as T
from benchmarks.harness import weights as W
from benchmarks.harness.loader import hf_config, program_hf_config


def program_config(cell: dict, seed: int, out_dir: str, n_devices: int) -> dict:
    """The program's own YAML for this cell, as a dict."""
    prog = cell["config"]["program"]
    return {
        "seed": int(seed) & 0x7FFFFFFF,
        "model": {"hf_config": program_hf_config(cell["config"]), "backend": dict(prog["backend"])},
        "distributed": dict(prog["distributed"]),
        "generation": dict(prog["generation"], seed=int(seed) & 0x7FFFFFFF),
        "serving": dict(prog["serving"]),
    }


def build_engine(cell: dict, seed: int):
    import jax

    from automodel_tpu import auto_model
    from automodel_tpu.generation.engine import GenerationConfig
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.serving.engine import ServeConfig, ServingEngine

    prog = cell["config"]["program"]
    devices = jax.devices()[: cell["chips"]]
    mesh_ctx = build_mesh(MeshConfig.from_section(dict(prog["distributed"])), devices=devices)
    auto = auto_model.from_config(
        program_hf_config(cell["config"]), mesh_ctx, dict(prog["backend"]), abstract=True
    )
    abstract = auto.params
    auto.params = W.make(abstract, seed)
    gen = dict(prog["generation"], seed=int(seed) & 0x7FFFFFFF)
    events: list = []
    engine = ServingEngine(
        auto, ServeConfig.from_dict(dict(prog["serving"])), GenerationConfig.from_dict(gen),
        on_record=lambda rec: events.append(rec) if rec.get("event") != "serve_request" else None,
    )
    engine.bench_events = events  # rebuilds, stalls: anything but a request's record
    engine.collect_program_costs = False
    engine.start_watchdog()
    return engine, abstract


class Loop:
    """submit-when-due / step, with the harness's spans and samples."""

    def __init__(self, engine, spans, watch=None):
        self.engine, self.spans, self.watch = engine, spans, watch
        self.records: dict[str, dict] = {}
        self.sent: dict[str, tuple[float, float]] = {}  # id -> (due, sent)
        # one row an engine.step(): (begin, end, busy slots, context tokens the
        # decode attention had to read, queue depth, this thread's CPU seconds)
        self.steps: list[tuple[float, float, int, int, int, float]] = []

    def _step(self) -> None:
        eng = self.engine
        begin, cpu = time.perf_counter(), time.thread_time()
        if self.watch is not None:
            self.watch.begin = begin
        with self.spans.span("engine.step"):
            done = eng.step()
        end = time.perf_counter()
        if self.watch is not None:
            self.watch.begin = None
        # context tokens: engine internals, the program has no counter for it yet
        self.steps.append((begin, end, eng.busy_slots,
                           int((eng._lengths * eng._active).sum()), eng.queue_depth,
                           time.thread_time() - cpu))
        for rec in done:
            rec["t_done"] = end
            self.records[rec["request_id"]] = rec

    def drive(self, requests: list, t0: float, prefix: str, until: float | None,
              on_tick=None) -> None:
        """Submit ``requests`` (offsets from ``t0``) as they fall due and step
        the engine until ``until`` (or, with None, until all are in and the
        engine is idle)."""
        eng = self.engine
        pending = sorted(
            ((t0 + off, f"{prefix}{i}", ids, n) for i, (off, ids, n) in enumerate(requests)),
            key=lambda r: r[0],
        )
        k = 0
        while True:
            now = time.perf_counter()
            if until is not None and now >= until:
                break
            if on_tick is not None:
                on_tick(now)
            while k < len(pending) and pending[k][0] <= now:
                due, rid, ids, n = pending[k]
                with self.spans.span("submit"):
                    eng.submit(ids, request_id=rid, max_new_tokens=n, t_submit=due)
                self.sent[rid] = (due, time.perf_counter())
                k += 1
            if eng.idle():
                if k >= len(pending) and until is None:
                    break
                nxt = pending[k][0] if k < len(pending) else until
                time.sleep(max(0.0, min(0.001, nxt - time.perf_counter())))
                continue
            self._step()

    def drain(self, deadline: float) -> None:
        while not self.engine.idle() and time.perf_counter() < deadline:
            self._step()

    def warm(self, vocab: int, seed: int) -> None:
        """One short request through every program (chunk prefill, the
        first-token sample, paged decode) before anything is timed."""
        rng = np.random.default_rng([int(seed), 0xAA])
        self.drive([(0.0, rng.integers(3, vocab, size=40).tolist(), 4)],
                   time.perf_counter(), "warm", until=None)

    def queue_depth_at(self, t: float) -> int:
        return next((s[4] for s in reversed(self.steps) if s[1] <= t), 0)


def free(engine, loop: Loop) -> None:
    """Give the device back before the reference runs."""
    import jax

    engine.stop_watchdog()
    engine.release_pools()
    engine.auto.params = None
    loop.engine = None
    jax.clear_caches()


def reduce_window(loop: Loop, window: list, t0: float, t1: float) -> dict:
    """End-to-end numbers over ALL requests due in the window."""
    ttft, tpot, queue = [], [], []
    failed = 0
    for i, (off, ids, n) in enumerate(window):
        rec = loop.records.get(f"w{i}")
        ok = (
            rec is not None and rec.get("completion_reason") in ("stop", "length")
            and rec.get("n_generated") == n and "ttft_s" in rec
        )
        if not ok:
            failed += 1
            ttft.append(math.inf)
            tpot.append(math.inf)
            queue.append(math.inf)
            continue
        ttft.append(float(rec["ttft_s"]))
        queue.append(float(rec["queue_s"]))
        if n > 1:
            tps = float(rec.get("decode_tps") or 0.0)
            tpot.append(1.0 / tps if tps > 0 else math.inf)
    # output tokens GENERATED inside the window, from the records alone: a
    # request's first token at due + ttft, the rest spread evenly up to the
    # moment step() handed the record back. (Tokens of requests COMPLETED in
    # the window swing by +-10 % with where one 512-token answer, 30 s of
    # decoding, falls against the window's edge.)
    out_tokens = 0.0
    completed_tokens = 0
    for r in loop.records.values():
        if r.get("completion_reason") not in ("stop", "length") or "ttft_s" not in r:
            continue
        n = int(r["n_generated"])
        if t0 <= r["t_done"] < t1:
            completed_tokens += n
        first = loop.sent[r["request_id"]][0] + float(r["ttft_s"])
        out_tokens += 1.0 if t0 <= first < t1 else 0.0
        if n > 1 and r["t_done"] > first:
            overlap = max(0.0, min(r["t_done"], t1) - max(first, t0))
            out_tokens += (n - 1) * overlap / (r["t_done"] - first)
    return {
        "failed": failed, "ttft": ttft, "tpot": tpot, "queue": queue,
        "output_tokens_in_window": out_tokens,
        "output_tokens_of_requests_completed_in_window": completed_tokens,
    }


def check_sample(cell: dict, seed: int, window: list, loop: Loop) -> list:
    """A seeded sample of the requests the window finished, the longest (by
    prompt + output) in it: [(prompt ids, served tokens)]."""
    n_check = int(cell["traffic"].get("check_requests", 6))
    finished = [
        i for i in range(len(window))
        if (r := loop.records.get(f"w{i}")) is not None
        and r.get("completion_reason") in ("stop", "length") and r.get("tokens")
    ]
    if not finished:
        return []
    longest = max(finished, key=lambda i: len(window[i][1]) + window[i][2])
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = [i for i in finished if i != longest]
    picks = [longest] + [int(i) for i in rng.permutation(rest)[: max(n_check - 1, 0)]]
    return [(window[i][1], list(loop.records[f"w{i}"]["tokens"])) for i in picks]


def served_token_gaps(cell: dict, abstract, seed: int, sample: list, precision: str = "f32",
                      alter: int = 0) -> dict:
    """Run the reference once over each prompt with its served tokens. For
    every served token: how far its reference logit lies below the
    reference's best at that position. With ``precision`` below f32 the
    control is read instead: the gap of the token the LOWER precision puts
    first at each of the same positions (no decoding needed). With ``alter``
    the gap of the token ``alter`` ids after each served one is read: what a
    token from nowhere looks like at that position."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import moe_decoder as R

    config = cell["config"]
    spec = R.DecoderSpec.from_config(hf_config(config), config["reference"])
    bare = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), abstract)
    params = W.make(bare, seed, reference_layout=True)
    gaps: list[float] = []
    n_rows = int(cell["traffic"]["output_tokens"]["max"])
    for prompt, served in sample:
        n, p = len(served), len(prompt)
        real = list(prompt) + list(served[:-1])
        # pad at the END to a power of two (no row sees a later one), and read
        # a fixed number of rows: a handful of shapes compile, whatever the seed
        size = max(1 << (len(real) + n_rows - 1).bit_length(), n_rows)
        ids = np.zeros((size,), np.int32)
        ids[: len(real)] = real
        start = jnp.int32(p - 1)
        logits = R.rows_logits(params, jnp.asarray(ids), start, spec, "f32", n_rows)[:n]
        best = jnp.max(logits, axis=-1)
        if precision == "f32":
            tok = jnp.asarray((np.asarray(served, np.int64) + alter) % spec.vocab_size, jnp.int32)
        else:
            low = R.rows_logits(params, jnp.asarray(ids), start, spec, precision, n_rows)
            tok = jnp.argmax(low[:n], axis=-1)
        got = jnp.take_along_axis(logits, tok[:, None], axis=1)[:, 0]
        gaps.extend(np.asarray(best - got, np.float64).tolist())
    ceiling = float(config["reference"]["limits"]["served_token_gap_ceiling"])
    return {"gaps": gaps, "tokens": len(gaps),
            "widest": max(gaps) if gaps else math.nan,
            "widest_five": sorted(gaps, reverse=True)[:5],
            "over_ceiling": sum(g > ceiling for g in gaps),
            "mean": float(np.mean(gaps)) if gaps else math.nan}


def run(cell: dict, args, ctx) -> dict:
    import jax

    from automodel_tpu.telemetry import compile_events

    traffic = cell["traffic"]
    if traffic.get("generator") != "open_loop":
        raise ValueError(f"serve traffic generator {traffic.get('generator')!r}")
    t0 = time.perf_counter()
    engine, abstract = build_engine(cell, args.seed)
    ctx.note("setup_split", engine_build_s=time.perf_counter() - t0)
    reqs = T.open_loop_requests(traffic, args.seed, args.seconds, int(cell["config"]["vocab_size"]))
    watch = stalls.StallWatch().start()
    loop = Loop(engine, ctx.spans, watch)

    t0 = time.perf_counter()
    loop.warm(int(cell["config"]["vocab_size"]), args.seed)
    ctx.note("setup_split", warm_requests_s=time.perf_counter() - t0)

    # ramp: the cell's own rate, before the window, as set-up
    ramp_s = float(traffic.get("ramp_s", 0.0))
    t_ramp = time.perf_counter()
    loop.drive(reqs["ramp"], t_ramp, "r", until=t_ramp + ramp_s)
    ctx.note("setup_split", ramp_s=time.perf_counter() - t_ramp)

    # -- the window ------------------------------------------------------------
    compiles_before = compile_events.compile_totals()
    ctx.window_opens()
    t_start = ctx.t_window
    t_end = t_start + float(args.seconds)
    trace_from = t_start + 0.25 * float(args.seconds)
    trace_len = min(4.0, 0.25 * float(args.seconds))

    def on_tick(now: float) -> None:
        if not ctx.trace:
            return
        if not ctx.tracing and not ctx.traced and now >= trace_from:
            ctx.start_trace()
        elif ctx.tracing and now >= ctx.trace_t0 + trace_len:
            ctx.stop_trace()

    loop.drive(reqs["window"], t_start, "w", until=t_end, on_tick=on_tick)
    if ctx.tracing:
        ctx.stop_trace()
    compiles_after = compile_events.compile_totals()
    ctx.window_closes()
    compiled_in_window = compiles_after["compiles"] - compiles_before["compiles"]
    trace_secs_in_window = compiles_after["compile_secs"] - compiles_before["compile_secs"]
    depth_mid, depth_end = loop.queue_depth_at(0.5 * (t_start + t_end)), loop.queue_depth_at(t_end)
    loop.drain(time.perf_counter() + float(traffic.get("drain_s", 30.0)))
    watch.stop()
    memory_peak = ctx.memory_peak_bytes()

    red = reduce_window(loop, reqs["window"], t_start, t_end)
    late = T.lateness(
        [loop.sent[f"w{i}"][0] for i in range(len(reqs["window"])) if f"w{i}" in loop.sent],
        [loop.sent[f"w{i}"][1] for i in range(len(reqs["window"])) if f"w{i}" in loop.sent],
    )
    never_sent = len(reqs["window"]) - late.get("n", 0)
    ctx.note("serve", generator_lateness=late, never_sent=never_sent,
             queue_depth_mid=depth_mid, queue_depth_end=depth_end,
             requests_due_in_window=len(reqs["window"]), failed=red["failed"],
             ttft_p50_s=T.percentile(red["ttft"], 0.5), tpot_p50_s=T.percentile(red["tpot"], 0.5),
             ttft_p95_s=T.percentile(red["ttft"], 0.95), tpot_p95_s=T.percentile(red["tpot"], 0.95),
             p95_sample_count=len(red["ttft"]), engine_steps=len(loop.steps),
             compile_secs_in_window=trace_secs_in_window,
             generated_tokens_per_s=red["output_tokens_in_window"] / float(args.seconds),
             tokens_per_s_of_requests_completed_in_window=(
                 red["output_tokens_of_requests_completed_in_window"] / float(args.seconds)),
             engine_events=[e.get("event") for e in engine.bench_events][:20],
             longest_steps=[
                 {"at_s": round(a - t_start, 3), "took_s": round(b - a, 3), "busy": busy,
                  "cpu_s": round(cpu, 3), "where": watch.seen.get(a)}
                 for a, b, busy, _, _, cpu in sorted(loop.steps, key=lambda s: s[0] - s[1])[:6]
             ],
             gc_s=watch.gc_s, gc_longest_s=watch.gc_longest_s,
             stall_looker_longest_gap_s=watch.looker_longest_gap_s)

    # -- free the program, then the reference ------------------------------------
    sample = check_sample(cell, args.seed, reqs["window"], loop)
    free(engine, loop)
    del engine
    t0 = time.perf_counter()
    limits = cell["config"]["reference"]["limits"]
    rows = []
    if sample:
        gaps = served_token_gaps(cell, abstract, args.seed, sample)
        rows.append((f"served_token_gap_mean[{gaps['tokens']}_tokens]", gaps["mean"],
                     limits["served_token_gap_mean"]))
        rows.append((f"served_tokens_over_gap_ceiling[{limits['served_token_gap_ceiling']}]",
                     float(gaps["over_ceiling"]), float(limits["served_tokens_over_ceiling"])))
        ctx.note("check", served_tokens_compared=gaps["tokens"], mean_gap=gaps["mean"],
                 widest_five_gaps=gaps["widest_five"], requests_compared=len(sample))
    else:
        rows.append(("no_finished_request_to_compare", 1.0, 0.0))
    ctx.note("check", reference_s=time.perf_counter() - t0)
    rows.append(("requests_failed_or_short", float(red["failed"]), 0.0))
    rows.append(("compiles_in_window", float(compiled_in_window), 0.0))
    ctx.print_comparison(rows)
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    window_s = float(args.seconds)
    return {
        "correct": bool(correct),
        "attempted": len(reqs["window"]),
        "failed": int(red["failed"]),
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"tpot_p50_s": T.percentile(red["tpot"], 0.5)},
        "artefacts": {
            "kind": "serve", "window_s": window_s,
            "output_tokens_in_window": red["output_tokens_in_window"],
            "queue_s": red["queue"], "ttft_s": red["ttft"], "tpot_s": red["tpot"],
            "slots": int(cell["config"]["program"]["serving"]["slots"]),
            "steps": [s for s in loop.steps if t_start <= s[1] < t_end],
            "records": [
                {k: v for k, v in r.items() if k != "tokens"} for r in loop.records.values()
            ],
        },
    }

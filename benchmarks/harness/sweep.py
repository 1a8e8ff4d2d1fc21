"""Find a serving mix's knee, once, when the cell is defined (or defined anew).

    python3 -m benchmarks.harness.sweep --workload <cell> --seed <n> --seconds <s> --rates 4,6,8

One process, one engine, one row a rate: the cell's own traffic with only the
arrival rate replaced, a ramp, a window, a drain. The knee is the highest
rate at which every request completes and the queue is no deeper at the
window's end than at its middle; the cell's traffic file then gets a fixed
rate (about four fifths of the knee, or above it) as a number. The benchmark
itself never searches.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)

    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import numpy as np

    from benchmarks.harness import loader, serve
    from benchmarks.harness import traffic as T
    from benchmarks.harness.spans import Spans

    cell = loader.load_cell(loader.load_benchmark(), args.workload)
    engine, _ = serve.build_engine(cell, args.seed)
    vocab = int(cell["config"]["vocab_size"])
    slots = int(cell["config"]["program"]["serving"]["slots"])
    serve.Loop(engine, Spans()).warm(vocab, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = copy.deepcopy(cell["traffic"])
        traffic["arrivals"]["rate_per_s"] = rate
        reqs = T.open_loop_requests(traffic, args.seed + i, args.seconds, vocab)
        loop = serve.Loop(engine, Spans())
        t_ramp = time.perf_counter()
        loop.drive(reqs["ramp"], t_ramp, "r", until=t_ramp + float(traffic["ramp_s"]))
        t0 = time.perf_counter()
        t1 = t0 + args.seconds
        loop.drive(reqs["window"], t0, "w", until=t1)
        mid, end = loop.queue_depth_at(0.5 * (t0 + t1)), loop.queue_depth_at(t1)
        loop.drain(time.perf_counter() + float(traffic["drain_s"]))
        red = serve.reduce_window(loop, reqs["window"], t0, t1)
        occ = [s[2] for s in loop.steps if t0 <= s[1] < t1]
        print(json.dumps({
            "rate_per_s": rate, "due": len(reqs["window"]), "failed": red["failed"],
            "generated_tokens_per_s": red["output_tokens_in_window"] / args.seconds,
            "ttft_p50_s": T.percentile(red["ttft"], 0.5), "ttft_p95_s": T.percentile(red["ttft"], 0.95),
            "tpot_p50_s": T.percentile(red["tpot"], 0.5), "tpot_p95_s": T.percentile(red["tpot"], 0.95),
            "queue_depth_mid": mid, "queue_depth_end": end,
            "occupancy_pct": 100.0 * float(np.mean(occ)) / slots if occ else 0.0,
            "idle_after_drain": engine.idle(),
        }), flush=True)
        if not engine.idle():
            loop.drain(time.perf_counter() + 120.0)
    engine.stop_watchdog()
    return 0


if __name__ == "__main__":
    import os

    code = main()
    sys.stdout.flush()
    os._exit(code)

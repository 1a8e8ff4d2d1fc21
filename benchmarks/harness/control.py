"""Read what a cell's limits are set from: the sound program and the control.

    python3 -m benchmarks.harness.control --workload <cell> --seeds 1,2,3 [--seconds 15]

For each seed, in one process: the program at the cell's own size (training:
its first three steps, no window; serving: a short window at the cell's own
load), the float32 reference, and the CONTROL: the reference put in the
program's place one precision below the one the configuration states (fp8
for bfloat16). Prints, per seed, every number ``correct`` compares, once for
the sound program and once for the control. A limit belongs above the sound
runs' largest and below the control's smallest. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CONTROL_PRECISION = {"bfloat16": "fp8", "float32": "bf16"}


def train_seed(cell, seed: int, out_dir: Path, control: str) -> dict:
    from benchmarks.harness import train
    from benchmarks.harness.spans import Spans

    cfg = train.program_config(cell, seed, str(out_dir), cell["chips"])
    recipe = train.build_recipe(cfg, seed)
    stepper = train.Stepper(recipe, Spans())
    b1 = float(cell["config"]["program"]["optimizer"]["betas"][0])
    program = train.first_steps(stepper, recipe.abstract_params, seed, b1)
    abstract = train.free(recipe, stepper)
    del recipe, stepper
    reference = train.reference_steps(cell, abstract, seed, program["batches"])
    low = train.reference_steps(cell, abstract, seed, program["batches"], precision=control)
    limits = cell["config"]["reference"]["limits"]
    _, sound = train.compare(program, reference, limits)
    _, ctrl = train.compare(low, reference, limits)
    return {"sound": {n: v for n, v, _ in sound}, "control": {n: v for n, v, _ in ctrl}}


def serve_seed(cell, seed: int, seconds: float, control: str) -> dict:
    from benchmarks.harness import serve
    from benchmarks.harness import traffic as T
    from benchmarks.harness.spans import Spans

    engine, abstract = serve.build_engine(cell, seed)
    reqs = T.open_loop_requests(cell["traffic"], seed, seconds, int(cell["config"]["vocab_size"]))
    loop = serve.Loop(engine, Spans())
    loop.warm(int(cell["config"]["vocab_size"]), seed)
    t0 = time.perf_counter()
    loop.drive(reqs["window"], t0, "w", until=t0 + seconds)
    loop.drain(time.perf_counter() + float(cell["traffic"].get("drain_s", 30.0)))
    sample = serve.check_sample(cell, seed, reqs["window"], loop)
    serve.free(engine, loop)
    del engine, loop
    out = {}
    for name, prec, alter in (("sound", "f32", 0), ("control", control, 0), ("altered_tokens", "f32", 1)):
        g = serve.served_token_gaps(cell, abstract, seed, sample, precision=prec, alter=alter)
        out[name] = {"mean": g["mean"], "widest": g["widest"], "widest_five": g["widest_five"],
                     "over_ceiling": g["over_ceiling"], "tokens": g["tokens"]}
    # a token from nowhere at each served position: the share the ceiling catches
    out["altered_tokens"]["smallest_five"] = sorted(g["gaps"])[:5]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from automodel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.harness import loader

    cell = loader.load_cell(loader.load_benchmark(), args.workload)
    if args.rehearse:
        from benchmarks.run import rehearse_overrides

        cell = rehearse_overrides(cell)
    control = CONTROL_PRECISION[cell["config"]["program"]["backend"]["compute_dtype"]]
    out_dir = ROOT / ".benchmark_out" / f"{args.workload}.control"
    out_dir.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if cell["traffic"]["kind"] == "train":
            res = train_seed(cell, seed, out_dir, control)
        else:
            res = serve_seed(cell, seed, args.seconds, control)
        print(json.dumps({"workload": args.workload, "seed": seed, "control_precision": control,
                          "seconds": time.perf_counter() - t0, **res}), flush=True)
    return 0


if __name__ == "__main__":
    import os

    code = main()
    sys.stdout.flush()
    os._exit(code)

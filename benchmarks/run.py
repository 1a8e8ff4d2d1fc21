"""Run one benchmark cell once, in this process, and print the contract's line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is data: BENCHMARK.json names its configuration
and traffic mix, ``benchmarks/configs`` and ``benchmarks/traffic`` hold them,
``benchmarks/metrics/<name>.py`` reads each per-layer metric. No TPU, fewer
chips than the cell asks for, a device missing from ``peaks.py`` or a
checkout without the program: a message on stderr, a non-zero exit, no
result line. ``--rehearse`` shrinks every size (``tests/rehearse.json``) for
a control-flow rehearsal on the CPU; its line says ``platform: cpu`` and
carries no device metric.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse
import copy
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def fail(msg: str, code: int = 2):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


class Ctx:
    """What one run shares between the driver and the result line."""

    def __init__(self, args, cell, out_dir: Path, device: dict):
        from benchmarks.harness.spans import Spans

        self.args, self.cell, self.out_dir, self.device = args, cell, out_dir, device
        self.trace = bool(args.trace)
        self.spans = Spans(annotate=self.trace)
        self.notes: dict = {}
        self.tracing = self.traced = False
        self.trace_steps = 8
        self.traced_steps = self.traced_tokens = 0
        self.trace_dir = out_dir / "trace"
        self.trace_t0 = self.trace_t1 = None
        self.t_window = None

    def note(self, group: str, **kv) -> None:
        self.notes.setdefault(group, {}).update(kv)

    def window_opens(self) -> None:
        self.t_window = time.perf_counter()
        self.setup_s = self.t_window - _T_PROCESS

    def window_closes(self) -> None:
        self.t_window_end = time.perf_counter()

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir))
        self.tracing = True
        self.trace_t0 = time.perf_counter()

    def stop_trace(self) -> None:
        import jax

        if not self.tracing:
            return
        for d in jax.local_devices():
            jax.device_put(0, d).block_until_ready()
        self.trace_t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.tracing, self.traced = False, True

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = []
        for d in jax.local_devices()[: self.cell["chips"]]:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)

    def print_comparison(self, rows) -> None:
        for name, value, limit in rows:
            verdict = "ok" if value <= limit else "OVER"
            print(json.dumps({"compared": name, "value": value, "limit": limit,
                              "verdict": verdict}), flush=True)


def rehearse_overrides(cell: dict) -> dict:
    """Tiny sizes for a CPU rehearsal (control flow only)."""
    over = json.loads((ROOT / "benchmarks" / "tests" / "rehearse.json").read_text())
    cell = copy.deepcopy(cell)
    cell["config"].update({k: v for k, v in over["config"].items() if k in cell["config"]})
    kind = cell["config"]["program"]["kind"]
    for section, values in over["program"].get(kind, {}).items():
        cell["config"]["program"].setdefault(section, {}).update(values)
    # a tiny model's logits spread otherwise: limits that hold at that size
    cell["config"]["reference"]["limits"].update(over.get("limits", {}).get(kind, {}))
    for key, values in over["traffic"].get(cell["traffic"]["kind"], {}).items():
        if isinstance(values, dict):
            cell["traffic"][key].update(values)
        else:
            cell["traffic"][key] = values
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--program-config", action="store_true",
                    help="print the program's own YAML for this cell (e.g. for "
                         "tools/compile_check.py) and exit")
    args = ap.parse_args(argv)

    if not (ROOT / "automodel_tpu" / "cli" / "app.py").exists():
        fail("the benchmark needs the program around it: automodel_tpu/ is missing")
    from benchmarks import peaks
    from benchmarks.harness import loader

    try:
        bench = loader.load_benchmark()
        cell = loader.load_cell(bench, args.workload)
    except (loader.BenchmarkError, OSError, KeyError, ValueError) as e:
        fail(f"{type(e).__name__}: {e}")
    if args.program_config:
        from benchmarks.harness import serve, train

        mod = {"train": train, "serve": serve}[cell["traffic"]["kind"]]
        import yaml

        print(yaml.safe_dump(mod.program_config(cell, args.seed, "runs/compile_check", cell["chips"])))
        return 0
    if args.rehearse:
        cell = rehearse_overrides(cell)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell["chips"] > 1:
            os.environ.setdefault(
                "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell['chips']}"
            )
    elif os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        fail("JAX_PLATFORMS=cpu: the benchmark measures the chip and runs nowhere else")

    from automodel_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        fail(f"no TPU: JAX reports platform {platform!r}")
    if len(devices) < cell["chips"]:
        fail(f"cell {args.workload} asks for {cell['chips']} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if not args.rehearse:
        try:
            peaks.lookup(kind)
        except KeyError as e:
            fail(str(e))
    device = {"platform": platform, "kind": kind, "count": cell["chips"]}
    if len(devices) > cell["chips"] and cell["config"]["program"]["kind"] == "train":
        fail(f"{len(devices)} devices visible, the cell uses {cell['chips']}: run it on a "
             "machine that holds exactly the chips it asks for")

    out_dir = ROOT / ".benchmark_out" / args.workload
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    ctx = Ctx(args, cell, out_dir, device)
    ctx.note("setup_split", imports_and_devices_s=time.perf_counter() - _T_PROCESS,
             compile_cache_dir=cache_dir)

    from automodel_tpu.telemetry import compile_events

    compile_events._ensure_registered()
    from benchmarks.harness import serve, train

    driver = {"train": train, "serve": serve}[cell["traffic"]["kind"]]
    result = driver.run(cell, args, ctx)

    from benchmarks.harness import report

    line = report.result_line(result, ctx, compile_events.compile_totals())
    print(json.dumps({"notes": ctx.notes}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # threads the program started (watchdogs, guards) must not keep the
    # process alive past its result
    os._exit(code)

"""Assemble the contract's last line from what a driver returned."""

from __future__ import annotations

import json
import math

from benchmarks import peaks
from benchmarks.harness import loader, trace


def _value(v: float, unit: str) -> dict:
    return {"value": float(v), "unit": unit}


def result_line(result: dict, ctx, compile_totals: dict | None) -> dict:
    cell = ctx.cell
    on_chip = ctx.device["platform"] == "tpu"
    device = dict(ctx.device)
    device["memory_peak_bytes"] = int(result["memory_peak_bytes"])
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "workload": cell["workload"],
        "seed": int(ctx.args.seed),
        "seconds": float(ctx.args.seconds),
    }
    metrics: dict = {}
    if not ctx.trace:
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        for m in cell["end_to_end"]:
            if m["name"] in values and math.isfinite(values[m["name"]]):
                metrics[m["name"]] = _value(values[m["name"]], m["unit"])
            elif m["name"] in values:
                print(json.dumps({"not_finite": m["name"]}), flush=True)
                line["correct"] = False
    else:
        reduction = None
        if ctx.traced:
            reduction = trace.reduce_file(trace.find_xplane(ctx.trace_dir))
        run = {
            "artefacts": result["artefacts"], "reduction": reduction,
            "compile": compile_totals or {}, "spans": ctx.spans, "cell": cell,
            "device": device, "window": (ctx.t_window, ctx.t_window_end),
            "trace_window": (ctx.trace_t0, ctx.trace_t1),
            "traced_steps": ctx.traced_steps, "traced_tokens": ctx.traced_tokens,
            "peaks": peaks.lookup(device["kind"]) if on_chip else None,
            "notes": ctx.notes,
        }
        for m in cell["per_layer"]:
            reader = loader.load_module("metrics", m["name"])
            if m["source"] == "device_trace" and not on_chip:
                continue  # never a CPU number under a device metric's name
            value = reader.read(run)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = _value(value, m["unit"])
        if reduction and reduction["devices"] and on_chip:
            devs = reduction["devices"]
            device["busy_s"] = sum(d["busy_s"] for d in devs.values()) / len(devs)
            device["window_s"] = max(d["window_s"] for d in devs.values())
            first = devs[min(devs)]
            ops = sorted(first["op_sums"].items(), key=lambda kv: -kv[1])[:10]
            line["breakdown"] = {
                "device_ops": [[n[:80], s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in reduction["idle_gaps"]],
            }
    line["metrics"] = metrics
    line["device"] = device
    return line

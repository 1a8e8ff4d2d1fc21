"""From the profiler's trace (.xplane.pb) to busy time, gaps and kernel sums.

    python3 -m benchmarks.harness.trace <file.xplane.pb>      # look at one by hand

Per DEVICE, never merged: the busy time is the union of that device's op
intervals inside the device's own window (first op start to last op end of
the traced span), so four chips give four readings. Host annotations written
by the harness (``bench:<span>``) share the trace's clock and name what the
host was doing in each idle gap.
"""

from __future__ import annotations

import bisect
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"alltoall|allgather|allreduce|ragged-all-to-all", re.I)
ANNOTATION_PREFIX = "bench:"


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _module_base(name: str) -> str:
    """``jit_step_fn(123456789)`` -> ``jit_step_fn``: the id changes per compile."""
    return re.sub(r"\(\d+\)$", "", name)


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """-> (covered length, merged intervals); input need not be sorted."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def _subtract(intervals, cover) -> float:
    """Length of ``intervals`` (merged, sorted) not covered by ``cover`` (merged, sorted)."""
    total, j = 0.0, 0
    for a, b in intervals:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            total += b - cur
    return total


def short_name(name: str) -> str:
    """A device op's event name is its HLO text (``%fused_expert_mlp_fwd.12 =
    bf16[...] custom-call(...)``): keep the instruction's own name, which for
    a Pallas call is the kernel's."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def read_events(path: Path) -> dict:
    """-> {"devices": {id: {"ops": [(start_s, end_s, name)], "modules": [...]}},
           "host": [(start_s, end_s, name)]}; times in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dev["ops"].append((s, s + ev.duration_ns * 1e-9, short_name(ev.name)))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dev["modules"].append((s, s + ev.duration_ns * 1e-9, ev.name))
            out["devices"][int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        s = ev.start_ns * 1e-9
                        out["host"].append(
                            (s, s + ev.duration_ns * 1e-9, ev.name[len(ANNOTATION_PREFIX):])
                        )
    return out


def reduce_events(events: dict, top: int = 10, min_gap_s: float = 20e-6) -> dict:
    """The numbers every metric reader shares. Per device: window, busy, idle,
    per-op-name sums and counts, per-module durations, collective time and
    the part of it during which no other op ran; longest gaps by host span."""
    devices = {}
    for dev_id, dev in sorted(events["devices"].items()):
        ops = dev["ops"]
        if not ops:
            continue
        start = min(o[0] for o in ops)
        end = max(o[1] for o in ops)
        busy, merged = _union([(o[0], o[1]) for o in ops])
        sums: dict[str, float] = {}
        counts: dict[str, int] = {}
        for s, e, name in ops:
            sums[name] = sums.get(name, 0.0) + (e - s)
            counts[name] = counts.get(name, 0) + 1
        coll = [(o[0], o[1]) for o in ops if COLLECTIVE.search(o[2])]
        comp = [(o[0], o[1]) for o in ops if not COLLECTIVE.search(o[2])]
        coll_s, coll_m = _union(coll)
        _, comp_m = _union(comp)
        modules: dict[str, list[float]] = {}
        mods = sorted(dev["modules"])
        for s, e, name in mods:
            modules.setdefault(_module_base(name), []).append(e - s)
        # each op belongs to the module event that contains its start
        starts = [m[0] for m in mods]
        module_ops: dict[str, dict[str, float]] = {}
        for s, e, name in ops:
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < mods[k][1]:
                per = module_ops.setdefault(_module_base(mods[k][2]), {})
                per[name] = per.get(name, 0.0) + (e - s)
        gaps = [
            (merged[i][1], merged[i + 1][0])
            for i in range(len(merged) - 1)
            if merged[i + 1][0] - merged[i][1] >= min_gap_s
        ]
        devices[dev_id] = {
            "window_s": end - start, "busy_s": busy, "start_s": start, "end_s": end,
            "op_sums": sums, "op_counts": counts,
            "modules": modules, "module_ops": module_ops,
            "collective_s": coll_s,
            "collective_exposed_s": _subtract(coll_m, comp_m),
            "gaps": gaps,
        }
    host = sorted(events["host"])

    def span_at(t: float) -> str:
        inner, width = "outside_spans", float("inf")
        for s, e, name in host:
            if s <= t < e and e - s < width:
                inner, width = name, e - s
        return inner

    gap_by_span: dict[str, float] = {}
    if devices:
        first = devices[min(devices)]
        for a, b in first["gaps"]:
            name = span_at(0.5 * (a + b))
            gap_by_span[name] = gap_by_span.get(name, 0.0) + (b - a)
    return {
        "devices": devices,
        "host_spans": host,
        "idle_gaps": sorted(gap_by_span.items(), key=lambda kv: -kv[1])[:top],
    }


def reduce_file(path: Path) -> dict:
    return reduce_events(read_events(path))


def op_time(reduction: dict, pattern: str, device: int | None = None) -> tuple[float, int]:
    """Summed duration and count of the ops whose name matches ``pattern``,
    on one device (the first by default)."""
    devs = reduction["devices"]
    dev = devs[min(devs) if device is None else device]
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for name, s in dev["op_sums"].items():
        if rx.search(name):
            total += s
            n += dev["op_counts"][name]
    return total, n


def module_op_time(reduction: dict, module_pattern: str, op_pattern: str,
                   device: int | None = None) -> float:
    """Summed duration of the ops matching ``op_pattern`` that ran inside
    module events matching ``module_pattern``."""
    devs = reduction["devices"]
    dev = devs[min(devs) if device is None else device]
    mrx, orx = re.compile(module_pattern), re.compile(op_pattern)
    total = 0.0
    for mod, per in dev["module_ops"].items():
        if mrx.search(mod):
            total += sum(s for name, s in per.items() if orx.search(name))
    return total


def module_durations(reduction: dict, pattern: str, device: int | None = None) -> list[float]:
    devs = reduction["devices"]
    dev = devs[min(devs) if device is None else device]
    rx = re.compile(pattern)
    out: list[float] = []
    for name, durs in dev["modules"].items():
        if rx.search(name):
            out.extend(durs)
    return out


def dump(path: Path, top: int = 40) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            sums: dict[str, list] = {}
            for ev in evs:
                rec = sums.setdefault(ev.name, [0.0, 0, ev])
                rec[0] += ev.duration_ns * 1e-9
                rec[1] += 1
            for name, (s, n, ev) in sorted(sums.items(), key=lambda kv: -kv[1][0])[:top]:
                stats = {k: (str(v)[:160]) for k, v in ev.stats}
                print(f"    {s:10.6f}s x{n:<6d} {name[:100]!r} stats={stats}")


if __name__ == "__main__":
    target = Path(sys.argv[1])
    dump(find_xplane(target) if target.is_dir() else target)

"""What the PROGRAM says about itself inside a profiler trace (.xplane.pb).

    python3 -m benchmarks.harness.program_trace <file.xplane.pb | trace dir>

Two things ``harness/trace.py`` does not read:

* **Device side.** Every op's event METADATA carries ``tf_op`` (the JAX
  op-name path, where the program's ``jax.named_scope``s land) and ``source``
  (file:line of the user frame). ``jax.profiler.ProfileData`` shows only an
  event's own stats, so the few XSpace fields needed are decoded here by hand
  (planes -> event_metadata -> stats, stat_metadata names, the ``XLA Ops`` and
  ``XLA Modules`` lines). Every instant of the ``XLA Ops`` line is given to
  the INNERMOST op covering it (self time: a ``while`` owns only what its body
  does not), and an op's group is the innermost name of ``VOCABULARY`` found
  in its path; ``transpose(`` in the path marks backward,
  ``rematted_computation`` marks recompute; no name -> ``unscoped``.
* **Host side.** The program's spans are the host-plane events named
  ``serve.*`` / ``train.*`` (``jax.profiler.TraceAnnotation`` inside
  ``ServingEngine.step`` and the train input path) with their stats; children
  by containment on one thread; a device idle gap is cut at the spans' edges
  and each piece goes to the innermost program span covering it.

A program without scopes or spans (an older commit) gives empty tables, and
every reader built on this returns None for it.
"""

from __future__ import annotations

import bisect
import functools
import re
import statistics
import sys
from pathlib import Path

from benchmarks.harness import loader, trace

# The program's scope vocabulary (automodel_tpu/utils/profiler.SCOPES); a test
# keeps the two equal. "a/b" is scope b inside scope a.
VOCABULARY = (
    "embed", "layers", "norm", "attn", "kv_write",
    "moe/router", "moe/dispatch", "moe/experts", "moe/combine", "mlp",
    "final_norm", "lm_head_ce", "lm_head", "sample",
    "grad_accum", "grad_clip", "anomaly", "optimizer",
)
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("serve.", "train.")
DIRECTIONS = ("fwd", "bwd", "remat")

_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_LEAVES = {v.rsplit("/", 1)[-1]: v for v in VOCABULARY}


# -- the op-name path ----------------------------------------------------------


def path_segments(tf_op: str) -> list[str]:
    """``jit(step_fn)/transpose(jvp(layers))/while/body/attn/dot_general:`` ->
    [jit(step_fn), layers, while, body, attn, dot_general]: JAX writes a
    transform around the scope it was applied under."""
    out = []
    for seg in tf_op.rstrip(":").split("/"):
        while (m := _TRANSFORM.match(seg)) is not None:
            seg = m.group(1)
        out.append(seg)
    return out


def scope_of(tf_op: str) -> str:
    """The innermost vocabulary name in the path; a two-level name needs its
    first level earlier in the path."""
    segs = path_segments(tf_op)
    for i in range(len(segs) - 1, -1, -1):
        name = _LEAVES.get(segs[i])
        if name is None:
            continue
        outer = name.split("/")[:-1]
        if all(o in segs[:i] for o in outer):
            return name
    return UNSCOPED


def direction_of(tf_op: str) -> str:
    if "rematted_computation" in tf_op:
        return "remat"
    return "bwd" if "transpose(" in tf_op else "fwd"


# -- the XSpace fields, by hand -------------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: a varint's value, or a
    view of a length-delimited field's bytes (fixed64/32: their raw bytes)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wt == 1:
            val = buf[i:i + 8]
            i += 8
        elif wt == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an xplane")
        yield num, wt, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf) -> tuple[int, object]:
    """XStat -> (stat metadata id, value); a ``ref_value`` stays ("ref", id)."""
    key, value = 0, None
    for num, wt, val in _fields(buf):
        if num == 1:
            key = val
        elif num in (3, 4):
            value = val
        elif num in (5, 6):
            value = _text(val)
        elif num == 7:
            value = ("ref", val)
    return key, value


def _map_entry(buf) -> tuple[int, object]:
    key, value = 0, b""
    for num, _, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _plane_name(buf) -> str:
    for num, wt, val in _fields(buf):
        if num == 2 and wt == 2:
            return _text(val)
    return ""


def _line(buf, wanted: tuple[str, ...]):
    """XLine -> (name, [(metadata id, start_ps, duration_ps)]) or None."""
    name, t0_ns, raw = "", 0, []
    for num, wt, val in _fields(buf):
        if num == 2 and wt == 2:
            name = _text(val)
        elif num == 3:
            t0_ns = val
        elif num == 4:
            raw.append(val)
    if name not in wanted:
        return None
    events = []
    for ev in raw:
        meta = off = dur = 0
        for num, _, val in _fields(ev):
            if num == 1:
                meta = val
            elif num == 2:
                off = val
            elif num == 3:
                dur = val
        events.append((meta, t0_ns * 1000 + off, dur))
    return name, events


def read_device_ops(path: Path) -> dict:
    """-> {device id: {"ops": [(start_ps, duration_ps, name, tf_op, source)],
    "modules": [(start_ps, duration_ps, name)]}}; ``tf_op`` / ``source`` are
    "" where the op's metadata has none."""
    data = memoryview(Path(path).read_bytes())
    out = {}
    for num, wt, plane in _fields(data):
        if num != 1 or wt != 2:
            continue
        m = trace.DEVICE_PLANE.match(_plane_name(plane))
        if not m:
            continue
        lines, metas, stat_names = [], {}, {}
        for num2, wt2, val in _fields(plane):
            if wt2 != 2:
                continue
            if num2 == 3:
                line = _line(val, (trace.OPS_LINE, trace.MODULES_LINE))
                if line is not None:
                    lines.append(line)
            elif num2 == 4:
                key, meta = _map_entry(val)
                metas[key] = meta
            elif num2 == 5:
                key, meta = _map_entry(val)
                stat_names[key] = next(
                    (_text(v) for n, w, v in _fields(meta) if n == 2 and w == 2), "")
        wanted = {k for k, v in stat_names.items() if v in ("tf_op", "source")}
        decoded: dict[int, tuple[str, str, str]] = {}

        def meta_of(key: int) -> tuple[str, str, str]:
            if key not in decoded:
                name, got = "", {}
                for n, w, v in _fields(metas.get(key, b"")):
                    if n == 2 and w == 2:
                        name = _text(v)
                    elif n == 5 and w == 2:
                        sk, sv = _stat(v)
                        if sk in wanted:
                            if isinstance(sv, tuple):
                                sv = stat_names.get(sv[1], "")
                            got[stat_names[sk]] = sv or ""
                decoded[key] = (name, got.get("tf_op", ""), got.get("source", ""))
            return decoded[key]

        dev = {"ops": [], "modules": []}
        for name, events in lines:
            for meta, start, dur in events:
                full, tf_op, source = meta_of(meta)
                if name == trace.OPS_LINE:
                    dev["ops"].append((start, dur, trace.short_name(full), tf_op, source))
                else:
                    dev["modules"].append((start, dur, full))
        out[int(m.group(1))] = dev
    return out


# -- self time -------------------------------------------------------------------


def self_times(ops: list) -> list[int]:
    """For [(start, duration, ...)] that nest on one line: the time of each not
    covered by the ops inside it. Same unit as the input."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [o[1] for o in ops]
    stack: list[int] = []
    for i in order:
        start, dur = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            parent_end = ops[stack[-1]][0] + ops[stack[-1]][1]
            own[stack[-1]] -= max(0, min(start + dur, parent_end) - start)
        stack.append(i)
    return own


def scope_table(dev: dict) -> dict:
    """One device's ops -> per module run, self seconds by scope and direction.

    {"runs": {module: [{scope: {direction: s}}]}, "unscoped": {(module, what): s}
    (what = the op's source line, or its name where it has none), "busy_s",
    "scoped_s", "tagged_s" (self time of ops that carry ``tf_op``)}."""
    ops = dev["ops"]
    own = self_times(ops)
    mods = sorted(dev["modules"])
    starts = [m[0] for m in mods]
    runs: dict[str, list[dict]] = {}
    index: dict[int, dict] = {}
    for k, (_, _, name) in enumerate(mods):
        index[k] = {}
        runs.setdefault(trace._module_base(name), []).append(index[k])
    unscoped: dict[tuple[str, str], float] = {}
    busy = scoped = tagged = 0.0
    for (start, _, name, tf_op, source), mine in zip(ops, own):
        s = mine * 1e-12
        busy += s
        if tf_op:
            tagged += s
        k = bisect.bisect_right(starts, start) - 1
        inside = k >= 0 and start < mods[k][0] + mods[k][1]
        module = trace._module_base(mods[k][2]) if inside else "outside_modules"
        scope = scope_of(tf_op) if tf_op else UNSCOPED
        if scope == UNSCOPED:
            key = (module, "/".join(source.split("/")[-2:]) if source else name)
            unscoped[key] = unscoped.get(key, 0.0) + s
        else:
            scoped += s
        if inside:
            cell = index[k].setdefault(scope, dict.fromkeys(DIRECTIONS, 0.0))
            cell[direction_of(tf_op)] += s
    return {"runs": runs, "unscoped": unscoped, "busy_s": busy, "scoped_s": scoped,
            "tagged_s": tagged}


def per_run_ms(table: dict, module_pattern: str, keep) -> list[float]:
    """Per run of the modules matching ``module_pattern``: the milliseconds of
    the (scope, direction) cells for which ``keep(scope, direction)``."""
    rx = re.compile(module_pattern)
    out = []
    for module, runs in table["runs"].items():
        if not rx.search(module):
            continue
        for run in runs:
            out.append(1e3 * sum(s for scope, cell in run.items()
                                 for d, s in cell.items() if keep(scope, d)))
    return out


def median_by_scope_ms(table: dict, module_pattern: str) -> dict:
    """{scope: {direction: median ms a run}} over the matching modules' runs."""
    rx = re.compile(module_pattern)
    runs = [r for m, rs in table["runs"].items() if rx.search(m) for r in rs]
    out = {}
    for scope in sorted({s for r in runs for s in r}):
        out[scope] = {
            d: round(1e3 * statistics.median(r.get(scope, {}).get(d, 0.0) for r in runs), 4)
            for d in DIRECTIONS
        }
    return out


# -- the program's spans ---------------------------------------------------------


def read_spans(path: Path) -> list[dict]:
    """The host-plane events named ``serve.*`` / ``train.*``: [{"name",
    "start_s", "end_s", "stats", "thread", "parent" (index or None)}], children
    by containment on one thread, in start order."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: list[dict] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            mine = []
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    s = ev.start_ns * 1e-9
                    mine.append({"name": ev.name, "start_s": s,
                                 "end_s": s + ev.duration_ns * 1e-9,
                                 "stats": dict(ev.stats), "thread": f"{line.name}#{k}",
                                 "parent": None})
            spans.extend(nest(mine, base=len(spans)))
    return spans


def nest(spans: list[dict], base: int = 0) -> list[dict]:
    """Sort one thread's spans by start and give each the index (``base`` +
    position) of the innermost span that contains it."""
    spans = sorted(spans, key=lambda s: (s["start_s"], -s["end_s"]))
    stack: list[int] = []
    for i, sp in enumerate(spans):
        while stack and spans[stack[-1]]["end_s"] < sp["end_s"]:
            stack.pop()
        sp["parent"] = base + stack[-1] if stack else None
        stack.append(i)
    return spans


def children_of(spans: list[dict], i: int) -> list[dict]:
    return [s for s in spans if s["parent"] == i]


def gaps_by_span(gaps: list[tuple[float, float]], spans: list[dict]) -> list[tuple]:
    """Device idle gaps -> [(seconds, innermost program span or None, that
    span's outermost ancestor or None)]. A gap is cut at the edges of the spans
    it meets and each piece goes to the innermost span covering it: one gap
    between two decode programs runs through the end of a wait, the records,
    the caller's loop, the next admission and the next launch, and a whole-gap
    rule (its midpoint, as ``harness/trace.py`` names gaps by the harness's
    spans) would hand all of it to whichever of them holds the middle."""
    out = []
    for a, b in gaps:
        near = [i for i, sp in enumerate(spans) if sp["start_s"] < b and sp["end_s"] > a]
        cuts = sorted({a, b, *(t for i in near for t in (spans[i]["start_s"], spans[i]["end_s"])
                               if a < t < b)})
        for lo, hi in zip(cuts, cuts[1:]):
            t = 0.5 * (lo + hi)
            inner = None
            for i in near:
                sp = spans[i]
                if sp["start_s"] <= t < sp["end_s"] and (
                    inner is None
                    or sp["end_s"] - sp["start_s"]
                    < spans[inner]["end_s"] - spans[inner]["start_s"]
                ):
                    inner = i
            root = inner
            while root is not None and spans[root]["parent"] is not None:
                root = spans[root]["parent"]
            out.append((hi - lo, None if inner is None else spans[inner]["name"],
                        None if root is None else spans[root]["name"]))
    return out


# -- what the metric readers share ------------------------------------------------


@functools.lru_cache(maxsize=2)
def _device_tables(path: str) -> dict:
    return {dev: scope_table(ops) for dev, ops in read_device_ops(Path(path)).items()}


@functools.lru_cache(maxsize=2)
def _spans(path: str) -> tuple:
    return tuple(read_spans(Path(path)))


def xplane_of(run: dict):
    """The traced run's file (``run.py`` writes it under .benchmark_out/<cell>/trace)."""
    if not run.get("reduction"):
        return None
    try:
        return trace.find_xplane(loader.ROOT / ".benchmark_out" / run["cell"]["workload"] / "trace")
    except FileNotFoundError:
        return None


def device_table(run: dict, need_scopes: bool = True):
    """The first device's scope table, or None without device ops or (unless
    the reader needs no scope: recompute is marked by JAX itself) without a
    single op that carries a vocabulary name, as on an older commit."""
    path = xplane_of(run)
    tables = _device_tables(str(path)) if path is not None else {}
    if not tables:
        return None
    table = tables[min(tables)]
    return table if table["scoped_s"] > 0 or not need_scopes else None


def spans(run: dict, prefix: str):
    """The program's spans named ``prefix``*, or None when the trace has none."""
    path = xplane_of(run)
    if path is None:
        return None
    all_spans = list(_spans(str(path)))
    return all_spans if any(s["name"].startswith(prefix) for s in all_spans) else None


def device_gaps(run: dict):
    """Idle gaps of the first device as ``harness/trace.py`` found them."""
    red = run.get("reduction")
    if not red or not red["devices"]:
        return None
    return red["devices"][min(red["devices"])]["gaps"]


def median_ms(run: dict, module_pattern: str, keep, need_scopes: bool = True):
    """Median over the matching modules' runs of the self milliseconds in the
    (scope, direction) cells that ``keep`` takes; None without a table."""
    table = device_table(run, need_scopes)
    if table is None:
        return None
    values = per_run_ms(table, module_pattern, keep)
    return statistics.median(values) if values else None


def moe_overhead(scope: str, direction: str) -> bool:
    """The sparse block without its expert matmuls: router, dispatch, combine."""
    return scope.startswith("moe/") and scope != "moe/experts"


def largest_unscoped(table: dict, n: int = 8) -> list:
    """[[module, source line or op name, seconds]] of the ops no scope covers."""
    top = sorted(table["unscoped"].items(), key=lambda kv: -kv[1])[:n]
    return [[module, what, round(s, 6)] for (module, what), s in top]


def serve_iterations(run: dict):
    """[(the ``serve.step`` span, its children in order)] or None."""
    all_spans = spans(run, "serve.step")
    if all_spans is None:
        return None
    kids: dict[int, list] = {}
    for sp in all_spans:
        if sp["parent"] is not None:
            kids.setdefault(sp["parent"], []).append(sp)
    return [(sp, kids.get(i, [])) for i, sp in enumerate(all_spans)
            if sp["name"] == "serve.step"]


def iteration_counts(run: dict):
    """The integer stats of each iteration's ``serve.counts``, in order."""
    its = serve_iterations(run)
    if its is None:
        return None
    rows = [{k: int(v) for k, v in kid["stats"].items()}
            for _, kids in its for kid in kids if kid["name"] == "serve.counts"]
    return rows or None


def dump(path: Path) -> None:
    for dev, table in _device_tables(str(path)).items():
        print(f"device {dev}: busy {table['busy_s']:.4f} s, carrying tf_op "
              f"{100 * table['tagged_s'] / max(table['busy_s'], 1e-12):.1f} %, scoped "
              f"{100 * table['scoped_s'] / max(table['busy_s'], 1e-12):.1f} %")
        for module, runs in table["runs"].items():
            print(f"  {module} x{len(runs)}: median ms a run by scope (fwd/bwd/remat)")
            for scope, cell in median_by_scope_ms(table, f"^{re.escape(module)}$").items():
                print(f"    {scope:14s} " + " ".join(f"{cell[d]:9.3f}" for d in DIRECTIONS))
        for module, what, s in largest_unscoped(table, 15):
            print(f"  unscoped {s:9.5f} s  {module}  {what}")
    by_name: dict[str, list[float]] = {}
    for sp in _spans(str(path)):
        by_name.setdefault(sp["name"], []).append(sp["end_s"] - sp["start_s"])
    for name, durs in sorted(by_name.items()):
        print(f"span {name:28s} x{len(durs):<6d} median {1e3 * statistics.median(durs):8.3f} ms")


if __name__ == "__main__":
    target = Path(sys.argv[1])
    dump(trace.find_xplane(target) if target.is_dir() else target)

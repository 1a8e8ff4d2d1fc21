"""Device self time under scopes the program names and the harness's frozen
vocabulary does not hold (``harness/program_trace.VOCABULARY``): an op belongs
to scope S when S is a segment of its ``tf_op`` path
(``program_trace.path_segments``), whatever encloses it. On a program that
never writes the scope (an older commit) every reader built on this returns
None."""

from __future__ import annotations

import bisect
import functools
import re
import statistics
from pathlib import Path

from benchmarks.harness import program_trace, trace


@functools.lru_cache(maxsize=4)
def _per_run_seconds(path: str, module_pattern: str, scopes: tuple) -> tuple:
    """Per run of the matching modules on the first device: self seconds of
    the ops under any of ``scopes``; () when no op anywhere carries one."""
    devices = program_trace.read_device_ops(Path(path))
    if not devices:
        return ()
    dev = devices[min(devices)]
    rx = re.compile(module_pattern)
    mods = sorted(dev["modules"])
    starts = [m[0] for m in mods]
    per_run = {k: 0.0 for k, m in enumerate(mods) if rx.search(trace._module_base(m[2]))}
    seen = False
    for (start, _, _, tf_op, _), own in zip(dev["ops"], program_trace.self_times(dev["ops"])):
        if not tf_op or not set(scopes) & set(program_trace.path_segments(tf_op)):
            continue
        seen = True
        k = bisect.bisect_right(starts, start) - 1
        if k in per_run and start < mods[k][0] + mods[k][1]:
            per_run[k] += own * 1e-12
    return tuple(per_run.values()) if seen else ()


def median_ms(run: dict, module_pattern: str, *scopes: str):
    path = program_trace.xplane_of(run)
    if path is None:
        return None
    values = _per_run_seconds(str(path), module_pattern, scopes)
    return 1e3 * statistics.median(values) if values else None

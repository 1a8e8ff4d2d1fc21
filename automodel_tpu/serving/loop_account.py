"""The serve loop's own account of where its thread's time goes.

``ServingEngine.step`` marks its phases with ONE primitive,
``with account.phase("decode_dispatch"):``, which has two sinks:

* the profiler's: the same ``jax.profiler.TraceAnnotation`` (``serve.<phase>``
  with its stats) a trace viewer and ``benchmarks/harness/program_trace.py``
  read, costing a flag test while no trace runs;
* the account's, always on: the phase's ``time.perf_counter_ns`` duration,
  the clock that times a request (``t_first``, ``decode_tps``), added to a
  cumulative total a bucket. A bucket holds a phase's SELF time: a phase
  opened inside another takes its extent out of the outer one. ``step`` is
  ``serve.step``'s own remainder (expiry, drain, swap, watchdog, glue) and
  ``outside_step`` everything between one ``step()``'s return and the next's
  entry (the caller's loop), so every nanosecond of the loop's thread since
  the engine was built is in exactly one bucket.

The iteration's integers accumulate beside the buckets; the ``serve.counts``
event that closes an iteration takes its arguments from the iteration's delta
of them, so the trace and the account cannot disagree. A request's record
carries the account's difference between its first token and its end
(``between``): what the loop did during THAT request's token gaps.

Only the loop's thread writes. ``totals`` may be read from another thread
under the engine-loop lock (``server.stats_snapshot``'s contract); without it
the bucket in progress may be one phase stale.
"""

from __future__ import annotations

import functools
from time import perf_counter_ns
from typing import Any, NamedTuple, Optional

from jax.profiler import TraceAnnotation

BUCKETS = (
    "outside_step", "step", "admit", "prefill_dispatch", "first_token_wait",
    "decode_plan", "decode_dispatch", "decode_h2d", "decode_launch",
    "decode_wait", "record", "spec_propose", "spec_verify",
)
# the integers `serve.counts` closes an iteration with
ITERATION_COUNTS = (
    "admitted", "chunks", "decoded", "context_tokens", "state_resets",
    "attn_grid_steps", "attn_live_steps", "expert_grid_units",
    "expert_live_units", "decode_launched", "decode_launched_ahead",
    "discarded_rows",
    # a latent layout's rows the decode attention had to read (all layers); a
    # held share's picks that landed on the experts this chip holds
    "latent_context_rows", "held_expert_rows",
)
COUNTS = ("iterations", "first_token_waits") + ITERATION_COUNTS
# what of them a request's slice keeps (docs/serving.md "decode_account")
REQUEST_COUNTS = (
    "iterations", "chunks", "first_token_waits", "decode_launched",
    "decode_launched_ahead", "decoded", "context_tokens",
    "expert_live_units", "expert_grid_units", "discarded_rows",
)


_SPAN = {bucket: f"serve.{bucket}" for bucket in BUCKETS}


class Snapshot(NamedTuple):
    """The account at one instant of its own clock."""

    t_ns: int
    ns: dict
    n: dict

    @property
    def t(self) -> float:
        """The instant as ``time.perf_counter()`` would have read it."""
        return self.t_ns / 1e9


class _Phase:
    """One ``with`` block: the profiler's span around the account's. ``t0`` /
    ``t1`` are the account's own clock reads at entry and exit."""

    __slots__ = ("_account", "_name", "_stats", "_span", "_outer", "t0", "t1")

    def __init__(self, account: "LoopAccount", name: str, **stats: Any):
        self._account = account
        self._name = name
        self._stats = stats

    @property
    def start_s(self) -> float:
        return self.t0 / 1e9

    @property
    def end_s(self) -> float:
        return self.t1 / 1e9

    def __enter__(self) -> "_Phase":
        a = self._account
        self._outer = a._bucket
        if self._outer == "step":  # a child of `step`: the watchdog's evidence
            a.step_phase = self._name
        # a TraceAnnotation's span begins where it is built (its __enter__
        # only returns it) and ends at its __exit__: the two clocks are read
        # back to back at both ends. Every call here is traced too while a
        # profiler's Python tracer is on, so there are few.
        self._span = TraceAnnotation(_SPAN[self._name], **self._stats)
        self.t0 = now = perf_counter_ns()
        a.ns[self._outer] += now - a._t
        a._t = now
        a._bucket = self._name
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = now = perf_counter_ns()
        self._span.__exit__(*exc)
        a = self._account
        a.ns[self._name] += now - a._t
        a._t = now
        a._bucket = self._outer
        return False


class LoopAccount:
    def __init__(self):
        self.ns = dict.fromkeys(BUCKETS, 0)
        self.n = dict.fromkeys(COUNTS, 0)
        # ``with account.phase("<bucket>", **span_stats):``
        self.phase = functools.partial(_Phase, self)
        # the phase in progress directly under `step` (None between
        # iterations): what a stall watchdog names
        self.step_phase: Optional[str] = None
        self._bucket = "outside_step"
        self._n0 = dict(self.n)  # the counts when the iteration began
        self._t = perf_counter_ns()

    def begin_iteration(self) -> None:
        self.n["iterations"] += 1
        self._n0 = dict(self.n)

    def close_iteration(self, **stats: Any) -> None:
        """The zero-length ``serve.counts`` event: the iteration's delta of
        the counts, and the caller's ``stats`` (gauges, not sums)."""
        self.step_phase = None
        n, n0 = self.n, self._n0
        with TraceAnnotation(
            "serve.counts", **{k: n[k] - n0[k] for k in ITERATION_COUNTS}, **stats
        ):
            pass

    def snapshot(self) -> Snapshot:
        """The account now, the elapsed part of the phase in progress
        included (nothing is written: any thread may ask)."""
        now, ns = perf_counter_ns(), dict(self.ns)
        ns[self._bucket] += now - self._t
        return Snapshot(now, ns, dict(self.n))

    def totals(self) -> dict:
        """{"s": seconds a bucket, "n": the counts} since the engine was
        built."""
        snap = self.snapshot()
        return {"s": {k: v / 1e9 for k, v in snap.ns.items()}, "n": snap.n}


def between(first: Snapshot, last: Snapshot) -> dict:
    """A request's slice: {"s": seconds in each bucket that got any, "n":
    ``REQUEST_COUNTS``} over ``[first, last]``. The seconds sum to
    ``last.t - first.t``."""
    return {
        "s": {k: (v - first.ns[k]) / 1e9 for k, v in last.ns.items() if v > first.ns[k]},
        "n": {k: last.n[k] - first.n[k] for k in REQUEST_COUNTS},
    }

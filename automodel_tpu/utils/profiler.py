"""Profiling hooks.

Parity: the reference's NVTX/nsys tracing (autonvtx/__init__.py:33-60
recursive fwd/bwd range hooks; nsys windows by step, _cli/app.py:160-172,
benchmark.py:66-70). TPU-native: `jax.profiler` traces (viewable in
XProf/TensorBoard, incl. per-op HLO timing — strictly more detail than NVTX
ranges) opened/closed on a configured step window, plus `jax.named_scope`
for model-code annotations (scan-stacked layers appear as one scanned region
by construction, so no recursive patcher is needed).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import jax

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class ProfilerConfig:
    enabled: bool = False
    trace_dir: str = "/tmp/automodel_tpu_trace"
    start_step: int = 3
    end_step: int = 5
    # also write the Chrome-trace-event JSON (perfetto_trace.json.gz) the
    # telemetry/profiling trace analyzer parses — on by default so every
    # captured window is analyzable without xplane tooling
    perfetto: bool = True


def start_trace(trace_dir: str, perfetto: bool = True) -> None:
    """One place to start a jax trace with the perfetto JSON enabled
    (gracefully degrades on jax builds without the kwarg)."""
    try:
        jax.profiler.start_trace(trace_dir, create_perfetto_trace=perfetto)
    except TypeError:
        jax.profiler.start_trace(trace_dir)


class StepProfiler:
    """Opens a jax.profiler trace for steps in [start_step, end_step)."""

    def __init__(self, config: ProfilerConfig):
        self.config = config
        self._active = False

    @property
    def active(self) -> bool:
        return self._active

    def on_step(self, step: int) -> None:
        c = self.config
        if not c.enabled:
            return
        # window CONTAINMENT, not exact equality: a run resumed from a
        # checkpoint at step > start_step must still open the trace for the
        # remainder of its window instead of silently never profiling
        if not self._active and c.start_step <= step < c.end_step:
            start_trace(c.trace_dir, perfetto=c.perfetto)
            self._active = True
            logger.info("profiler: trace started at step %d → %s", step, c.trace_dir)
        elif self._active and step >= c.end_step:
            jax.profiler.stop_trace()
            self._active = False
            logger.info("profiler: trace stopped at step %d", step)

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False


# The scope vocabulary of the two jitted programs (train step; serve chunk and
# decode). Each name is a `jax.named_scope` at the place the work happens
# ("a/b" = scope `b` inside scope `a`), so it lands in every op's name path,
# which a device trace carries as the op's `tf_op` stat: a trace viewer groups
# by it, and benchmarks/harness/program_trace.py (which holds a copy of the
# names it knows; a test keeps every one of them a scope here, in this order)
# gives each op to the innermost name in its path.
# Metadata only: the compiled program is the same with or without them.
# docs/observability.md says what each covers.
SCOPES = (
    "embed", "layers", "norm", "attn", "kv_write",
    "moe/router", "moe/dispatch", "moe/experts", "moe/combine", "mlp",
    "final_norm", "lm_head_ce", "lm_head", "sample",
    "grad_accum", "grad_clip", "anomaly", "optimizer",
    # a recurrent layer's operator and its state's write (models/lfm2_moe).
    # The benchmark's frozen vocabulary lacks them and files their ops under
    # the scope around them (`layers`, `kv_write`); its newer readers match
    # the path segment (benchmarks/metrics/_scope_segments.py)
    "conv", "kv_write/state_write",
    # the two mixers of models/kimi_linear, both inside `attn` (where the
    # frozen vocabulary files them): the delta-rule layer with its segments
    # (`kda_chunk` is ops/delta_rule.py, forward and backward) and the latent
    # block; benchmarks/metrics/kda_ms.train.py and kda_chunk_roofline.py read them
    "attn/kda", "attn/kda/kda_conv", "attn/kda/kda_gate", "attn/kda/kda_chunk",
    "attn/kda/kda_norm", "attn/mla",
    # models/xing4: the hyper-connection residual path of a sublayer, inside
    # `norm` (where the frozen vocabulary files it), with its segments (the
    # coefficients with their Sinkhorn rounds, the pre-mix, the post-mix:
    # ops/hyper_connections.py), and the multi-token-prediction module, an
    # OUTER scope: its block's ops keep `attn`, `moe/...`, `norm/mhc`
    # innermost, its projection `mlp`; benchmarks/metrics/mhc_ms.train.py,
    # mhc_stream_roofline.py and mtp_ms.train.py read them
    "norm/mhc", "norm/mhc/mhc_coeff", "norm/mhc/mhc_pre", "norm/mhc/mhc_post", "mtp",
    # the latent block served from a latent pool (models/sarvam_mla,
    # ops/latent_attention.py): the decode step's absorption, absorbed attention
    # and value expansion, a prompt chunk's prefix expansion and attention,
    # and the row's write; benchmarks/metrics/mla_ms.serve.py,
    # latent_attn_roofline.serve.py and mla_attn_roofline.serve.py read them
    "attn/mla/mla_q_absorb", "attn/mla/mla_latent_attn", "attn/mla/mla_v_expand",
    "attn/mla/mla_prefix_expand", "attn/mla/mla_chunk_attn", "kv_write/latent_write",
)

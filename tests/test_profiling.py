"""Performance-observability pillar (telemetry/profiling/ + prometheus):

- cost walker: trip-count-aware measured FLOPs (scan × length), exact dot
  counts, collective classification, and the dense-vs-MoE cross-check
  pinning the analytic flops_utils laws against the traced program;
- trace analytics: parse of a committed miniature Chrome-trace fixture
  (self-time subtraction, comm/compute split, host gap, scope attribution)
  + the `automodel_tpu profile` CLI e2e on CPU;
- triggered capture: unit arming/firing semantics with a fake clock, and
  the e2e via the fault-injection straggle knob (one injected slow step →
  a real trace + memory profile + trace_capture evidence in the JSONL);
- /metrics: exposition-format lint and a scrape e2e against the serving
  HTTP server (block-pool occupancy gauge + ttft histogram).

All CPU-fast, tier-1."""

import gzip
import json
import re
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.telemetry.profiling import (
    ProfilingConfig,
    RooflineConfig,
    TriggeredCapture,
    TriggeredCaptureConfig,
    analyze_trace,
    load_trace_events,
    mfu_measured_pct,
    program_cost,
    render_markdown,
    roofline,
    trace_cost,
)

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "mini_trace.trace.json"


# -- cost walker ---------------------------------------------------------------


def test_cost_walker_multiplies_scan_trip_counts():
    """The reason the walker exists: XLA's cost_analysis counts a scan body
    ONCE; the walker multiplies by the static length. Both numbers ride the
    summary so the discrepancy is visible, not silent."""
    W = jnp.ones((16, 16))

    def body(c, x):
        return c + x @ W, ()

    def f(xs):
        c, _ = jax.lax.scan(body, jnp.zeros((4, 16)), xs)
        return c.sum()

    xs = jnp.ones((5, 4, 16))
    cost = program_cost(jax.jit(f), xs, program="scan5")
    one_matmul = 2 * 4 * 16 * 16
    assert cost.dot_flops == 5 * one_matmul
    assert cost.flops == cost.dot_flops
    assert cost.dot_ops == 1  # one eqn, five trips
    # XLA's body-once number is kept as the cross-check anchor
    assert cost.hlo_flops is not None and cost.hlo_flops < cost.flops

    # scan-free: the two sources must agree on dot flops to a few %
    g = jax.jit(lambda a, b: (a @ b).sum())
    a, b = jnp.ones((32, 64)), jnp.ones((64, 16))
    c2 = program_cost(g, a, b)
    assert c2.dot_flops == 2 * 32 * 64 * 16
    assert c2.hlo_flops == pytest.approx(c2.flops, rel=0.05)


def test_cost_walker_batched_dot_and_while():
    def f(a, b):
        return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))))

    a = jnp.ones((4, 8, 16))
    b = jnp.ones((4, 16, 32))
    cost = trace_cost(f, a, b)
    assert cost.dot_flops == 2 * 4 * 8 * 32 * 16

    W = jnp.ones((8, 8))

    def wh(x):
        def cond(c):
            return c[0] < 5

        def body(c):
            return (c[0] + 1, c[1] @ W)

        return jax.lax.while_loop(cond, body, (0, x))[1].sum()

    cw = trace_cost(wh, jnp.ones((8, 8)))
    assert cw.while_loops == 1
    assert cw.dot_flops == 2 * 8 * 8 * 8  # body counted once = per-iteration


def test_cost_walker_sees_explicit_collectives(devices8):
    """shard_map collectives (the a2a/ring paths) appear in the jaxpr and
    classify as collective bytes; GSPMD-inserted ones do not (documented)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(devices8[:4]), ("x",))

    def f(x):
        return jax.lax.psum(x, "x")

    sm = shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P())
    cost = trace_cost(sm, jnp.ones((8, 16)))
    assert cost.collective_ops >= 1
    assert cost.collective_bytes > 0


def _step_cost_for(hf, backend, batch=2, seq=32):
    from automodel_tpu import auto_model
    from automodel_tpu.optim.builders import build_optimizer, init_opt_state
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import (
        build_train_step,
        make_causal_lm_loss,
    )
    from automodel_tpu.utils.flops_utils import flops_per_token_for_config

    ctx = build_mesh(MeshConfig(dp_shard=-1))  # 8 virtual cpu devices in tier-1
    auto = auto_model.from_config(hf, ctx, backend, seed=0)
    loss = make_causal_lm_loss(auto.model, loss="masked_ce", constrain=auto.constrain)
    opt = build_optimizer(name="adamw", lr=1e-3)
    state = TrainState.create(auto.params, init_opt_state(opt, auto.params, auto.mesh_ctx))
    step = build_train_step(loss, opt)
    ids = jax.ShapeDtypeStruct((1, batch, seq), jnp.int32)
    cost = trace_cost(step, state, {"input_ids": ids, "labels": ids})
    return cost, auto.model.config, batch * seq


def test_cost_cross_check_dense_matches_analytic_law():
    """THE drift guard (ISSUE 7 satellite): the analytic flops_utils law vs
    the traced program's dot flops on a tiny dense llama. Expected gap:
    the law halves causal attention score flops (XLA computes the full
    rectangle) and does not count the optimizer — both small at this
    shape. A big drift means a law term went missing or the program
    computes something the law does not know about."""
    from automodel_tpu.utils.flops_utils import flops_per_token_for_config

    hf = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": 128,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "max_position_embeddings": 128,
    }
    backend = {"attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32"}
    cost, mcfg, tokens = _step_cost_for(hf, backend, batch=2, seq=32)
    analytic = flops_per_token_for_config(mcfg, 32)
    measured = cost.flops / tokens
    ratio = measured / analytic
    assert 0.75 < ratio < 1.35, (
        f"dense law drift: measured {measured:.3e} vs analytic {analytic:.3e} "
        f"flops/token (ratio {ratio:.3f})"
    )


def test_cost_cross_check_moe_matches_analytic_law():
    """MoE edition, `dense` experts backend (every expert computes every
    token — the einsum-visible path on CPU): the traced program must match
    the analytic MoE law evaluated at num_active := num_experts, and
    exceed the law at the REAL num_active — the gap between the two IS the
    dense backend's O(E/K) overcompute, exactly what mfu_measured_pct vs
    mfu_pct surfaces on a real run."""
    from automodel_tpu.utils.flops_utils import moe_transformer_flops_per_token

    hf = {
        "architectures": ["Qwen3MoeForCausalLM"],
        "model_type": "qwen3_moe",
        "vocab_size": 128,
        "hidden_size": 64,
        "intermediate_size": 128,
        "moe_intermediate_size": 32,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 16,
        "num_experts": 8,
        "num_experts_per_tok": 2,
        "decoder_sparse_step": 1,
        "norm_topk_prob": True,
        "mlp_only_layers": [],
        "max_position_embeddings": 128,
        "tie_word_embeddings": False,
    }
    backend = {
        "attn": "sdpa",
        "param_dtype": "float32",
        "compute_dtype": "float32",
        "experts": "dense",
    }
    cost, mcfg, tokens = _step_cost_for(hf, backend, batch=2, seq=32)
    measured = cost.flops / tokens

    def law(active):
        return moe_transformer_flops_per_token(
            hidden_size=64, num_layers=2, moe_intermediate_size=32,
            num_active_experts=active, shared_expert_intermediate=0,
            vocab_size=128, seq_len=32, num_heads=4, num_kv_heads=2,
            head_dim=16,
        )

    dense_equiv = law(8)  # what the dense backend actually computes
    ratio = measured / dense_equiv
    assert 0.7 < ratio < 1.4, (
        f"moe law drift: measured {measured:.3e} vs dense-equivalent "
        f"{dense_equiv:.3e} flops/token (ratio {ratio:.3f})"
    )
    # the active-experts law must sit clearly BELOW the dense compute
    assert law(2) < 0.8 * measured


def test_roofline_classification_and_measured_mfu():
    g = jax.jit(lambda a, b: (a @ b).sum())
    a, b = jnp.ones((64, 64)), jnp.ones((64, 64))
    cost = program_cost(g, a, b)
    # compute-rich basis -> memory bound; byte-rich basis -> compute bound
    low_bw = roofline(cost, RooflineConfig(peak_tflops=1.0, hbm_gbps=0.000001))
    assert low_bw["roofline_class"] == "memory_bound"
    hi_bw = roofline(cost, RooflineConfig(peak_tflops=0.000001, hbm_gbps=1000.0))
    assert hi_bw["roofline_class"] == "compute_bound"
    unknown = roofline(cost, RooflineConfig())
    if unknown["ridge_intensity"] is None:  # CPU: no device-table entry
        assert unknown["roofline_class"] == "unknown"
    m = mfu_measured_pct(1e12, 1.0, 1, RooflineConfig(peak_tflops=1.0))
    assert m == pytest.approx(100.0)
    assert mfu_measured_pct(1e12, 0.0, 1, RooflineConfig(peak_tflops=1.0)) is None


# -- trace analytics -----------------------------------------------------------


def test_trace_parse_fixture_decomposition_and_self_time():
    events = load_trace_events(FIXTURE)
    rep = analyze_trace(events, top_k=10)
    # hand-computable truth (see the fixture's metadata note)
    assert rep["op_events"] == 6
    assert rep["window_s"] == pytest.approx(800e-6)
    assert rep["device_busy_s"] == pytest.approx(650e-6)
    assert rep["host_gap_s"] == pytest.approx(150e-6)
    assert rep["comm_s"] == pytest.approx(50e-6)
    assert rep["comm_fraction"] == pytest.approx(50 / 650, abs=1e-3)
    top = rep["top_ops"]
    assert [o["name"] for o in top[:3]] == ["dot", "fusion", "all-reduce"]
    # self-time subtraction: fusion.9 (100) minus nested dot.5.clone (50)
    fusion = next(o for o in top if o["name"] == "fusion")
    assert fusion["self_s"] == pytest.approx(150e-6)
    assert fusion["count"] == 2
    dot = next(o for o in top if o["name"] == "dot")
    assert dot["self_s"] == pytest.approx(450e-6)
    ar = next(o for o in top if o["name"] == "all-reduce")
    assert ar["category"] == "comm"
    # scope attribution from the args-provided long name
    assert rep["scopes"][0]["scope"] == "jit_train_step/transformer"
    assert rep["scopes"][0]["self_s"] == pytest.approx(300e-6)
    # markdown renders without blowing up and carries the table
    md = render_markdown(rep, title="FIXTURE")
    assert "| `dot` |" in md and "## Decomposition" in md


def test_trace_load_accepts_gz_and_dir(tmp_path):
    raw = FIXTURE.read_bytes()
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.trace.json.gz").write_bytes(gzip.compress(raw))
    events = load_trace_events(tmp_path)  # directory search + gz decompress
    assert analyze_trace(events)["op_events"] == 6
    with pytest.raises(FileNotFoundError):
        load_trace_events(tmp_path / "empty_nothing_here_after_mkdir")


# -- triggered capture ---------------------------------------------------------


class _FakeTracer:
    def __init__(self, monkeypatch):
        self.started, self.stopped = [], 0
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, **kw: self.started.append(str(d)),
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: setattr(self, "stopped", self.stopped + 1)
        )
        monkeypatch.setattr(
            jax.profiler, "save_device_memory_profile", lambda p: Path(p).write_text("x")
        )


def test_triggered_capture_arms_fires_and_bounds(tmp_path, monkeypatch):
    tracer = _FakeTracer(monkeypatch)
    clock = [0.0]
    events = []
    cap = TriggeredCapture(
        TriggeredCaptureConfig(
            slow_step_factor=3.0, warmup_steps=2, capture_steps=2,
            max_captures=1, capture_dir=str(tmp_path / "cap"),
        ),
        event_hook=events.append,
        now=lambda: clock[0],
    )

    def step(i, dt):
        clock[0] += dt
        cap.on_step(i)

    step(1, 0.0)
    step(2, 5.0)   # compile interval — must be DROPPED, not learned
    for i in range(3, 7):
        step(i, 0.1)  # EMA ~0.1, armed after warmup
    assert not cap.active
    step(7, 1.0)   # 10x the EMA -> fire
    assert cap.active and len(tracer.started) == 1
    step(8, 0.1)
    step(9, 0.1)   # capture window (2 steps) closes
    assert not cap.active and tracer.stopped == 1
    rec = [e for e in events if e.get("capture_path")][-1]
    assert rec["reason"] == "slow_step" and rec["factor"] >= 3.0
    assert Path(rec["memory_profile"]).exists()
    # bounded: max_captures=1 — a second spike must NOT fire, but the
    # blocked trigger leaves evidence (once per run, not per slow step)
    step(10, 5.0)
    assert not cap.active and len(tracer.started) == 1
    skips = [e for e in events if "budget exhausted" in str(e.get("skipped", ""))]
    assert len(skips) == 1
    # external trigger path also respects the budget (and doesn't re-stamp)
    cap.trigger(11, "nonfinite")
    assert len(tracer.started) == 1
    skips = [e for e in events if "budget exhausted" in str(e.get("skipped", ""))]
    assert len(skips) == 1


def test_triggered_capture_nonfinite_trigger(tmp_path, monkeypatch):
    tracer = _FakeTracer(monkeypatch)
    events = []
    cap = TriggeredCapture(
        TriggeredCaptureConfig(capture_steps=1, capture_dir=str(tmp_path / "cap")),
        event_hook=events.append,
    )
    cap.trigger(4, "nonfinite")
    assert cap.active and len(tracer.started) == 1
    cap.on_step(5)
    assert not cap.active and tracer.stopped == 1
    assert events[-1]["reason"] == "nonfinite"


def test_manual_window_preempts_inflight_capture(tmp_path, monkeypatch):
    """A triggered capture spanning the manual window's [start, end) must
    not consume it: at start_step the capture is closed (trace stopped +
    evidence stamped) and the operator's window opens."""
    tracer = _FakeTracer(monkeypatch)
    from automodel_tpu.telemetry import Telemetry, TelemetryConfig
    from automodel_tpu.telemetry.profiling import ProfilingConfig

    tel = Telemetry(
        TelemetryConfig(
            flight_recorder_steps=0, compile_events=False,
            profile={"enabled": True, "start_step": 4, "end_step": 6,
                     "trace_dir": str(tmp_path / "manual")},
        )
    )
    events = []
    tel.attach_profiling(
        ProfilingConfig(triggered={"warmup_steps": 1, "capture_steps": 4}),
        capture_dir=str(tmp_path / "cap"),
        event_hook=events.append,
    )
    tel.on_step(1)
    tel.on_step(2)
    tel.triggered.trigger(2, "nonfinite")  # capture until step 6 — spans it
    assert tel.triggered.active and len(tracer.started) == 1
    tel.on_step(3)
    assert tel.triggered.active and not tel.profiler.active
    tel.on_step(4)  # manual start: capture preempted, window opens
    assert not tel.triggered.active and tel.profiler.active
    assert tracer.stopped == 1 and len(tracer.started) == 2
    assert any(e.get("capture_path") for e in events)
    tel.on_step(6)  # past end_step: manual window closes
    assert not tel.profiler.active and tracer.stopped == 2
    tel.close()


def _tiny_train_cfg(tmp_path, extra=None):
    from automodel_tpu.config.loader import ConfigNode

    cfg = {
        "seed": 7,
        "model": {
            "hf_config": {
                "architectures": ["LlamaForCausalLM"],
                "model_type": "llama",
                "vocab_size": 128,
                "hidden_size": 64,
                "intermediate_size": 128,
                "num_hidden_layers": 2,
                "num_attention_heads": 4,
                "num_key_value_heads": 2,
                "max_position_embeddings": 128,
            },
            "backend": {
                "attn": "sdpa", "param_dtype": "float32", "compute_dtype": "float32",
            },
        },
        "distributed": {"dp_shard": -1},
        "dataset": {
            "_target_": "automodel_tpu.data.sft.MockSFTDataset",
            "vocab_size": 128,
            "seq_length": 32,
            "num_samples": 64,
        },
        "dataloader": {"global_batch_size": 8},
        "step_scheduler": {"grad_acc_steps": 1, "num_epochs": 1, "max_steps": 8},
        "optimizer": {"name": "adamw", "lr": 1e-3},
        "output_dir": str(tmp_path / "run"),
    }
    for k, v in (extra or {}).items():
        cfg[k] = v
    return ConfigNode(cfg)


@pytest.fixture(scope="module")
def straggled_run(tmp_path_factory):
    """ONE tiny recipe run shared by the e2e assertions below (a full run
    costs ~10s of tier-1 budget): straggle injection for the triggered
    capture, peak/bandwidth overrides so the MFU fields materialize on
    CPU. → (records, run_dir)."""
    from automodel_tpu.recipes.train_ft import main

    tmp_path = tmp_path_factory.mktemp("straggled")
    cfg = _tiny_train_cfg(
        tmp_path,
        extra={
            "fault_injection": {
                "straggle_host": 0, "straggle_ms": 1500.0, "straggle_at_step": 5,
            },
            "profiling": {
                "peak_tflops": 0.5,
                "hbm_gbps": 10.0,
                "triggered": {
                    "slow_step_factor": 3.0, "warmup_steps": 2,
                    "capture_steps": 1, "max_captures": 1,
                },
            },
        },
    )
    main(cfg)
    run_dir = tmp_path / "run"
    lines = [
        json.loads(l)
        for l in (run_dir / "train_metrics.jsonl").read_text().splitlines()
    ]
    return lines, run_dir


def test_triggered_capture_e2e_via_straggle_injection(straggled_run):
    """The injected one-step straggle (fault_injection.straggle_at_step)
    spikes the host inter-step interval; the armed profiler captures a REAL
    trace + device memory profile and stamps the evidence into the metrics
    JSONL."""
    lines, _ = straggled_run
    caps = [l for l in lines if l.get("event") == "trace_capture" and l.get("capture_path")]
    assert caps, f"no trace_capture evidence in {[l.get('event') for l in lines]}"
    cap = caps[-1]
    assert cap["reason"] == "slow_step" and cap["factor"] >= 3.0
    cap_dir = Path(cap["capture_path"])
    assert cap_dir.exists() and list(cap_dir.rglob("*.trace.json.gz"))
    assert Path(cap["memory_profile"]).exists()
    # the run's cost-attribution + measured MFU rode the same JSONL
    assert any(l.get("event") == "cost_attribution" for l in lines)


# -- cost attribution in the recipes ------------------------------------------


def test_train_metrics_carry_both_mfu_provenances(straggled_run):
    """Acceptance: mfu_measured_pct (cost_analysis-sourced program cost)
    beside the analytic mfu_pct on the log records, and the two agree on a
    dense model within the law's known blind spots — with the whole JSONL
    (including the capture/cost event records) strict-lint clean."""
    from automodel_tpu.telemetry.report import lint_metrics_jsonl

    _, run_dir = straggled_run
    records, problems = lint_metrics_jsonl(str(run_dir / "train_metrics.jsonl"))
    assert not problems, problems
    logged = [r for r in records if "mfu_measured_pct" in r]
    assert logged, "no log record carries mfu_measured_pct"
    r = logged[-1]
    assert "mfu_pct" in r
    assert 0.5 < r["mfu_measured_pct"] / r["mfu_pct"] < 1.5
    cost = next(r for r in records if r.get("event") == "cost_attribution")
    assert cost["program"] == "train_step"
    assert cost["flops"] > 0 and cost["dot_flops"] > 0
    assert cost["roofline_class"] in ("compute_bound", "memory_bound", "comm_heavy")
    # stray-CWD regression: nothing landed outside output_dir
    assert not Path("train_metrics.jsonl").exists()


def test_profiling_config_rejects_unknown_keys():
    from automodel_tpu.telemetry.prometheus import MetricsServerConfig

    with pytest.raises(TypeError, match="unknown profiling"):
        ProfilingConfig.from_dict({"tracee_steps": 3})
    with pytest.raises(TypeError, match="unknown metrics_server"):
        MetricsServerConfig.from_dict({"prot": 1})
    assert ProfilingConfig.from_dict(None).enabled
    assert MetricsServerConfig.from_dict({"port": 0}).port == 0


# -- generation/serving program costs -----------------------------------------


def test_generation_engine_program_costs():
    from automodel_tpu.auto_model import AutoModel
    from automodel_tpu.generation.engine import GenerationConfig, GenerationEngine
    from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
    from automodel_tpu.models.llama import LlamaForCausalLM

    bk = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")
    model = LlamaForCausalLM(
        TransformerConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=8,
        ),
        bk,
    )
    auto = AutoModel(
        model=model, params=model.init(jax.random.key(0)), adapter=None, mesh_ctx=None
    )
    eng = GenerationEngine(
        auto, GenerationConfig(max_new_tokens=4, greedy=True, pad_to_multiple=1)
    )
    eng.collect_program_costs = True
    eng.generate_ids([[1, 2, 3]])
    assert set(eng.program_costs) == {"prefill", "decode"}
    assert eng.program_costs["prefill"]["flops"] > 0
    # decode is a while program: body counted once = per-token cost
    assert eng.program_costs["decode"]["while_loops"] >= 1
    assert eng.program_costs["decode"]["flops"] > 0


# -- /metrics ------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_=\",.+-]*\})? "
    r"(NaN|[-+]?Inf|[-+]?[0-9.eE+-]+)$"
)


def _lint_exposition(body: str) -> None:
    """The grammar a Prometheus scraper applies to text format 0.0.4."""
    seen_type = {}
    for line in body.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), line
            seen_type[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"
    assert seen_type, "no TYPE headers rendered"


def test_prometheus_registry_exposition_lint():
    from automodel_tpu.telemetry.prometheus import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("automodel_test_things", "Things counted")
    g = reg.gauge("automodel_test_level", "A level")
    h = reg.histogram("automodel_test_latency_seconds", "A latency", buckets=(0.1, 1.0))
    c.inc(3)
    g.set(0.25)
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    body = reg.render()
    _lint_exposition(body)
    assert "automodel_test_things_total 3" in body
    # histogram: cumulative buckets, +Inf == count, sum carried
    assert 'automodel_test_latency_seconds_bucket{le="0.1"} 1' in body
    assert 'automodel_test_latency_seconds_bucket{le="1"} 2' in body
    assert 'automodel_test_latency_seconds_bucket{le="+Inf"} 3' in body
    assert "automodel_test_latency_seconds_count 3" in body
    # counters refuse to run backwards
    c.set_total(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)


def test_train_exporter_updates_and_events():
    from automodel_tpu.telemetry.prometheus import TrainMetricsExporter

    ex = TrainMetricsExporter()
    ex.update(
        {"step": 7, "loss": 2.5, "tps": 1000.0, "step_time_s": 0.1,
         "mfu_pct": 12.5, "mfu_measured_pct": 13.0, "skipped_steps_total": 2}
    )
    ex.event("hang")
    ex.event("nonfinite_step")
    ex.event("not_a_known_event")  # ignored, never raises
    body = ex.registry.render()
    _lint_exposition(body)
    assert "automodel_train_step 7" in body
    assert "automodel_train_mfu_measured_pct 13" in body
    assert "automodel_train_skipped_steps_total 2" in body
    assert "automodel_train_hang_events_total 1" in body
    assert "automodel_train_nonfinite_steps_total 1" in body


def _tiny_serving_engine():
    from automodel_tpu.auto_model import AutoModel
    from automodel_tpu.generation.engine import GenerationConfig
    from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
    from automodel_tpu.models.llama import LlamaForCausalLM
    from automodel_tpu.serving.engine import ServeConfig, ServingEngine

    bk = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")
    model = LlamaForCausalLM(
        TransformerConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=8,
        ),
        bk,
    )
    auto = AutoModel(
        model=model, params=model.init(jax.random.key(0)), adapter=None, mesh_ctx=None
    )
    return ServingEngine(
        auto,
        ServeConfig(slots=2, block_size=4, num_blocks=32, prefill_chunk=8, max_seq_len=64),
        GenerationConfig(max_new_tokens=4, greedy=True),
    )


def test_metrics_scrape_e2e_against_serving_server():
    """Acceptance: GET /metrics on the serving server returns valid
    Prometheus text exposition including block-pool occupancy and a ttft
    histogram — verified by an actual scrape over HTTP."""
    from automodel_tpu.serving.server import serve_http

    engine = _tiny_serving_engine()
    engine.collect_program_costs = True  # piggyback: one compile set
    server, loop = serve_http(engine, tokenizer=None, port=0)
    port = server.server_address[1]
    import threading

    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt": "1 2 3 4", "max_new_tokens": 3}).encode(),
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        assert out["n_generated"] >= 1
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as r:
            ctype = r.headers.get("Content-Type", "")
            body = r.read().decode()
        assert "version=0.0.4" in ctype
        _lint_exposition(body)
        assert "automodel_serve_block_occupancy " in body
        assert "automodel_serve_requests_completed_total 1" in body
        assert 'automodel_serve_ttft_seconds_bucket{le="+Inf"} 1' in body
        assert "automodel_serve_ttft_seconds_count 1" in body
        # allocator counters surfaced from BlockPool.counters
        assert "automodel_serve_block_allocated_total" in body
        assert "automodel_serve_generated_tokens_total" in body
        # the piggybacked cost collection saw both paged programs
        assert set(engine.program_costs) == {"chunk_prefill", "paged_decode"}
        assert engine.program_costs["chunk_prefill"]["flops"] > 0
        assert engine.program_costs["paged_decode"]["flops"] > 0
    finally:
        server.shutdown()
        loop.close()


# -- `automodel_tpu profile` CLI e2e ------------------------------------------


def test_profile_cli_e2e_train_mode(tmp_path, monkeypatch):
    """Acceptance: `automodel_tpu profile -c examples/...` on CPU emits a
    structured JSON + markdown report with top-K op self-times and a
    comm/compute/host decomposition."""
    from automodel_tpu.cli.app import main as cli_main

    monkeypatch.chdir(tmp_path)
    example = (
        Path(__file__).resolve().parent.parent
        / "examples" / "benchmark" / "tiny_cpu_profile.yaml"
    )
    rc = cli_main(
        ["profile", "-c", str(example), f"--output_dir={tmp_path / 'prof'}"]
    )
    assert rc == 0
    report = json.loads((tmp_path / "prof" / "profile" / "report.json").read_text())
    assert report["mode"] == "train"
    assert report["op_events"] > 0 and report["top_ops"], "no op events parsed"
    for key in ("window_s", "device_busy_s", "host_gap_s", "compute_s", "comm_s"):
        assert isinstance(report[key], (int, float)), key
    top = report["top_ops"][0]
    assert top["self_s"] > 0 and top["count"] >= 1
    # cost attribution rode the run: measured program numbers + mfu
    assert report["cost"]["train_step"]["flops"] > 0
    assert report["run_metrics"]["mfu_measured_pct"] > 0
    md = (tmp_path / "prof" / "profile" / "PROFILE.md").read_text()
    assert "## Decomposition" in md and "## Top ops by self time" in md

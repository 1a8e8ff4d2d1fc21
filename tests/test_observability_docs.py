"""docs/observability.md's key glossary must cover every metrics-JSONL key
the recipes emit — the test_perf_docs.py verbatim-guard pattern applied to
the glossary.

The linter's key lists (telemetry/report.py `_NUMERIC_KEYS` /
`_DURATION_KEYS`) are the canonical registry of emitted keys: every PR
that teaches a recipe a new JSONL key must add it there for `report
--strict` to accept it, so gating the glossary on the same lists means a
key can never ship linted-but-undocumented. The goodput segment taxonomy
and the attempt-envelope keys are pinned the same way.
"""

import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# envelope / marker keys the recipes emit that are not numeric-linted
_EXTRA_KEYS = (
    "attempt_id",
    "restart_count",
    "completion_reason",
    "retriable",
    "trace_id",
    "span_id",
    "parent_id",
    "stage",
    "nonfinite",
    "val_loss",
    "steps_spanned",
    # elastic fleet (serving/fleet/autoscale.py): replica boot provenance
    # + the scale_event envelope's string fields
    "boot_source",
    "direction",
    "trigger",
    # serving/loop_account.py: a serve_request record's slice of the
    # scheduler loop's account ({"s": seconds by phase, "n": counts})
    "decode_account",
)


def _doc():
    return open(os.path.join(REPO, "docs", "observability.md")).read()


def test_every_linted_jsonl_key_has_a_glossary_row():
    from automodel_tpu.telemetry.report import _DURATION_KEYS, _NUMERIC_KEYS

    doc = _doc()
    missing = sorted(
        k
        for k in set(_NUMERIC_KEYS) | set(_DURATION_KEYS) | set(_EXTRA_KEYS)
        if f"`{k}`" not in doc
    )
    assert not missing, (
        "docs/observability.md glossary is missing rows for these "
        f"metrics-JSONL keys (add a `key` row): {missing}"
    )


def test_goodput_segment_taxonomy_is_documented():
    from automodel_tpu.telemetry.goodput import SEGMENT_KINDS

    doc = _doc()
    missing = sorted(k for k in SEGMENT_KINDS if f"`{k}`" not in doc)
    assert not missing, (
        "docs/observability.md Goodput section is missing segment rows: "
        f"{missing}"
    )
    # the rollup-only residual is part of the taxonomy too
    assert "`unattributed`" in doc


def test_goodput_metrics_exporter_names_are_documented():
    doc = _doc()
    for name in (
        "automodel_train_goodput_fraction",
        "automodel_train_goodput_seconds",
        "automodel_train_ckpt_{save,restore,drain}_seconds",
    ):
        assert name in doc, f"/metrics glossary missing {name}"


# -- every emittable /metrics name must have a glossary row -------------------
#
# The doc names metrics both literally (`automodel_serve_queue_depth`) and
# as brace patterns (`automodel_serve_block_{allocated,freed}_total`,
# possibly wrapped across lines mid-pattern) and with label selectors
# (`automodel_alerts_firing{slo}`). The matcher normalizes the doc once and
# reads every token BOTH ways — brace-expanded and selector-stripped — so a
# documented name is found regardless of notation. False positives from the
# wrong reading are harmless: the result is only probed for membership.


def _expand_braces(tok: str) -> list[str]:
    out = [tok]
    for _ in range(4):  # bounded: patterns nest at most once in practice
        nxt = []
        for t in out:
            if "{" not in t or "}" not in t:
                nxt.append(t)
                continue
            pre, rest = t.split("{", 1)
            body, _, post = rest.partition("}")
            for alt in body.split(","):
                nxt.append(pre + alt + post)
        if nxt == out:
            break
        out = nxt
    return out


def _documented_names(doc: str) -> set:
    import re

    # metric names live in code spans (the same convention the JSONL-key
    # guard requires); adjacent spans are merged first so a brace pattern
    # wrapped mid-span (`automodel_train_{step,` + `loss,...}`) reassembles
    merged = re.sub(r"`\s*`", "", doc)
    names = set()
    for span in re.findall(r"`([^`]+)`", merged):
        span = re.sub(r"\s+", "", span)
        for tok in re.findall(r"automodel_[a-zA-Z0-9_{},=.]+", span):
            candidates = list(_expand_braces(tok))
            candidates.append(re.sub(r"\{[^{}]*\}", "", tok))  # label sel.
            for cand in candidates:
                for piece in re.split(r"[.,]", cand):
                    if piece and "{" not in piece and "=" not in piece:
                        names.add(piece)
    return names


def _fleet_plane_registries():
    """→ (serving, train, router-with-slo) registries + the federation's
    self-metric render names — every family the repo can expose, built
    jax-free (no engine, no device runtime)."""
    from automodel_tpu.serving.fleet.router import RouterMetrics
    from automodel_tpu.telemetry.federation import Federation, parse_exposition
    from automodel_tpu.telemetry.prometheus import (
        ServingMetrics,
        TrainMetricsExporter,
    )
    from automodel_tpu.telemetry.slo import SLOConfig, SLOEngine

    serving = ServingMetrics().registry
    train = TrainMetricsExporter().registry
    router = RouterMetrics().registry
    # the SLO engine registers its alert families on the router registry
    SLOEngine(
        SLOConfig(objectives=[{
            "name": "doc_guard", "kind": "gauge",
            "metric": "automodel_serve_queue_depth", "max_value": 1.0,
        }]),
        Federation(),
        registry=router,
    )
    fed = parse_exposition(Federation().render_federated())
    fed_names = [
        m.name + ("_total" if m.kind == "counter" else "")
        for m in fed.values()
    ]
    return serving, train, router, fed_names


def test_every_metric_render_name_is_documented():
    doc = _doc()
    documented = _documented_names(doc)
    serving, train, router, fed_names = _fleet_plane_registries()
    required = set(fed_names)
    for reg in (serving, train, router):
        required.update(m.render_name for m in reg._metrics.values())
    missing = sorted(k for k in required if k not in documented)
    assert not missing, (
        "docs/observability.md /metrics glossary is missing these "
        f"emittable metric names: {missing}"
    )


def test_fleet_aggregate_derivation_is_documented():
    """Every replica family reappears on the router as a derived
    automodel_fleet_* aggregate (gauges also grow a _max companion). The
    doc must either name a derived family literally or document the base
    family + the derivation rule — the rule text is pinned here so it
    cannot silently vanish while the test keeps passing."""
    from automodel_tpu.telemetry.federation import fleet_name

    doc = _doc()
    assert "insert `fleet_` after `automodel_`" in doc, (
        "docs/observability.md no longer states the fleet-name derivation "
        "rule"
    )
    assert "_max` companion" in doc, (
        "docs/observability.md no longer states the gauge _max companion "
        "rule"
    )
    documented = _documented_names(doc)
    serving, _, _, _ = _fleet_plane_registries()
    missing = []
    for m in serving._metrics.values():
        fleet_family = fleet_name(m.name)
        derived = [fleet_family + ("_total" if m.kind == "counter" else "")]
        if m.kind == "gauge":
            derived.append(fleet_family + "_max")
        for name in derived:
            base = m.render_name
            if name not in documented and base not in documented:
                missing.append(name)
    assert not missing, (
        "fleet aggregates underivable from the doc (document the base "
        f"family or the derived name): {sorted(set(missing))}"
    )

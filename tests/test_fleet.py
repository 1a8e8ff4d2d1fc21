"""Fleet tier tests (serving/fleet/): router-side chain hashing vs the
replica prefix cache, prefix-affinity vs least-loaded placement,
power-of-two fallback, the KV transfer wire format (bit identity for bf16
and int8 pools), disaggregated prefill→decode greedy parity vs a single
mixed replica, the routed HTTP path end-to-end, the k8s fleet manifests,
and the routed bench sub-leg. All CPU-fast, tier-1."""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax

from automodel_tpu.auto_model import AutoModel
from automodel_tpu.generation.engine import GenerationConfig
from automodel_tpu.models.common.config import BackendConfig, TransformerConfig
from automodel_tpu.serving.block_pool import BlockPool, prompt_chain
from automodel_tpu.serving.engine import (
    ServeConfig,
    ServingEngine,
    StallConfig,
)
from automodel_tpu.serving.fleet.router import (
    FleetConfig,
    ReplicaSpec,
    Router,
    _Replica,
)

FP32 = BackendConfig(attn="sdpa", param_dtype="float32", compute_dtype="float32")


def _tiny_auto(seed=0):
    from automodel_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(
        TransformerConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=8,
        ),
        FP32,
    )
    return AutoModel(
        model=model, params=model.init(jax.random.key(seed)),
        adapter=None, mesh_ctx=None,
    )


def _engine(**over):
    over.setdefault("watchdog", StallConfig(enabled=False))
    gen = over.pop("gen", None) or GenerationConfig(max_new_tokens=6, greedy=True)
    return ServingEngine(
        _tiny_auto(),
        ServeConfig(
            slots=2, block_size=4, num_blocks=32, prefill_chunk=4,
            max_seq_len=48, **over,
        ),
        gen,
    )


# ---------------------------------------------------------------------------
# chain-hash parity
# ---------------------------------------------------------------------------


def test_chain_hash_parity_router_vs_block_pool():
    """The router's prompt_chain must produce exactly the keys
    register_prefix files blocks under — and match_prefix must hit them."""
    pool = BlockPool(16, 4)
    prompt = list(range(10, 23))  # 13 tokens -> 3 full blocks, 3 matchable
    blocks = pool.allocate(4)
    pool.register_prefix(prompt, blocks)
    chains = prompt_chain(prompt, 4)
    assert len(chains) == 3  # capped at len-1: (13-1)//4
    cached = set(pool.cached_chain_hashes())
    assert set(chains) <= cached
    # the deepest router-side hash is the exact key of the deepest
    # matchable block
    hits, matched = pool.match_prefix(prompt)
    assert matched == 12 and len(hits) == 3
    pool.free(hits)
    # a different prompt shares no chain
    assert not set(prompt_chain(list(range(50, 60)), 4)) & cached


def test_chain_hash_deterministic_across_processes():
    """The whole point of replacing builtin hash(): a fresh interpreter
    (different PYTHONHASHSEED) computes the identical chain."""
    here = prompt_chain([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
    code = (
        "from automodel_tpu.serving.block_pool import prompt_chain;"
        "print(prompt_chain([1,2,3,4,5,6,7,8,9], 4))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, check=True,
        cwd=str(Path(__file__).resolve().parent.parent),
        env={"PYTHONHASHSEED": "12345", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
    )
    assert json.loads(out.stdout.replace("'", '"')) == here


def test_hot_prefix_advertise_keeps_newest():
    pool = BlockPool(64, 2)
    for i in range(10):
        blocks = pool.allocate(2)
        pool.register_prefix([100 + i, 200 + i, 300 + i, 400 + i], blocks)
        pool.free(blocks)
    all_hashes = pool.cached_chain_hashes()
    assert pool.cached_chain_hashes(limit=4) == all_hashes[-4:]
    # a re-hit prefix is pinned (referenced — not evictable) and must
    # survive the limit even though it was registered FIRST; after the
    # free it re-parks at the far-from-eviction end and stays advertised
    hits, n = pool.match_prefix([100, 200, 300, 400, 999])
    assert n == 4
    kept = pool.cached_chain_hashes(limit=4)
    assert all_hashes[0] in kept and all_hashes[1] in kept
    pool.free(hits)
    kept = pool.cached_chain_hashes(limit=4)
    assert all_hashes[0] in kept and all_hashes[1] in kept


# ---------------------------------------------------------------------------
# placement policy (unit level: fabricated replica states)
# ---------------------------------------------------------------------------


def _fake_router(replica_states, **over):
    over.setdefault("block_size", 4)
    over.setdefault("affinity", True)
    cfg = FleetConfig.from_dict({
        "replicas": [r.spec for r in replica_states], **over,
    })
    router = Router(cfg)
    for r in replica_states:
        router._replicas[r.name] = r
    return router


def _rep(name, hot=(), load=0, role="mixed", block_size=4):
    return _Replica(
        spec=ReplicaSpec(url=f"http://fake/{name}", name=name),
        alive=True, ready=True, role=role,
        stats={"queue_depth": load, "busy_slots": 0, "block_size": block_size},
        hot=frozenset(hot),
    )


def test_prefix_affinity_beats_least_loaded():
    """A replica holding the prompt's prefix wins placement even when a
    cold replica is less loaded — the hit is worth more than the queue."""
    prompt = list(range(1, 14))
    chains = prompt_chain(prompt, 4)
    hot = _rep("hot", hot=chains, load=3)
    cold = _rep("cold", hot=(), load=0)
    router = _fake_router([hot, cold])
    rep, match = router.place_decode(chains)
    assert rep.name == "hot" and match == len(chains)
    # a LONGER match beats a shorter one regardless of load
    partial = _rep("partial", hot=chains[:1], load=0)
    router = _fake_router([hot, partial])
    rep, match = router.place_decode(chains)
    assert rep.name == "hot" and match == len(chains)
    # affinity off -> pure load
    router = _fake_router([hot, cold], affinity=False)
    rep, match = router.place_decode(chains)
    assert rep.name == "cold" and match == 0


def test_affinity_skipped_on_block_size_mismatch():
    """A replica caching under a different block size can never match the
    router's chain hashes — its advertised set must be ignored, not
    trusted by accident."""
    prompt = list(range(1, 14))
    chains = prompt_chain(prompt, 4)
    mism = _rep("mism", hot=chains, load=0, block_size=8)
    mism.block_size_ok = False
    cold = _rep("cold", hot=(), load=1)
    router = _fake_router([mism, cold])
    rep, match = router.place_decode(chains)
    assert match == 0  # never an affinity placement


def test_power_of_two_fallback_distribution():
    """No prefix anywhere: placement spreads over replicas (both get
    requests) and prefers the lighter of each sampled pair."""
    reps = [_rep(f"r{i}", load=0) for i in range(4)]
    router = _fake_router(reps)
    placed = {r.name: 0 for r in reps}
    for _ in range(200):
        rep, match = router.place_decode([])
        assert match == 0
        placed[rep.name] += 1
    assert all(v > 0 for v in placed.values()), placed
    # skewed loads: the overloaded replica must receive almost nothing
    reps = [_rep("busy", load=100)] + [_rep(f"ok{i}", load=0) for i in range(3)]
    router = _fake_router(reps)
    placed = {r.name: 0 for r in reps}
    for _ in range(200):
        rep, _ = router.place_decode([])
        placed[rep.name] += 1
    assert placed["busy"] < 200 * 0.2, placed


def test_place_excludes_tried_and_not_ready():
    a, b = _rep("a"), _rep("b")
    b.ready = False
    router = _fake_router([a, b])
    rep, _ = router.place_decode([], exclude={"a"})
    assert rep is None  # b not ready, a excluded
    assert router.ready()  # a alone keeps the fleet ready
    a.ready = False
    assert not router.ready()


def test_prefill_pool_and_disaggregation_flag():
    pre = _rep("pre", role="prefill", load=1)
    dec = _rep("dec", role="decode")
    router = _fake_router([pre, dec])
    assert router.place_prefill().name == "pre"
    assert router._disaggregate_active()
    # decode placement never picks the prefill replica
    rep, _ = router.place_decode([])
    assert rep.name == "dec"
    router = _fake_router([pre, dec], disaggregate=False)
    assert not router._disaggregate_active()


# ---------------------------------------------------------------------------
# KV transfer wire format
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_kv_transfer_roundtrip_bit_identity(dtype):
    """Extract → socket frame → store → inject-side arrays: byte-for-byte
    identical for raw and (values, scales) pools, and a geometry mismatch
    is refused loudly."""
    from automodel_tpu.serving.fleet.kv_transfer import (
        KVTransferError,
        KVTransferServer,
        send_kv,
    )

    eng = _engine(kv_cache_dtype=dtype)
    prompt = list(range(1, 12))
    rid = eng.submit(prompt, prefill_only=True)
    recs = {r["request_id"]: r for r in eng.run()}
    assert recs[rid]["completion_reason"] == "prefilled"
    payload = eng.pop_prefill_payload(rid)
    eng.pool.check_invariants()
    assert eng.pool.available() == eng.pool.usable_blocks

    srv = KVTransferServer(eng.kv_geometry(), port=0).start()
    try:
        meta = {
            "handoff_id": "h1", "request_id": rid,
            "prompt_len": payload["prompt_len"],
            "first_token": payload["first_token"],
            "geometry": eng.kv_geometry(),
        }
        resp = send_kv(("127.0.0.1", srv.port), meta, payload["kv"])
        assert resp["ok"]
        entry = srv.store.pop("h1")
        assert entry["meta"]["first_token"] == payload["first_token"]
        for side in ("k", "v"):
            a, b = payload["kv"][side], entry["kv"][side]
            if dtype == "int8":
                assert isinstance(a, tuple) and isinstance(b, tuple)
                assert a[0].tobytes() == b[0].tobytes()
                assert a[1].tobytes() == b[1].tobytes()
            else:
                assert a.tobytes() == b.tobytes()
                assert a.dtype == b.dtype
        # geometry mismatch: loud refusal, nothing stored
        bad = dict(meta, handoff_id="h2")
        bad["geometry"] = {**meta["geometry"], "head_dim": 999}
        with pytest.raises(KVTransferError, match="geometry mismatch"):
            send_kv(("127.0.0.1", srv.port), bad, payload["kv"])
        with pytest.raises(KeyError):
            srv.store.pop("h2")
    finally:
        srv.close()


def test_handoff_store_bounds_and_ttl():
    from automodel_tpu.serving.fleet.kv_transfer import HandoffStore

    store = HandoffStore(max_pending=2, ttl_s=1000.0)
    for i in range(4):
        store.put(f"h{i}", {"i": i})
    assert len(store) == 2
    with pytest.raises(KeyError):
        store.pop("h0")  # evicted (store full)
    assert store.pop("h3")["i"] == 3


# ---------------------------------------------------------------------------
# disaggregated prefill -> decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_disaggregated_greedy_parity_vs_mixed(dtype):
    """prefill-only on engine P, payload injected into engine D, decode —
    greedy tokens identical to one mixed engine serving the same request."""
    prompt = list(range(1, 14))
    mixed = _engine(kv_cache_dtype=dtype)
    mrid = mixed.submit(prompt, max_new_tokens=6)
    mrec = {r["request_id"]: r for r in mixed.run()}[mrid]

    pre = _engine(kv_cache_dtype=dtype, role="prefill")
    prid = pre.submit(prompt, prefill_only=True)
    prec = {r["request_id"]: r for r in pre.run()}[prid]
    assert prec["completion_reason"] == "prefilled"
    assert prec["tokens"] == mrec["tokens"][:1]  # greedy first token agrees
    payload = pre.pop_prefill_payload(prid)

    dec = _engine(kv_cache_dtype=dtype, role="decode")
    drid = dec.submit_prefilled(
        prompt, payload["first_token"], payload["kv"], max_new_tokens=6
    )
    drec = {r["request_id"]: r for r in dec.run()}[drid]
    assert drec["tokens"] == mrec["tokens"]
    assert drec["completion_reason"] == mrec["completion_reason"]
    dec.pool.check_invariants()
    assert dec.kv_injected_total == 1
    # the injected prefix is matchable: a repeat prompt hits it locally
    r2 = dec.submit(prompt, max_new_tokens=6)
    rec2 = {r["request_id"]: r for r in dec.run()}[r2]
    assert rec2["prefix_hit_tokens"] > 0
    assert rec2["tokens"] == mrec["tokens"]


def test_submit_prefilled_validates_payload_and_spec_refusal():
    from automodel_tpu.generation.engine import GenerationUnsupported

    eng = _engine()
    prompt = [1, 2, 3, 4, 5]
    rid = eng.submit(prompt, prefill_only=True)
    eng.run()
    payload = eng.pop_prefill_payload(rid)
    dec = _engine()
    with pytest.raises(ValueError, match="shape"):
        dec.submit_prefilled(prompt + [6, 7, 8, 9], 1, payload["kv"])
    # int8 payload into a raw pool: dtype refusal
    int8_eng = _engine(kv_cache_dtype="int8")
    rid8 = int8_eng.submit(prompt, prefill_only=True)
    int8_eng.run()
    p8 = int8_eng.pop_prefill_payload(rid8)
    with pytest.raises(ValueError, match="int8"):
        dec.submit_prefilled(prompt, 1, p8["kv"])
    # a speculative engine refuses handoffs loudly
    spec_draft = {
        "hf_config": {
            "architectures": ["LlamaForCausalLM"], "model_type": "llama",
            "vocab_size": 64, "hidden_size": 16, "intermediate_size": 32,
            "num_hidden_layers": 1, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 8,
            "max_position_embeddings": 128,
        },
        "backend": {
            "attn": "sdpa", "param_dtype": "float32",
            "compute_dtype": "float32",
        },
    }
    from automodel_tpu.serving.engine import SpeculativeConfig

    spec = _engine(
        speculative=SpeculativeConfig(enabled=True, k=2, draft=spec_draft)
    )
    with pytest.raises(GenerationUnsupported, match="draft"):
        spec.submit_prefilled(prompt, 1, payload["kv"])
    # unclaimed payloads are bounded
    assert eng.config.kv_transfer.max_pending >= 1


# ---------------------------------------------------------------------------
# routed HTTP path end-to-end (in-process replicas)
# ---------------------------------------------------------------------------


def _http_replica(engine):
    from automodel_tpu.serving.server import serve_http

    engine.submit([1], max_new_tokens=2)
    engine.run()  # warm: compiles done, first_decode_done -> /readyz true
    server, loop = serve_http(engine, None, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, loop


def test_router_http_affinity_retry_and_metrics():
    """Two live replicas behind real HTTP: a repeat prompt routes back to
    the replica that cached it (prefix hit), a dead replica's requests
    retry onto the survivor, /readyz stays true with one replica down, and
    the /metrics counters move."""
    engines = [_engine(), _engine()]
    fronts = [_http_replica(e) for e in engines]
    records = []
    router = Router(
        FleetConfig.from_dict({
            "replicas": [
                {"url": f"http://127.0.0.1:{s.server_address[1]}",
                 "name": f"r{i}"}
                for i, (s, _) in enumerate(fronts)
            ],
            # long interval on purpose: after the kill below, placement
            # must act on STALE ready/hot state and hit the dead replica,
            # exercising the retry path instead of sidestepping it
            "block_size": 4, "probe_interval_s": 30.0, "retry_budget": 2,
            "request_timeout_s": 120.0,
        }),
        on_record=records.append,
    ).start()
    try:
        assert router.ready()
        prompt = list(range(1, 13))
        code, body = router.handle_generate(
            {"prompt_ids": prompt, "max_new_tokens": 6, "id": "a"}
        )
        assert code == 200 and body["completion_reason"] in ("stop", "length")
        first_replica = body["route"]["replica"]
        router.probe_once()  # learn the now-hot prefix
        code, body2 = router.handle_generate(
            {"prompt_ids": prompt, "max_new_tokens": 6, "id": "b"}
        )
        assert code == 200
        assert body2["route"]["replica"] == first_replica
        assert body2["route"]["prefix_match_blocks"] > 0
        assert body2["tokens"] == body["tokens"]
        # kill the hot replica (close the listener like a dead process)
        vidx = int(first_replica[1])
        fronts[vidx][0].shutdown()
        fronts[vidx][0].server_close()
        fronts[vidx][1].close()
        code, body3 = router.handle_generate(
            {"prompt_ids": prompt, "max_new_tokens": 6, "id": "c"}
        )
        assert code == 200, body3
        assert body3["route"]["replica"] != first_replica
        assert body3["route"]["retries"] >= 1
        assert body3["tokens"] == body["tokens"]
        router.probe_once()
        assert router.ready()  # one replica down, fleet still ready
        rendered = router.metrics.registry.render()
        assert "automodel_route_prefix_hits_total 1" in rendered
        assert "automodel_route_retries_total" in rendered
        assert f'automodel_route_replica_up{{replica="{first_replica}"}} 0' in rendered
        from tests.test_profiling import _lint_exposition

        _lint_exposition(rendered)
        by_id = {r["request_id"]: r for r in records}
        assert sorted(by_id) == ["a", "b", "c"]
        assert all(
            r["completion_reason"] in ("stop", "length")
            for r in by_id.values()
        )
    finally:
        router.close()
        for server, loop in fronts:
            try:
                server.shutdown()
                server.server_close()
            except OSError:
                pass
            loop.close()


def test_router_http_disaggregated_flow():
    """prefill-role + decode-role replicas behind HTTP: the router
    orchestrates /prefill → socket transfer → /generate with the handoff
    id, and the routed tokens match a single mixed replica. A repeat
    prompt takes the strong-affinity bypass (no second handoff)."""
    from automodel_tpu.serving.fleet.kv_transfer import KVTransferServer
    from automodel_tpu.serving.server import serve_http

    pre = _engine(role="prefill")
    dec = _engine(role="decode")
    pre_front = _http_replica(pre)
    dec.submit([1], max_new_tokens=2)
    dec.run()
    kvs = KVTransferServer(dec.kv_geometry(), port=0).start()
    dec.kv_transfer_port = kvs.port
    dec_server, dec_loop = serve_http(dec, None, port=0, kv_store=kvs.store)
    threading.Thread(target=dec_server.serve_forever, daemon=True).start()
    router = Router(
        FleetConfig.from_dict({
            "replicas": [
                {"url": f"http://127.0.0.1:{pre_front[0].server_address[1]}",
                 "name": "pre0"},
                {"url": f"http://127.0.0.1:{dec_server.server_address[1]}",
                 "name": "dec0"},
            ],
            "block_size": 4, "probe_interval_s": 0.2,
            "request_timeout_s": 120.0,
        }),
    ).start()
    try:
        assert router.stats()["disaggregated"]
        prompt = list(range(1, 14))
        code, body = router.handle_generate(
            {"prompt_ids": prompt, "max_new_tokens": 6, "id": "x"}
        )
        assert code == 200, body
        assert body["route"]["prefill_replica"] == "pre0"
        assert body["route"]["replica"] == "dec0"
        mixed = _engine()
        mrid = mixed.submit(prompt, max_new_tokens=6)
        mrec = {r["request_id"]: r for r in mixed.run()}[mrid]
        assert body["tokens"] == mrec["tokens"]
        assert router.handoffs_total == 1
        # strong affinity hit: the decode replica holds the prefix now —
        # no second transfer
        router.probe_once()
        code, body2 = router.handle_generate(
            {"prompt_ids": prompt, "max_new_tokens": 6, "id": "y"}
        )
        assert code == 200
        assert body2["route"]["prefill_replica"] is None
        assert body2["route"]["prefix_match_blocks"] > 0
        assert body2["tokens"] == mrec["tokens"]
        assert router.handoffs_total == 1
    finally:
        router.close()
        for server, loop in (pre_front, (dec_server, dec_loop)):
            server.shutdown()
            server.server_close()
            loop.close()
        kvs.close()


# ---------------------------------------------------------------------------
# k8s fleet manifests
# ---------------------------------------------------------------------------


def test_k8s_fleet_manifest_roles_probes_and_router():
    from automodel_tpu.launcher.k8s import K8sFleetConfig, render_fleet_manifest

    cfg = K8sFleetConfig(
        name="f", image="img:1", prefill=2, decode=3, mixed=0,
        router_port=8000, replica_port=8100, kv_port=8200,
    )
    doc = render_fleet_manifest(cfg, "/cfg/serve.yaml")
    # role-labelled StatefulSets with the PR 9 probes
    assert "name: f-prefill" in doc and "name: f-decode" in doc
    assert "role: prefill" in doc and "role: decode" in doc
    assert "--serving.role=prefill" in doc and "--serving.role=decode" in doc
    assert doc.count("path: /readyz") == 3  # 2 replica sets + router
    assert doc.count("path: /healthz") == 3
    # headless discovery service + router Deployment wired to it
    assert "clusterIP: None" in doc
    assert "--fleet.dns=f-replicas" in doc
    assert "--fleet.port=8000" in doc
    assert "--serving.kv_transfer.port=8200" in doc
    # the router pod requests no TPU
    router_doc = doc.split("kind: Deployment")[1]
    assert "google.com/tpu" not in router_doc
    # invalid topologies refuse loudly
    with pytest.raises(ValueError, match="at least one replica"):
        render_fleet_manifest(
            K8sFleetConfig(mixed=0, prefill=0, decode=0), "/c.yaml"
        )
    with pytest.raises(ValueError, match="decode"):
        render_fleet_manifest(
            K8sFleetConfig(mixed=0, prefill=2, decode=0), "/c.yaml"
        )


# ---------------------------------------------------------------------------
# router records through the report pipeline
# ---------------------------------------------------------------------------


def test_report_accepts_and_summarizes_route_records(tmp_path):
    from automodel_tpu.telemetry.report import (
        lint_metrics_jsonl,
        summarize_metrics,
    )

    path = tmp_path / "route_metrics.jsonl"
    recs = [
        {"event": "route_request", "request_id": "a", "replica": "r0",
         "retries": 0, "prefix_match_blocks": 2, "disaggregated": False,
         "completion_reason": "length", "n_generated": 6, "status": 200,
         "route_s": 0.01, "ts": 1.0},
        {"event": "route_request", "request_id": "b", "replica": "r1",
         "retries": 2, "prefix_match_blocks": 0, "disaggregated": True,
         "completion_reason": "stop", "n_generated": 3, "status": 200,
         "route_s": 0.02, "ts": 2.0},
        {"event": "route_request", "request_id": "c", "replica": None,
         "retries": 3, "prefix_match_blocks": 0,
         "completion_reason": "unroutable", "status": 503,
         "route_s": 0.03, "ts": 3.0},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    records, problems = lint_metrics_jsonl(str(path))
    assert problems == []
    summary = summarize_metrics(records)
    assert summary["route_requests"] == 3
    assert summary["route_retries"] == 5
    assert summary["route_prefix_hit_rate"] == round(1 / 3, 4)
    assert summary["route_replicas"] == {"r0": 1, "r1": 1}
    assert summary["route_unroutable"] == 1
    assert summary["route_kv_handoffs"] == 1


def test_router_retries_handoff_miss_409():
    """A decode replica that lost its handoff payload answers 409
    retriable (docs/serving.md, Retry semantics) — the router must
    resubmit to a different replica, not surface the 409 to the client."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    def _stub(generate_status, generate_body, queue_depth):
        class H(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def _json(self, code, obj):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/readyz":
                    return self._json(200, {"ready": True})
                return self._json(200, {
                    "role": "mixed", "block_size": 4,
                    "queue_depth": queue_depth, "busy_slots": 0,
                    "hot_prefixes": [],
                })

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                return self._json(generate_status, generate_body)

        srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv

    # lower load -> the 409 replica wins placement first
    lossy = _stub(409, {"error": "no pending KV handoff", "retriable": True},
                  queue_depth=0)
    good = _stub(200, {"completion_reason": "length", "tokens": [7],
                       "n_generated": 1, "retriable": False},
                 queue_depth=5)
    router = Router(FleetConfig.from_dict({
        "replicas": [
            {"url": f"http://127.0.0.1:{lossy.server_address[1]}",
             "name": "lossy"},
            {"url": f"http://127.0.0.1:{good.server_address[1]}",
             "name": "good"},
        ],
        "block_size": 4, "retry_budget": 2,
    }))
    try:
        router.probe_once()
        code, body = router.handle_generate(
            {"prompt_ids": [1, 2, 3], "max_new_tokens": 1, "id": "x"}
        )
        assert code == 200, body
        assert body["route"]["replica"] == "good"
        assert body["route"]["retries"] == 1
        assert router.retries_total == 1
    finally:
        router.close()
        for srv in (lossy, good):
            srv.shutdown()
            srv.server_close()


def test_kv_transfer_refuses_oversize_and_lying_frames():
    """Wire lengths are untrusted: a u64 length that disagrees with the
    manifest's shape x dtype, or a frame bigger than the receiver's pool
    bound, is refused before allocation — never an OOM."""
    import socket

    from automodel_tpu.serving.fleet.kv_transfer import (
        MAGIC,
        KVTransferServer,
        KVTransferError,
        _read_response,
        send_kv,
    )

    geom = {
        "layers": 1, "block_size": 4, "num_kv_heads": 1, "head_dim": 2,
        "kv_cache_dtype": "bf16",
    }
    srv = KVTransferServer(geom, port=0, max_frame_bytes=64).start()
    try:
        # honest manifest but the frame exceeds the pool bound (64 bytes):
        # 2 sides x [1, 8, 4, 1, 2] f32 = 512 bytes
        # the server refuses mid-frame, so the sender sees either the
        # refusal response or a broken pipe — both wrap as KVTransferError
        big = np.zeros((1, 8, 4, 1, 2), np.float32)
        with pytest.raises(KVTransferError):
            send_kv(
                ("127.0.0.1", srv.port),
                {"handoff_id": "h", "prompt_len": 31, "geometry": geom},
                {"k": big, "v": big},
            )
        # length claim disagreeing with the manifest: refused, no 2^40 alloc
        hdr = json.dumps({
            "handoff_id": "h2", "prompt_len": 3, "geometry": geom,
            "arrays": [
                {"key": "k", "shape": [1, 1, 4, 1, 2], "dtype": "float32"}
            ],
        }).encode()
        with socket.create_connection(("127.0.0.1", srv.port), timeout=10) as s:
            s.sendall(MAGIC + len(hdr).to_bytes(4, "little") + hdr)
            s.sendall((1 << 40).to_bytes(8, "little"))
            resp = _read_response(s)
        assert not resp["ok"] and "implies" in resp["error"]
        assert len(srv.store) == 0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# hierarchical KV cache: peer prefix fetch (op: kv_fetch)
# ---------------------------------------------------------------------------


def test_kv_fetch_wire_roundtrip_and_refusals():
    """The /kv_fetch op at the wire level against a stub handler: block
    rows come back byte-identical, a clean miss is (0, None) not an
    error, and a geometry mismatch or unwired handler refuses loudly."""
    from automodel_tpu.serving.fleet.kv_transfer import (
        KVTransferError,
        KVTransferServer,
        fetch_kv,
    )

    geom = {
        "layers": 1, "block_size": 4, "num_kv_heads": 1, "head_dim": 2,
        "kv_cache_dtype": "bf16",
    }
    rows = {
        "k": np.arange(16, dtype=np.float32).reshape(1, 2, 4, 1, 2),
        "v": -np.arange(16, dtype=np.float32).reshape(1, 2, 4, 1, 2),
    }
    seen = []

    def handler(hashes):
        seen.append(list(hashes))
        return 2, rows

    srv = KVTransferServer(geom, port=0, fetch_handler=handler).start()
    try:
        n, kv = fetch_kv(("127.0.0.1", srv.port), [11, 22], geom)
        assert n == 2 and seen == [[11, 22]]
        for side in ("k", "v"):
            assert kv[side].tobytes() == rows[side].tobytes()
            assert kv[side].dtype == rows[side].dtype
        with pytest.raises(KVTransferError, match="geometry mismatch"):
            fetch_kv(("127.0.0.1", srv.port), [11],
                     {**geom, "head_dim": 999})
        srv.fetch_handler = lambda hashes: (0, None)
        assert fetch_kv(("127.0.0.1", srv.port), [11], geom) == (0, None)
        srv.fetch_handler = None
        with pytest.raises(KVTransferError, match="no prefix fetches"):
            fetch_kv(("127.0.0.1", srv.port), [11], geom)
    finally:
        srv.close()


def test_router_peer_hint_deeper_holder_wins():
    """_peer_hint forwards {host, port} only when another ready replica
    advertises a STRICTLY deeper consecutive match AND runs a KV
    listener; a KV-suspect replica never serves hints."""
    prompt = list(range(1, 14))
    chains = prompt_chain(prompt, 4)
    chosen = _rep("chosen", hot=chains[:1], load=0)
    deep = _rep("deep", hot=chains, load=5)
    deep.kv_port = 8200
    router = _fake_router([chosen, deep])
    assert router._peer_hint(chains, chosen, 1, set()) == {
        "host": "fake", "port": 8200,
    }
    # nobody deeper than the chosen replica's own match -> no hint
    assert router._peer_hint(chains, chosen, len(chains), set()) is None
    # a suspect KV listener (failed transfer target) never serves hints
    assert router._peer_hint(chains, chosen, 1, {"deep"}) is None
    # equal depth is not worth a fetch, nor is an empty chain
    equal = _rep("equal", hot=chains[:1], load=0)
    equal.kv_port = 8201
    assert _fake_router([chosen, equal])._peer_hint(
        chains, chosen, 1, set()
    ) is None
    assert router._peer_hint([], chosen, 0, set()) is None
    # no KV listener advertised -> no hint
    deep.kv_port = None
    assert router._peer_hint(chains, chosen, 1, set()) is None


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_peer_prefix_fetch_bit_identity(dtype):
    """A prefix first seen on engine A is served to cold engine B over a
    real /kv_fetch socket: B's greedy tokens are bit-identical to A's
    full recompute, the fetch is accounted token-weighted, and a repeat
    on B hits locally (the injected prefix registered)."""
    from automodel_tpu.serving.engine import KVSpillConfig
    from automodel_tpu.serving.fleet.kv_transfer import KVTransferServer

    spill = KVSpillConfig(enabled=True, max_host_mb=4.0)
    a = _engine(kv_cache_dtype=dtype, kv_spill=spill)
    prompt = list(range(1, 14))  # 13 tokens -> 3-block chain, 12 matchable
    rid = a.submit(prompt, max_new_tokens=6)
    ref = {r["request_id"]: r for r in a.run()}[rid]
    lock = threading.Lock()

    def handler(hashes):
        with lock:
            return a.fetch_prefix_blocks(hashes)

    srv = KVTransferServer(
        a.kv_geometry(), port=0, fetch_handler=handler,
        max_frame_bytes=a.kv_frame_bytes_bound(),
    ).start()
    b = _engine(kv_cache_dtype=dtype, kv_spill=spill)
    try:
        rb = b.submit(
            prompt, max_new_tokens=6,
            kv_peer={"host": "127.0.0.1", "port": srv.port},
        )
        rec = {r["request_id"]: r for r in b.run()}[rb]
        assert rec["tokens"] == ref["tokens"]
        assert rec["completion_reason"] == ref["completion_reason"]
        c = b.pool.counters
        assert c["peer_fetches"] == 1
        assert c["peer_fetch_blocks"] == 3
        assert c["peer_fetch_failures"] == 0
        assert rec["prefix_hit_tokens"] == 12
        b.pool.check_invariants()
        # the fetched prefix registered locally: a repeat needs no peer
        r2 = b.submit(prompt, max_new_tokens=6)
        rec2 = {r["request_id"]: r for r in b.run()}[r2]
        assert rec2["tokens"] == ref["tokens"]
        assert rec2["prefix_hit_tokens"] == 12
        assert b.pool.counters["peer_fetches"] == 1  # unchanged
        b.pool.check_invariants()
    finally:
        srv.close()


def test_peer_fetch_mid_stream_death_recomputes():
    """Chaos rung of the fallback ladder: the peer dies mid-reply (and
    later refuses connections outright) — every request still completes
    via local recompute with identical greedy output and the failures
    accounted, never a hang or a wrong answer."""
    import socket

    from automodel_tpu.serving.engine import KVSpillConfig

    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]

    def _die_mid_frame():
        conn, _ = lsock.accept()
        conn.recv(64)               # start reading the request...
        conn.sendall(b"AKV1\x00\x02")  # ...begin a reply frame, then vanish
        conn.close()

    t = threading.Thread(target=_die_mid_frame, daemon=True)
    t.start()
    eng = _engine(
        kv_spill=KVSpillConfig(enabled=True, max_host_mb=4.0,
                               fetch_timeout_s=10.0)
    )
    prompt = list(range(1, 14))
    rid = eng.submit(
        prompt, max_new_tokens=6,
        kv_peer={"host": "127.0.0.1", "port": port},
    )
    rec = {r["request_id"]: r for r in eng.run()}[rid]
    t.join(timeout=10)
    lsock.close()
    assert rec["completion_reason"] in ("stop", "length")
    assert rec["prefix_hit_tokens"] == 0  # nothing served from any tier
    assert eng.pool.counters["peer_fetch_failures"] == 1
    assert eng.pool.counters["peer_fetch_blocks"] == 0
    eng.pool.check_invariants()
    # same engine, recompute reference: clear every tier, re-serve
    eng.pool.clear_prefix_cache()
    r2 = eng.submit(prompt, max_new_tokens=6)
    ref = {r["request_id"]: r for r in eng.run()}[r2]
    assert rec["tokens"] == ref["tokens"]
    # dead peer (connection refused): same ladder, second failure
    eng.pool.clear_prefix_cache()
    r3 = eng.submit(
        prompt, max_new_tokens=6,
        kv_peer={"host": "127.0.0.1", "port": port},
    )
    rec3 = {r["request_id"]: r for r in eng.run()}[r3]
    assert rec3["tokens"] == ref["tokens"]
    assert eng.pool.counters["peer_fetch_failures"] == 2
    eng.pool.check_invariants()


def _http_json_raw(port, path, payload=None, timeout=120.0):
    import urllib.request

    url = f"http://127.0.0.1:{port}{path}"
    if payload is None:
        req = urllib.request.Request(url)
    else:
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _spawn_spill_replica(tmp_path, idx):
    from tests.test_serving_chaos import _clean_env

    worker = str(Path(__file__).resolve().parent / "resilience_worker.py")
    cfg = {
        "seed": 0,
        "model": {
            "hf_config": {
                "architectures": ["LlamaForCausalLM"],
                "model_type": "llama",
                "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
                "num_hidden_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 2, "head_dim": 8,
                "max_position_embeddings": 128,
            },
            "backend": {"attn": "sdpa", "param_dtype": "float32",
                        "compute_dtype": "float32"},
        },
        "distributed": {"dp_shard": 1},
        "generation": {"max_new_tokens": 6, "greedy": True},
        "serving": {
            "slots": 1, "block_size": 4, "num_blocks": 32,
            "prefill_chunk": 4, "max_seq_len": 64,
            "http": {"port": 0},
            "watchdog": {"enabled": False},
            # kv_spill auto-starts the KV listener (serving.kv_transfer
            # enabled: null) and wires the engine-backed fetch handler
            "kv_spill": {"enabled": True, "max_host_mb": 4.0},
        },
    }
    cfg_path = tmp_path / f"spill_replica{idx}.yaml"
    cfg_path.write_text(json.dumps(cfg))
    return subprocess.Popen(
        [sys.executable, worker, "serve", "-c", str(cfg_path)],
        stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, env=_clean_env(),
    )


@pytest.mark.slow  # two replica subprocess boots: well over the tier-1
# per-test budget on a contended 1-CPU box (conftest enforces it)
def test_peer_prefix_fetch_across_replica_processes(tmp_path):
    """Acceptance (ISSUE 16): a prefix first seen on replica process A is
    served to replica process B via /kv_fetch — two REAL serve
    subprocesses, real sockets on both hops, greedy output bit-identical,
    the fetch visible in B's /stats."""
    from tests.test_serving_chaos import _replica_port

    procs = [_spawn_spill_replica(tmp_path, i) for i in range(2)]
    try:
        ports = [_replica_port(p) for p in procs]
        prompt = list(range(1, 14))  # 3-block chain, 12 matchable tokens
        body_a = _http_json_raw(
            ports[0], "/generate",
            {"prompt_ids": prompt, "max_new_tokens": 6, "id": "a"},
        )
        assert body_a["completion_reason"] in ("stop", "length")
        stats_a = _http_json_raw(ports[0], "/stats")
        kv_port = stats_a["kv_transfer_port"]
        assert kv_port, "spill-enabled replica must run a KV listener"
        assert stats_a["spill_bytes"] is not None
        body_b = _http_json_raw(
            ports[1], "/generate",
            {"prompt_ids": prompt, "max_new_tokens": 6, "id": "b",
             "kv_peer": {"host": "127.0.0.1", "port": kv_port}},
        )
        assert body_b["tokens"] == body_a["tokens"]
        assert body_b["completion_reason"] == body_a["completion_reason"]
        assert body_b["prefix_hit_tokens"] == 12
        alloc_b = _http_json_raw(ports[1], "/stats")["allocator"]
        assert alloc_b["peer_fetches"] == 1
        assert alloc_b["peer_fetch_blocks"] == 3
        assert alloc_b["peer_fetch_failures"] == 0
        assert alloc_b["prefix_hit_tokens"] == 12
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)

"""What the sarvam-105b cell adds to the benchmark: the cell as ``load_cell``
sees it, the configuration against the source's keys, a traffic mix in which
the seed cannot change the work, the reference's ``shapes`` and the two new
laws, the per-token law against the published parameter counts at the uncut
configuration, the new readers on synthetic traces, and the rehearsal."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.harness import loader, program_trace
from benchmarks.harness import traffic as T

ROOT = Path(__file__).resolve().parents[2]
CELL = "serve-docqueue-sarvam-105b"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = {"latent_attn_roofline.serve", "mla_attn_roofline.serve", "mla_ms.serve",
               "held_expert_rows_pct.serve"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cell():
    return loader.load_cell(loader.load_benchmark(), CELL)


def _source():
    if not CATALOG.exists():
        pytest.skip("no catalog on this machine")
    return next(json.loads(line) for line in CATALOG.read_text().splitlines()
                if json.loads(line)["name"] == "sarvam-105b")


def test_the_cell_as_the_loader_sees_it():
    cell = _cell()
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "open_loop"
    assert cell["reference"].__name__.endswith("sarvam_mla")
    names = {m["name"] for m in cell["per_layer"]}
    assert NEW_METRICS | {"paged_grid_live_pct", "expert_grid_live_pct", "expert_mlp_ms.serve",
                          "decode_step_device_ms", "prefill_chunk_device_ms",
                          "scoped_device_time_pct.serve", "device_idle_pct.serve"} <= names
    # per-head K/V's law, a conv state's metrics: another layout's
    assert not {"paged_attn_roofline", "conv_mixer_ms.serve", "state_resets_per_step"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {"tpot_p50_s", "setup_s"}
    for m in cell["per_layer"]:  # every reader the cell reports loads
        assert callable(loader.load_module("metrics", m["name"]).read)
    for m in loader.load_benchmark()["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_s"
    hf = loader.program_hf_config(cell["config"])
    assert hf["num_experts"] == 128 and hf["held_experts"] == [0, 16] and hf["vocab_size"] == 32768
    assert cell["config"]["num_experts"] == 16  # the file's key counts the experts held here
    serving = cell["config"]["program"]["serving"]
    assert (serving["slots"], serving["block_size"], serving["num_blocks"], serving["prefill_chunk"],
            serving["max_seq_len"], serving["prefix_cache"]) == (32, 16, 20480, 512, 8192, False)
    # the traffic needs 32 x 15 / 271 = 1.77 chunks an iteration: the smallest whole bound above it
    assert 32 * 15 / 271 < serving["max_prefill_chunks_per_step"] == 2
    # the pool holds the traffic at its fullest with room: 32 x (7680 + 256) of 327,680 tokens
    assert 32 * 7936 <= serving["num_blocks"] * serving["block_size"]


def test_every_published_key_is_the_sources_but_the_reduced_ones():
    src = _source()
    mine = _cell()["config"]
    assert mine["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in src["config"].items():
        if key not in mine["reduced"]:
            assert mine[key] == value, key
    assert mine["source"] == src["source_url"]
    # the cut: the leading dense layer + five expert layers, an eighth of the experts and of the vocabulary
    assert (mine["num_hidden_layers"], mine["first_k_dense_replace"]) == (6, 1)
    assert mine["num_experts"] * 8 == src["config"]["num_experts"]
    assert mine["vocab_size"] * 8 == src["config"]["vocab_size"]
    assert {k: mine["published"][k] for k in mine["reduced"]} == {k: src["config"][k] for k in mine["reduced"]}
    for key in ("deployment", "departures", "assumed"):
        assert mine[key], key
    assert "NOT applied" in mine["assumed"]["use_qk_norm"]


def test_the_seed_cannot_change_the_work():
    traffic = _cell()["traffic"]
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 7680, "max": 7680}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256, "max": 256}
    # ISSUE 47's ramp, and the far end of its range for the drain (60-90): the file's `why`
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["check_requests"]) == (16.0, 90.0, 6)
    assert traffic["arrivals"]["process"] == "exponential_gaps"
    # 7680 + 256 pads to 8192 in the reference's check, not 16384
    assert 1 << (7680 + 255 + 256 - 1).bit_length() == 8192
    runs = [T.open_loop_requests(traffic, seed, 51.0, 32768) for seed in (1, 2147700101, 2**31 + 7)]
    sizes = [[(len(ids), n) for _, ids, n in r["window"]] for r in runs]
    assert sizes[0] == sizes[1] == sizes[2] and set(sizes[0]) == {(7680, 256)}
    assert len(sizes[0]) == round(traffic["arrivals"]["rate_per_s"] * 51)
    assert [len(r["ramp"]) for r in runs] == [round(traffic["arrivals"]["rate_per_s"] * 16)] * 3
    # what the seed does deal: the ids and the order of arrival
    assert runs[0]["window"][0][1] != runs[1]["window"][0][1]
    assert all(max(ids) < 32768 and min(ids) >= 3 for _, ids, _ in runs[2]["window"][:4])


def test_shapes_and_the_two_laws():
    cell = _cell()
    c = cell["reference"].shapes(loader.hf_config(cell["config"]))
    assert (c["latent_layers"], c["latent_width"], c["latent_rank"], c["q_heads"], c["qk_dim"],
            c["v_dim"]) == (6, 576, 512, 64, 192, 128)
    assert (c["expert_layers"], c["top_k"], c["hidden"], c["expert_width"], c["vocab"]) == (
        5, 8, 4096, 2048, 32768)
    assert (c["held_experts"], c["published_experts"]) == (16, 128)
    # ISSUE 47's arithmetic: 3,179 M parameters held, 6.36 GB of bf16
    assert c["parameter_count"]() == pytest.approx(3179e6, rel=0.002)
    lat = loader.load_module("kernels", "latent_paged_attn")
    assert lat.row_bytes(1, 576) == 1152 and lat.flops(1, 64, 576, 512) == 64 * (576 + 512) * 2
    # 121 FLOP a byte: bytes-bound under a v5e's 240, FLOP-bound on a chip with a tenth of the MXU
    assert lat.flops(1, 64, 576, 512) / lat.row_bytes(1, 576) == pytest.approx(120.9, rel=0.01)
    assert lat.bound(250_000 * 6, 64, 576, 512, PEAKS) == (pytest.approx(250_000 * 6 * 1152 / 819e9), "bytes")
    assert lat.bound(1000, 64, 576, 512, dict(PEAKS, bf16_flops_per_s=19.7e12))[1] == "flops"
    chunk = loader.load_module("kernels", "mla_chunk_attn")
    assert chunk.pairs(512, 0) == 512 * 513 / 2 and chunk.pairs(512, 3840) == 512 * 3840 + 512 * 513 / 2
    assert chunk.forward_flops(512, 3840, 64, 192, 128) == chunk.pairs(512, 3840) * 64 * 320 * 2
    # a whole 7,680-token prompt in 15 chunks is the causal half of mla_attn's law
    mla = loader.load_module("kernels", "mla_attn")
    whole = sum(chunk.forward_flops(512, s, 64, 192, 128) for s in range(0, 7680, 512))
    assert whole == pytest.approx(mla.forward_flops(1, 7680, 64, 192, 128))


def test_the_law_against_the_published_counts_at_the_uncut_configuration():
    src = _source()["config"]
    c = _cell()["reference"].shapes(src)
    # the name says 105B. These keys count 106.03 B; a token uses 11.3 B
    assert c["parameter_count"]() == pytest.approx(106.03e9, rel=0.001)
    assert c["parameter_count"](active=True) == pytest.approx(11.3e9, rel=0.01)
    # forward FLOPs a token at a short sequence: twice the active parameters but the embedding row
    assert c["forward_flops_per_token"](1) == pytest.approx(2 * c["parameter_count"](active=True), rel=0.01)
    more = c["forward_flops_per_token"](8192) - c["forward_flops_per_token"](4096)
    assert more == pytest.approx(32 * 2 * 64 * (192 + 128) * 2048)


def _device_ops():
    """Two ``jit_step`` runs and one ``jit_chunk`` on one device, picoseconds."""
    s, c = "jit(step)/layers/attn/mla/", "jit(chunk)/layers/attn/mla/"
    return {0: {
        "modules": [(0, 10_000, "jit_step(1)"), (10_000, 30_000, "jit_chunk(2)"), (40_000, 10_000, "jit_step(1)")],
        "ops": [
            (0, 300, "fusion.1", s + "mla_q_absorb/dot_general", ""),
            (1000, 4000, "latent_paged_attention", s + "mla_latent_attn/pallas_call", ""),
            (6000, 200, "fusion.2", s + "mla_v_expand/dot_general", ""),
            (7000, 100, "fusion.3", s + "kv_write/latent_write/scatter", ""),
            (8000, 900, "fusion.4", "jit(step)/layers/moe/experts/pallas_call", ""),
            (12_000, 5000, "fusion.5", c + "while/body/mla_prefix_expand/dot_general", ""),
            (18_000, 15_000, "fusion.6", c + "while/body/mla_chunk_attn/dot_general", ""),
            (41_000, 6000, "latent_paged_attention", s + "mla_latent_attn/pallas_call", ""),
        ],
    }}


def _counts(rows):
    return [{"name": "serve.step", "stats": {}, "parent": None, "start_s": i, "end_s": i + 0.5}
            for i in range(len(rows))], rows


def test_the_new_readers_on_synthetic_ops(monkeypatch, tmp_path):
    monkeypatch.setattr(program_trace, "read_device_ops", lambda path: _device_ops())
    monkeypatch.setattr(program_trace, "xplane_of", lambda run: tmp_path)
    rows = [{"latent_context_rows": 6 * 200_000, "held_expert_rows": 170, "expert_grid_units": 85},
            {"latent_context_rows": 0, "held_expert_rows": 0, "expert_grid_units": 0},
            {"latent_context_rows": 6 * 220_000, "held_expert_rows": 150, "expert_grid_units": 85}]
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: rows)
    monkeypatch.setattr(program_trace, "spans", lambda run, prefix: [
        {"name": "serve.prefill_dispatch", "stats": {"slot": 3, "pos": 1024, "tokens": 512}},
        {"name": "serve.prefill_dispatch", "stats": {"slot": 5, "pos": 0, "tokens": 512}}])
    cell = _cell()
    run = {"cell": cell, "artefacts": {"kind": "serve", "slots": 32}, "device": {"count": 1},
           "peaks": PEAKS}
    read = lambda name: loader.load_module("metrics", name).read(run)
    # the decode kernel's scope alone, a mean a step; rows a mean over the launches that counted any
    seconds = (4000 + 6000) / 2 * 1e-12
    assert read("latent_attn_roofline.serve") == pytest.approx(
        100 * (6 * 210_000 * 1152 / 819e9) / seconds)
    # two dispatched chunks' pairs a chunk, six layers, over the chunk program's two scopes
    chunk = loader.load_module("kernels", "mla_chunk_attn")
    need = 6 * (chunk.forward_flops(512, 1024, 64, 192, 128) + chunk.forward_flops(512, 0, 64, 192, 128)) / 2
    assert read("mla_attn_roofline.serve") == pytest.approx(100 * (need / 197e12) / (20_000e-12))
    # every op with the `mla` segment in a decode step, median of the two runs
    assert read("mla_ms.serve") == pytest.approx(((300 + 4000 + 200 + 100) + 6000) / 2 * 1e-9)
    # 320 held picks of two steps x 32 slots x 8 picks x 5 layers
    assert read("held_expert_rows_pct.serve") == pytest.approx(100 * 320 / (2 * 32 * 8 * 5))
    # a program without the scopes or the counters (the parent): nothing, not zeros
    monkeypatch.setattr(program_trace, "read_device_ops", lambda path: {0: {
        "modules": [(0, 10, "jit_step(1)")], "ops": [(0, 5, "fusion", "jit(step)/attn/dot", "")]}})
    monkeypatch.setattr(program_trace, "iteration_counts", lambda run: [{"decoded": 3, "expert_grid_units": 4}])
    monkeypatch.setattr(program_trace, "spans", lambda run, prefix: None)
    for name in NEW_METRICS:
        assert read(name) is None, name


def test_the_reference_refuses_what_it_does_not_model():
    cell = _cell()
    R, hf, block = cell["reference"], loader.hf_config(cell["config"]), cell["config"]["reference"]
    spec = R.spec(hf, block)
    assert spec.held == (0, 16) and spec.published_experts == 128 and spec.route_scale == 2.5
    assert (spec.heads, spec.nope, spec.pe, spec.v_dim, spec.latent) == (64, 128, 64, 128, 512)
    assert R.softmax_scale(spec) == pytest.approx(0.13523, rel=1e-4)
    with pytest.raises(ValueError, match="WITHOUT q compression"):
        R.spec(dict(hf, q_lora_rank=1536), block)
    with pytest.raises(ValueError, match="held_experts"):
        R.spec(dict(hf, num_experts=32), block)
    with pytest.raises(ValueError, match="YaRN or none"):
        R.spec(dict(hf, rope_scaling={"type": "linear", "factor": 2}), block)
    with pytest.raises(ValueError, match="untied head"):
        R.spec(dict(hf, tie_word_embeddings=True), block)


def test_the_rehearsal_runs_to_a_result_line(tmp_path):
    # one CPU device, whatever the suite around this test forces (tests/conftest.py: 8),
    # and a compile cache of its own: other tests' subprocesses write the checkout's
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    for _ in range(3):
        out = subprocess.run(
            [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", CELL, "--seed", "2147483999",
             "--seconds", "2", "--rehearse", "--trace", "0"],
            capture_output=True, text=True, cwd=ROOT, timeout=900, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        lines = out.stdout.strip().splitlines()
        line = json.loads(lines[-1])
        said = (line["compared"], lines[-2][:3000], out.stderr[-1500:])
        # the harness's rule, not the cell's fault: a step that crosses the window's end
        # loses the requests due inside it (the last is due 0.25 s before the end), and on a
        # machine busy with the rest of the suite a tiny step has taken 1.7 s. Run again
        if json.loads(lines[-2])["notes"]["serve"]["never_sent"] == 0:
            break
    assert line["workload"] == CELL and line["attempted"] > 0 and line["failed"] == 0, said
    assert line["correct"] is True, said

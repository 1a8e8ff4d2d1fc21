"""What the Xing4.0 cell adds to the benchmark: the cell as ``load_cell`` sees
it, the configuration against the source's keys, the reference's ``shapes``
and the residual path's law, the per-token law against the published
parameter counts at the uncut configuration, the new readers on synthetic
traces, and the rehearsal."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.harness import loader, program_trace

ROOT = Path(__file__).resolve().parents[2]
CELL = "train-8k-xing4.0-29b-a4b"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = {"mhc_ms.train", "mhc_stream_roofline", "mtp_ms.train", "mhc_res_row_err"}


def _cell():
    return loader.load_cell(loader.load_benchmark(), CELL)


def _source():
    if not CATALOG.exists():
        pytest.skip("no catalog on this machine")
    return next(json.loads(line) for line in CATALOG.read_text().splitlines()
                if json.loads(line)["name"] == "Xing4.0-29B-A4B")


def test_the_cell_as_the_loader_sees_it():
    cell = _cell()
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "mock_documents"
    assert cell["reference"].__name__.endswith("xing4")
    t = cell["traffic"]
    assert (t["seq_length"], t["sequences_per_chip"], t["num_samples"], t["label_mask_ratio"],
            t["shuffle"], t["warm_steps"]) == (8192, 1, 4096, 0.25, True, 2)
    names = {m["name"] for m in cell["per_layer"]}
    assert NEW_METRICS | {"mla_attn_roofline.train", "held_expert_rows_pct.train",
                          "model_flops_util_pct", "lm_head_ce_ms", "optimizer_ms"} <= names
    # their laws count another shape (every pick a row, one head width), and
    # the delta rule's are another family's
    assert not {"expert_mlp_roofline", "flash_attn_roofline", "kda_ms.train", "kda_chunk_roofline"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s_per_chip", "setup_s"}
    for m in cell["per_layer"]:  # every reader the cell reports loads
        assert callable(loader.load_module("metrics", m["name"]).read)
    # the new metrics are this cell's alone
    for m in loader.load_benchmark()["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s_per_chip"
    hf = loader.program_hf_config(cell["config"])
    assert hf["n_routed_experts"] == 64 and hf["held_experts"] == [0, 8] and hf["vocab_size"] == 16384
    assert cell["config"]["n_routed_experts"] == 8  # the file's key counts the experts held here
    # one weight of the module's loss, stated to the program and to the reference alike
    assert hf["mtp_loss_weight"] == cell["config"]["reference"]["mtp_loss_weight"] == 0.3


def test_every_published_key_is_the_sources_but_the_reduced_ones():
    src = _source()
    mine = _cell()["config"]
    assert mine["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    for key, value in src["config"].items():
        if key not in mine["reduced"]:
            assert mine[key] == value, key
    assert mine["source"] == src["source_url"]
    # the floors: a leading dense layer and four that follow, 8 experts, an eighth of the vocabulary
    assert (mine["num_hidden_layers"] - mine["first_k_dense_replace"], mine["first_k_dense_replace"]) == (4, 1)
    assert mine["n_routed_experts"] == 8 and mine["vocab_size"] * 8 == src["config"]["vocab_size"]
    assert {k: mine["published"][k] for k in mine["reduced"]} == {k: src["config"][k] for k in mine["reduced"]}
    for key in ("deployment", "departures", "assumed"):
        assert mine[key], key


def test_shapes_and_the_residual_paths_law():
    cell = _cell()
    c = cell["reference"].shapes(loader.hf_config(cell["config"]))
    # six blocks: five of the stack and the module's
    assert (c["attention_layers"], c["expert_layers"], c["hc_sublayers"], c["hc_streams"]) == (6, 5, 12, 4)
    assert (c["q_heads"], c["qk_head_dim"], c["v_head_dim"], c["hidden"], c["expert_width"]) == (
        32, 192, 128, 3584, 1024)
    assert (c["held_experts"], c["published_experts"], c["top_k"], c["vocab"]) == (8, 64, 4, 16384)
    mhc = loader.load_module("kernels", "mhc")
    # forward: X and y in, X' and u out; backward: X, dX', y, du in, dX, dy out; bfloat16
    assert mhc.forward_bytes(1, 4, 3584) == (4 + 1 + 4 + 1) * 3584 * 2
    assert mhc.train_bytes(1, 4, 3584) == (10 + 4 + 4 + 1 + 1 + 4 + 1) * 3584 * 2
    # the step: 12 sublayers x 8,192 tokens = 17.6 GB, 21.5 ms at the HBM peak
    assert 12 * mhc.train_bytes(8192, 4, 3584) == pytest.approx(17.6e9, rel=0.01)
    # the attention law holds at these widths: 6 blocks x 32 heads x (192 + 128)
    mla = loader.load_module("kernels", "mla_attn")
    assert c["attention_layers"] * mla.forward_flops(1, 8192, 32, 192, 128) == pytest.approx(
        6 * 32 * (8192 * 8193 / 2) * 320 * 2)
    # ISSUE 43's table: 913.4 M parameters held; its 1.51 GFLOP a token forward
    assert c["parameter_count"]() == pytest.approx(913.4e6, rel=0.002)
    assert c["forward_flops_per_token"](8192) == pytest.approx(1.51e9, rel=0.03)
    # the head runs twice, the mixers are in it: twice the sequence moves only attention
    more = c["forward_flops_per_token"](16384) - c["forward_flops_per_token"](8192)
    assert more == pytest.approx(6 * 2 * 32 * (192 + 128) * 4096)


def test_the_law_against_the_published_counts_at_the_uncut_configuration():
    src = _source()["config"]
    c = _cell()["reference"].shapes(src)
    # the name says 29B-A4B. These keys count 29.51 B in the 40 layers, the
    # embedding and the head, + 0.77 B in the module = 30.28 B; a token uses
    # 3.93 B of the stack and the head + 0.11 B of the module
    total, active = c["parameter_count"](), c["parameter_count"](active=True)
    assert total == pytest.approx(30.28e9, rel=0.005)
    assert active == pytest.approx(4.04e9, rel=0.01)
    bare = _cell()["reference"].shapes(dict(src, num_nextn_predict_layers=0))
    assert bare["parameter_count"]() == pytest.approx(29e9, rel=0.02)
    assert bare["parameter_count"](active=True) == pytest.approx(4e9, rel=0.02)
    assert total - bare["parameter_count"]() == pytest.approx(0.77e9, rel=0.01)
    # forward FLOPs a token at a short sequence: twice the active parameters,
    # the head once more (the module's pass), but the embedding row
    short = c["forward_flops_per_token"](1)
    assert short == pytest.approx(2 * (active + 3584 * 131072), rel=0.01)


def _device_ops():
    """One ``jit_step_fn`` run on one device, picoseconds: (start, duration, name, tf_op, source)."""
    p = "jit(step_fn)/"
    return {0: {
        "modules": [(0, 20_000, "jit_step_fn(1)")],
        "ops": [
            (0, 400, "fusion.1", p + "jvp(norm)/mhc/mhc_coeff/dot_general", ""),
            (1000, 600, "fusion.2", p + "jvp(norm)/mhc/mhc_pre/mul", ""),
            (2000, 900, "fusion.3", p + "jvp(norm)/mhc/mhc_post/concatenate", ""),
            (3000, 700, "fusion.4", p + "rematted_computation/norm/mhc/mhc_post/concatenate", ""),
            (4000, 1500, "fusion.5", p + "transpose(jvp(norm))/mhc/mhc_post/mul", ""),
            (6000, 1100, "fusion.6", p + "transpose(jvp(norm))/mhc/mhc_pre/mul", ""),
            (8000, 2000, "splash_mha_fwd", p + "jvp(mtp)/attn/mla/pallas_call", ""),
            (10000, 300, "fusion.7", p + "jvp(mtp)/norm/mhc/mhc_pre/mul", ""),
            (11000, 500, "fusion.8", p + "transpose(jvp(mtp))/mlp/dot_general", ""),
            (12000, 5000, "fusion.9", p + "jvp(attn)/mla/dot_general", ""),
        ],
    }}


def test_the_new_readers_on_synthetic_ops(monkeypatch, tmp_path):
    monkeypatch.setattr(program_trace, "read_device_ops", lambda path: _device_ops())
    monkeypatch.setattr(program_trace, "xplane_of", lambda run: tmp_path)
    cell = _cell()
    run = {"cell": cell, "artefacts": {"kind": "train", "tokens_per_step": 8192},
           "device": {"count": 1}, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: loader.load_module("metrics", name).read(run)
    # every op with the `mhc` segment, the module's included
    assert read("mhc_ms.train") == pytest.approx((400 + 600 + 900 + 700 + 1500 + 1100 + 300) * 1e-9)
    # every op under `mtp`, whatever scope it keeps innermost
    assert read("mtp_ms.train") == pytest.approx((2000 + 300 + 500) * 1e-9)
    # the law's bytes at the peak over the time under mhc_pre + mhc_post alone
    need = 12 * (25 * 3584 * 2) * 8192
    seconds = (600 + 900 + 700 + 1500 + 1100 + 300) * 1e-12
    assert read("mhc_stream_roofline") == pytest.approx(100 * need / 819e9 / seconds)
    # a program without the scopes (the parent): nothing, not zeros
    monkeypatch.setattr(program_trace, "read_device_ops", lambda path: {0: {
        "modules": [(0, 10, "jit_step_fn(1)")], "ops": [(0, 5, "fusion", "jit(step_fn)/attn/dot", "")]}})
    for name in ("mhc_ms.train", "mtp_ms.train", "mhc_stream_roofline"):
        assert read(name) is None, name


def test_the_counter_reader_leaves_an_older_program_out(monkeypatch):
    reader = loader.load_module("metrics", "mhc_res_row_err")
    run = {"cell": _cell()}
    spans = [{"name": "train.place", "stats": {}},
             {"name": "train.counts", "stats": {"tokens": 8192, "held_expert_rows": 20000,
                                                "mtp_loss": 9.7, "mhc_res_row_err": "3.0e-05"}},
             {"name": "train.counts", "stats": {"tokens": 8192, "mhc_res_row_err": 5e-05}},
             {"name": "train.counts", "stats": {"tokens": 8192, "mhc_res_row_err": 4e-05}}]
    monkeypatch.setattr(program_trace, "spans", lambda run, prefix: spans)
    assert reader.read(run) == pytest.approx(4e-05)
    # a program that counts rows but no residual path (the Kimi cell's), and one with no counter
    monkeypatch.setattr(program_trace, "spans", lambda run, prefix: [
        {"name": "train.counts", "stats": {"tokens": 16384, "held_expert_rows": 16000}}])
    assert reader.read(run) is None
    monkeypatch.setattr(program_trace, "spans", lambda run, prefix: None)
    assert reader.read(run) is None


def test_the_reference_refuses_what_it_does_not_model():
    R = _cell()["reference"]
    hf = loader.hf_config(_cell()["config"])
    block = _cell()["config"]["reference"]
    spec = R.spec(hf, block)
    assert spec.held == (0, 8) and spec.published_experts == 64 and spec.mtp_weight == 0.3
    assert (spec.streams, spec.sinkhorn_iters, spec.clamp, spec.mtp_modules) == (4, 20, (-30.0, 30.0), 1)
    assert R.softmax_scale(spec) == pytest.approx(192 ** -0.5 * 1.4158883 ** 2, rel=1e-6)
    with pytest.raises(ValueError, match="low-rank q"):
        R.spec(dict(hf, q_lora_rank=None), block)
    with pytest.raises(ValueError, match="held_experts"):
        R.spec(dict(hf, n_routed_experts=16), block)
    with pytest.raises(ValueError, match="one multi-token-prediction module"):
        R.spec(dict(hf, num_nextn_predict_layers=2), block)
    with pytest.raises(ValueError, match="YaRN or none"):
        R.spec(dict(hf, rope_scaling={"type": "linear", "factor": 2}), block)


def test_the_rehearsal_runs_to_a_result_line():
    # one CPU device, whatever the suite around this test forces (tests/conftest.py: 8)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", CELL, "--seed", "2147483999",
         "--seconds", "2", "--rehearse", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["workload"] == CELL and line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, line["compared"]

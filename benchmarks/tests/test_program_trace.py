"""The program-trace reader, on the recorded chip trace of the train cell
(eight steps, TPU v5 lite, recorded before the program named its scopes: every
op is ``unscoped`` there, which is what an older commit looks like) and on
hand-made events."""

import gzip
import re
from pathlib import Path

import pytest

from benchmarks.harness import program_trace as P

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def train_xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "train_step.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "train_step.xplane.pb.gz").read_bytes()))
    return path


@pytest.fixture(scope="module")
def train_ops(train_xplane):
    devices = P.read_device_ops(train_xplane)
    assert list(devices) == [0]
    return devices[0]


def test_hand_decoder_finds_op_name_and_source_on_nearly_all_self_time(train_ops):
    ops = train_ops["ops"]
    own = P.self_times(ops)
    total = sum(own)
    tagged = sum(s for o, s in zip(ops, own) if o[3] and o[4])
    assert tagged / total >= 0.99  # 99.4 %; the rest are sub-microsecond copies
    # ... though only just over half of the EVENTS carry them
    assert 0.5 < sum(1 for o in ops if o[3]) / len(ops) < 0.65
    assert any(o[3].startswith("jit(step_fn)/transpose(jvp())/while/body/") for o in ops)
    assert any(o[4].endswith("automodel_tpu/ops/losses.py:135") for o in ops)


def test_self_time_sums_to_the_module_time(train_ops):
    modules = train_ops["modules"]
    assert len(modules) == 8 and all(re.match(r"jit_step_fn\(\d+\)$", m[2]) for m in modules)
    step_ms = sorted(m[1] * 1e-9 for m in modules)[4]
    assert 265.0 < step_ms < 266.2
    table = P.scope_table(train_ops)
    per_step = P.per_run_ms(table, r"^jit_step_fn$", lambda scope, d: True)
    assert len(per_step) == 8
    for ms in per_step:  # every instant of a step belongs to exactly one op
        assert abs(ms - step_ms) / step_ms < 0.005
    assert abs(table["busy_s"] - sum(per_step) * 1e-3) < 1e-6  # nothing outside the modules


def test_recompute_and_the_loss_lines_read_what_the_scratch_decode_read(train_ops):
    table = P.scope_table(train_ops)
    by = P.median_by_scope_ms(table, r"^jit_step_fn$")
    assert set(by) == {P.UNSCOPED}  # recorded before the scopes
    assert by[P.UNSCOPED]["remat"] == pytest.approx(47.1, abs=0.3)
    assert by[P.UNSCOPED]["fwd"] == pytest.approx(87.9, abs=0.5)
    assert by[P.UNSCOPED]["bwd"] == pytest.approx(130.6, abs=0.5)
    losses = sum(s for (_, what), s in table["unscoped"].items() if what.startswith("ops/losses.py"))
    assert 1e3 * losses / 8 == pytest.approx(134.0, abs=1.0)
    scan_line = table["unscoped"][("jit_step_fn", "qwen3_moe/model.py:332")]
    assert 1e3 * scan_line / 8 == pytest.approx(47.9, abs=0.3)
    assert table["scoped_s"] == 0.0 and table["tagged_s"] / table["busy_s"] > 0.99


def test_tensorflow_s_own_decoder_agrees(train_xplane, train_ops):
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    space.ParseFromString(train_xplane.read_bytes())
    plane = next(p for p in space.planes if p.name == "/device:TPU:0")
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    assert len(line.events) == len(train_ops["ops"])
    for ev, op in list(zip(line.events, train_ops["ops"]))[::97]:
        meta = plane.event_metadata[ev.metadata_id]
        stats = {names[s.metadata_id]: s.str_value for s in meta.stats}
        assert (line.timestamp_ns * 1000 + ev.offset_ps, ev.duration_ps) == op[:2]
        assert (stats.get("tf_op", ""), stats.get("source", "")) == op[3:5]


def test_self_time_gives_every_instant_to_the_innermost_op():
    #  while [0, 100) holds fusion [10, 40) which holds copy [20, 25); then add [100, 130)
    ops = [(0, 100, "while"), (10, 30, "fusion"), (20, 5, "copy"), (100, 30, "add")]
    assert P.self_times(ops) == [70, 25, 5, 30]
    # order in the file does not matter
    assert P.self_times(ops[::-1]) == [30, 5, 25, 70]


def test_scope_table_groups_by_innermost_scope_direction_and_module():
    path = "jit(step_fn)/{}/while/body/closed_call/{}mul:"
    dev = {
        "modules": [(0, 1000, "jit_step_fn(7)"), (2000, 1000, "jit_step_fn(7)")],
        "ops": [
            (0, 600, "while.1", "jit(step_fn)/jvp(layers)/while:", "model.py:1"),
            (100, 200, "fusion.1", path.format("jvp(layers)", "attn/"), "a/b/llama/model.py:9"),
            (300, 100, "fusion.2", path.format("jvp(layers)", "moe/dispatch/"), "x.py:2"),
            (600, 300, "fusion.3", path.format("transpose(jvp(layers))", "moe/experts/"), ""),
            (900, 100, "copy.1", "", ""),
            (2000, 500, "fusion.4",
             path.format("transpose(jvp(layers))", "checkpoint/rematted_computation/attn/"), ""),
            (2500, 500, "fusion.5", "jit(step_fn)/jit(_where)/select_n:", "a/b/train_step.py:3"),
        ],
    }
    table = P.scope_table(dev)
    first, second = table["runs"]["jit_step_fn"]
    ps = 1e-12
    assert first["layers"]["fwd"] == pytest.approx(300 * ps)  # the loop's own share
    assert first["attn"]["fwd"] == pytest.approx(200 * ps)
    assert first["moe/dispatch"]["fwd"] == pytest.approx(100 * ps)
    assert first["moe/experts"]["bwd"] == pytest.approx(300 * ps)
    assert second["attn"] == {"fwd": 0.0, "bwd": 0.0, "remat": pytest.approx(500 * ps)}
    assert table["unscoped"] == {("jit_step_fn", "copy.1"): pytest.approx(100 * ps),
                                 ("jit_step_fn", "b/train_step.py:3"): pytest.approx(500 * ps)}
    assert table["busy_s"] == pytest.approx(2000 * ps)
    assert table["scoped_s"] == pytest.approx(1400 * ps)
    moe = P.per_run_ms(table, r"^jit_step_fn$", lambda s, d: s.startswith("moe/"))
    assert moe == [pytest.approx(400 * ps * 1e3), 0.0]


def test_spans_nest_by_containment_and_gaps_are_cut_at_their_edges():
    def span(name, a, b):
        return {"name": name, "start_s": a, "end_s": b, "stats": {}, "thread": "t", "parent": None}

    spans = P.nest([span("serve.decode_wait", 3.0, 6.0), span("serve.step", 0.0, 10.0),
                    span("serve.admit", 1.0, 2.0), span("serve.step", 11.0, 12.0)])
    assert [(s["name"], s["parent"]) for s in spans] == [
        ("serve.step", None), ("serve.admit", 0), ("serve.decode_wait", 0), ("serve.step", None)]
    # a gap is cut at the spans' edges; every piece goes to the innermost span over it
    rows = P.gaps_by_span([(4.0, 5.0), (7.0, 7.5), (10.2, 10.8), (1.9, 2.3), (9.5, 11.5)], spans)
    assert [(round(sec, 6), inner, root) for sec, inner, root in rows] == [
        (1.0, "serve.decode_wait", "serve.step"), (0.5, "serve.step", "serve.step"),
        (0.6, None, None),
        (0.1, "serve.admit", "serve.step"), (0.3, "serve.step", "serve.step"),
        (0.5, "serve.step", "serve.step"), (1.0, None, None), (0.5, "serve.step", "serve.step")]


def test_readers_return_none_for_a_run_without_a_trace():
    run = {"reduction": None, "cell": {"workload": "train-30b-a3b"}}
    assert P.device_table(run) is None and P.spans(run, "serve.") is None
    assert P.serve_iterations(run) is None and P.iteration_counts(run) is None
    assert P.median_ms(run, r"^jit_step_fn$", lambda s, d: True) is None
    assert P.median_ms(run, r"^jit_step_fn$", lambda s, d: True, need_scopes=False) is None


# -- the serve cell's record: three engine iterations of a traced run on the
# chip (PR 25, seed 2147483811), cut from the 4 s trace to the device plane's
# ops and modules and the host's serve.* / bench:* events of that stretch


@pytest.fixture(scope="module")
def serve_xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "serve_steps.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "serve_steps.xplane.pb.gz").read_bytes()))
    return path


def test_serve_record_names_the_splits_and_nearly_all_device_time(serve_xplane):
    dev = P.read_device_ops(serve_xplane)[0]
    table = P.scope_table(dev)
    assert table["scoped_s"] / table["busy_s"] > 0.99
    assert len(table["runs"]["jit_step"]) == 3 and len(table["runs"]["jit_chunk"]) == 5
    # the two 7.4 ms `split` ops of every program are the gate/up weight's
    splits = [o for o in dev["ops"] if o[2].startswith("split.")]
    assert len(splits) == 2 * 8
    assert {P.scope_of(o[3]) for o in splits} == {"moe/dispatch"}
    assert all(o[4].endswith("automodel_tpu/moe/experts.py:59") for o in splits)
    assert all(7.0e9 < o[1] < 8.0e9 for o in splits)  # picoseconds
    decode = P.median_by_scope_ms(table, r"^jit_step$")
    assert decode["moe/dispatch"]["fwd"] == pytest.approx(14.7, abs=0.3)
    assert decode["moe/experts"]["fwd"] == pytest.approx(10.6, abs=0.3)
    assert decode["attn"]["fwd"] == pytest.approx(6.8, abs=0.5)
    overhead = P.per_run_ms(table, r"^jit_step$", P.moe_overhead)
    assert sorted(overhead)[1] == pytest.approx(14.77, abs=0.3)
    assert all(cell["bwd"] == cell["remat"] == 0.0 for cell in decode.values())


def test_serve_record_spans_counts_and_idle_by_phase(serve_xplane):
    from benchmarks.harness import trace

    spans = P.read_spans(serve_xplane)
    steps = [(i, s) for i, s in enumerate(spans) if s["name"] == "serve.step"]
    assert [int(s["stats"]["step"]) for _, s in steps] == [336, 337, 338]
    counts = []
    for i, step in steps:
        kids = P.children_of(spans, i)
        assert kids[0]["name"] == "serve.admit" and kids[-1]["name"] == "serve.counts"
        assert sum(k["name"] == "serve.decode_wait" for k in kids) == 1
        counts.append({k: int(v) for k, v in kids[-1]["stats"].items()})
        assert counts[-1]["chunks"] == sum(k["name"] == "serve.prefill_dispatch" for k in kids)
    assert [c["chunks"] for c in counts] == [3, 1, 1]
    assert [c["decoded"] for c in counts] == [45, 45, 45]
    # nothing finished in the first two: every active slot grew by one token
    assert counts[1]["context_tokens"] - counts[0]["context_tokens"] == 45
    assert counts[2]["context_tokens"] - counts[1]["context_tokens"] == 45
    # spans cut by the record's edges have no parent and are no iteration
    assert [s["name"] for s in spans if s["parent"] is None and s["name"] != "serve.step"] == [
        "serve.counts", "serve.admit"]

    gaps = trace.reduce_file(serve_xplane)["devices"][0]["gaps"]
    by: dict = {}
    for seconds, inner, root in P.gaps_by_span(gaps, spans):
        by[inner] = by.get(inner, 0.0) + seconds
    assert sum(by.values()) == pytest.approx(sum(b - a for a, b in gaps))
    in_step = sum(v for k, v in by.items() if k is not None)
    assert in_step / sum(by.values()) > 0.95  # the harness's own loop is the rest
    assert by["serve.step"] / in_step < 0.05  # idle falls in NAMED phases
    # the device waits while a chunk or the decode program is being launched,
    # and again before the host has its result
    for phase in ("serve.prefill_dispatch", "serve.decode_dispatch", "serve.decode_wait",
                  "serve.first_token_wait"):
        assert by[phase] > 1e-3, phase

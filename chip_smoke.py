"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        # from the root of a checkout, on a TPU host

Drives the two main paths once, through the CLI a user would call, at the
full width of one supported model (the 30B-A3B sparse-expert block through
the ``qwen3_moe`` family; depth cut, seeded random weights, mock data — see
examples/chip_smoke/*.yaml for every cut):

1. ``automodel_tpu pretrain llm`` — a few optimizer steps;
2. ``automodel_tpu serve`` — stdin-JSONL, a handful of ``prompt_ids``
   requests of mixed length.

Each leg is a child process, one after another: a chip belongs to one
process at a time, so this parent never imports JAX. Mesh degrees follow the
device count a throw-away probe child reports — one chip: everything on it;
a four-chip host: experts over the chips for the train leg (``ep``), heads
and the KV pool over the chips for the serve leg (``tp``).

Exit codes are not trusted, least of all the serve leg's (the engine catches
a failed step, fails the wave, rebuilds, and the stdin front still returns
0). What is checked is what the children report from inside: platform
``tpu`` and the device count, the kernels each program really traced and its
Mosaic custom-call count, per-step losses that are finite, start at
ln(vocab) and fall, every request answered ``length``/``stop`` with its full
token count and the same prompt answered identically twice.

No TPU (or ``JAX_PLATFORMS=cpu``): a message on stderr, exit 1, no result.
On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Compile seconds per leg are printed, so a second run against the same
compile cache (utils/compile_cache.py) shows the hits.
"""

from __future__ import annotations

import json
import math
import os
import random
import shutil
import signal
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
TRAIN_YAML = ROOT / "examples" / "chip_smoke" / "train_30b_a3b.yaml"
SERVE_YAML = ROOT / "examples" / "chip_smoke" / "serve_30b_a3b.yaml"
OUT = ROOT / "chiprun_out" / "chip_smoke"

VOCAB = 151936
TRAIN_STEPS = 6
TOKENS_PER_CHIP = 2  # sequences of 4096 per chip per step (train yaml)
# 1200 s in all, compilation included
PROBE_TIMEOUT_S, TRAIN_TIMEOUT_S, SERVE_TIMEOUT_S = 120, 540, 480

_PROBE = (
    "import json, jax\n"
    "d = jax.devices()\n"
    "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind,"
    " 'count': len(d)}))\n"
)


class SmokeFailure(Exception):
    pass


def _run_child(cmd: list[str], timeout_s: float, stdin_text: str | None = None):
    """Run one child in its own process group; on timeout the whole group is
    killed, so nothing this script started outlives it. → (rc, out, err)."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(ROOT)] + [p for p in [os.environ.get("PYTHONPATH")] if p]
    ))
    proc = subprocess.Popen(
        cmd, cwd=ROOT, env=env, text=True, start_new_session=True,
        stdin=subprocess.PIPE if stdin_text is not None else subprocess.DEVNULL,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        out, err = proc.communicate(stdin_text, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, err = proc.communicate()
        raise SmokeFailure(
            f"{' '.join(cmd[:6])} ... did not finish in {timeout_s:.0f}s\n"
            f"--- stderr tail ---\n{err[-3000:]}"
        )
    return proc.returncode, out, err


def _json_lines(text: str) -> list[dict]:
    out = []
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def _read_jsonl(path: Path) -> list[dict]:
    return _json_lines(path.read_text()) if path.exists() else []


def _check(cond: bool, what: str, problems: list[str]) -> None:
    if not cond:
        problems.append(what)


def _check_device(report: dict | None, device: dict, problems: list[str]) -> None:
    _check(report is not None, "no device_report record from the child", problems)
    if report is not None:
        got = {k: report.get(k) for k in ("platform", "device_kind", "count")}
        want = {"platform": "tpu", "device_kind": device["kind"],
                "count": device["count"]}
        _check(got == want, f"child ran on {got}, probe saw {want}", problems)


def _check_kernels(
    cost: dict | None, program: str, families: dict[str, str],
    problems: list[str],
) -> dict:
    """``families``: {what the leg must run: kernel-name prefix}. Each must
    appear among the Pallas kernels the program traced, and the lowered
    module must hold at least that many Mosaic custom calls — a path that
    fell back to XLA shows as a missing family or a count of zero."""
    _check(cost is not None and "error" not in (cost or {}),
           f"no cost_attribution record for {program}: {cost}", problems)
    if not cost or "error" in cost:
        return {}
    kernels = cost.get("pallas_kernels") or {}
    for what, prefix in families.items():
        _check(
            any(name.startswith(prefix) for name in kernels),
            f"{program}: no {what} kernel ({prefix}*) among {sorted(kernels)}",
            problems,
        )
    mosaic = cost.get("mosaic_calls") or 0
    _check(mosaic >= max(len(families), 1),
           f"{program}: {mosaic} Mosaic custom calls", problems)
    return {"pallas_kernels": kernels, "mosaic_calls": mosaic}


def _compile_report(stderr_text: str) -> dict:
    for rec in reversed(_json_lines(stderr_text)):
        if rec.get("event") == "compile_report":
            return {k: rec[k] for k in
                    ("compiles", "compile_secs", "cache_hits", "cache_misses")}
    return {}


def _fail_leg(leg: str, problems: list[str], out: str, err: str) -> None:
    raise SmokeFailure(
        f"{leg} leg failed:\n  - " + "\n  - ".join(problems)
        + f"\n--- stdout tail ---\n{out[-2500:]}\n--- stderr tail ---\n{err[-2500:]}"
    )


def probe_device() -> dict:
    rc, out, err = _run_child([sys.executable, "-c", _PROBE], PROBE_TIMEOUT_S)
    recs = _json_lines(out)
    if rc != 0 or not recs:
        raise SmokeFailure(f"JAX found no usable device (rc {rc}):\n{err[-2000:]}")
    device = recs[-1]
    if device["platform"] != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX reports platform {device['platform']!r} "
            f"({device['count']} x {device['kind']}); chip_smoke.py proves "
            "the chip path and does not run anywhere else"
        )
    return device


def train_leg(device: dict) -> dict:
    n = device["count"]
    leg_dir = OUT / "train"
    shutil.rmtree(leg_dir, ignore_errors=True)
    metrics = leg_dir / "train_metrics.jsonl"
    rc, out, err = _run_child(
        [
            sys.executable, "-m", "automodel_tpu.cli.app", "pretrain", "llm",
            "-c", str(TRAIN_YAML),
            f"--distributed.ep={n}",
            f"--dataloader.global_batch_size={TOKENS_PER_CHIP * n}",
            f"--dataset.num_samples={TOKENS_PER_CHIP * n}",
            f"--logging.metrics_path={metrics}",
            f"--output_dir={leg_dir}",
        ],
        TRAIN_TIMEOUT_S,
    )
    records = _read_jsonl(metrics)
    by_event = {r["event"]: r for r in records if "event" in r}
    problems: list[str] = []
    _check(rc == 0, f"exit code {rc}", problems)
    _check_device(by_event.get("device_report"), device, problems)
    kernels = _check_kernels(
        by_event.get("cost_attribution"), "train_step",
        {"attention": "splash_mha", "experts": "fused_expert_mlp"}, problems,
    )
    losses = [r["loss"] for r in records if "loss" in r and "event" not in r]
    _check(len(losses) == TRAIN_STEPS,
           f"{len(losses)} logged steps, want {TRAIN_STEPS}", problems)
    finite = all(isinstance(x, (int, float)) and math.isfinite(x) for x in losses)
    _check(finite, f"non-finite loss in {losses}", problems)
    if losses and finite:
        # seeded near-zero-logit init: the first loss is ln(vocab) to within
        # the spread of unit-variance logits
        _check(abs(losses[0] - math.log(VOCAB)) < 1.0,
               f"first loss {losses[0]:.3f} vs ln(vocab) {math.log(VOCAB):.3f}",
               problems)
        _check(losses[-1] < losses[0], f"loss did not fall: {losses}", problems)
    if problems:
        _fail_leg("train", problems, out, err)
    report = by_event["device_report"]
    return {
        "leg": "train", "steps": len(losses),
        "loss": [round(x, 4) for x in losses],
        "mesh": report.get("mesh"), "attn": report.get("attn"),
        "experts": report.get("experts"), **kernels,
        "compile": _compile_report(err),
    }


def _requests() -> list[dict]:
    rng = random.Random(0)
    reqs = [
        {"id": f"r{i}", "max_new_tokens": new,
         "prompt_ids": [rng.randrange(3, VOCAB) for _ in range(plen)]}
        for i, (plen, new) in enumerate(
            [(5, 12), (37, 8), (300, 16), (700, 8), (1500, 10)]
        )
    ]
    # the first prompt again: the same program on the same input must give
    # the same tokens (shorter than a block, so no prefix-cache hit either)
    reqs.append(dict(reqs[0], id="r0-again"))
    return reqs


def serve_leg(device: dict) -> dict:
    n = device["count"]
    leg_dir = OUT / "serve"
    shutil.rmtree(leg_dir, ignore_errors=True)
    leg_dir.mkdir(parents=True)
    metrics = leg_dir / "serve_metrics.jsonl"
    reqs = _requests()
    rc, out, err = _run_child(
        [
            sys.executable, "-m", "automodel_tpu.cli.app", "serve",
            "-c", str(SERVE_YAML),
            f"--distributed.tp={n}",
            f"--logging.metrics_path={metrics}",
        ],
        SERVE_TIMEOUT_S,
        stdin_text="".join(json.dumps(r) + "\n" for r in reqs),
    )
    lines = _json_lines(out)
    records = _read_jsonl(metrics)
    problems: list[str] = []
    _check(rc == 0, f"exit code {rc}", problems)
    report = next((r for r in records if r.get("event") == "device_report"), None)
    _check_device(report, device, problems)
    _check((report or {}).get("decode_backend") == "fused",
           f"decode_backend {(report or {}).get('decode_backend')!r}, want 'fused'",
           problems)
    costs = {r["program"]: r for r in records
             if r.get("event") == "cost_attribution"}
    kernels = {
        "chunk_prefill": _check_kernels(
            costs.get("chunk_prefill"), "chunk_prefill",
            {"experts": "fused_expert_mlp"}, problems,
        ),
        "paged_decode": _check_kernels(
            costs.get("paged_decode"), "paged_decode",
            {"attention": "paged_attention", "experts": "fused_expert_mlp"},
            problems,
        ),
    }
    events = [r for r in lines if r.get("event") == "serve_engine_event"]
    _check(not events, f"engine rebuilt mid-run: {events}", problems)
    errors = [r for r in lines if "error" in r]
    _check(not errors, f"error lines: {errors}", problems)
    answers = {r["request_id"]: r for r in lines if "request_id" in r}
    summary = []
    for req in reqs:
        ans = answers.get(req["id"])
        if ans is None:
            problems.append(f"request {req['id']} was never answered")
            continue
        toks = [int(t) for t in ans.get("completion", "").split()]
        reason = ans.get("completion_reason")
        want = req["max_new_tokens"]
        _check(reason in ("length", "stop"),
               f"{req['id']}: completion_reason {reason!r}", problems)
        _check(
            len(toks) == ans.get("n_generated")
            and (len(toks) == want if reason == "length" else 0 < len(toks) <= want),
            f"{req['id']}: {len(toks)} tokens (n_generated "
            f"{ans.get('n_generated')}), asked {want}, reason {reason!r}",
            problems,
        )
        _check(all(0 <= t < VOCAB for t in toks),
               f"{req['id']}: token ids outside the vocabulary", problems)
        summary.append({"id": req["id"], "prompt_tokens": len(req["prompt_ids"]),
                        "completion_reason": reason, "n_generated": len(toks)})
    if "r0" in answers and "r0-again" in answers:
        _check(answers["r0"]["completion"] == answers["r0-again"]["completion"],
               "the same prompt gave two different greedy completions: "
               f"{answers['r0']['completion']!r} vs "
               f"{answers['r0-again']['completion']!r}", problems)
    if problems:
        _fail_leg("serve", problems, out, err)
    return {
        "leg": "serve", "requests": summary, "mesh": report.get("mesh"),
        "attn": report.get("attn"), "experts": report.get("experts"),
        "decode_backend": report.get("decode_backend"), "programs": kernels,
        "compile": _compile_report(err),
    }


def main() -> int:
    for path in (TRAIN_YAML, SERVE_YAML, ROOT / "automodel_tpu" / "cli" / "app.py"):
        if not path.exists():
            print(f"chip_smoke.py needs the repository around it: {path} is "
                  "missing", file=sys.stderr)
            return 2
    try:
        device = probe_device()
        print(json.dumps({"probe": device}), flush=True)
        for leg in (train_leg, serve_leg):
            print(json.dumps(leg(device)), flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Model-FLOP/s utilisation of the training window: the benchmark's per-token
law (kernels/model_flops.py; recomputation not counted) x tokens/s/chip of
this run's window / the chip's bf16 peak. Moves train_tokens_per_s_per_chip
(at a fixed configuration it is that rate times a constant)."""

from benchmarks.metrics._common import hf, kernel


def read(run: dict):
    a = run["artefacts"]
    if a["kind"] != "train" or not run["peaks"] or not a["steps"]:
        return None
    per_token = kernel("model_flops").train_flops_per_token(hf(run), a["seq_length"])
    rate = a["tokens"] / a["window_s"] / run["device"]["count"]
    return 100.0 * per_token * rate / run["peaks"]["bf16_flops_per_s"]

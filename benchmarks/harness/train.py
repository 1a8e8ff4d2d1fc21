"""Drive one training cell: the program's recipe, its dataloader, its step.

Set-up builds ONE object (the recipe: compiled step + state), drives it from
the seed through its first three steps by the very call and feed the window
uses, warms it, and hands that same object to the window. The window counts
the input tokens of the steps it completes; each step ends in a barrier on
its loss. After the window the program's state is freed and the plain
reference follows the same first three batches (``check`` below).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.harness import weights as W
from benchmarks.harness.loader import program_hf_config

CHECK_STEPS = 3
SMALL_LEAF = 1 << 24  # as the reference's: gradients compared element by element


def program_config(cell: dict, seed: int, out_dir: str, n_devices: int) -> dict:
    """The program's own YAML, as a dict: model + mesh + optimizer from the
    configuration file, data from the traffic mix."""
    config, traffic = cell["config"], cell["traffic"]
    prog = config["program"]
    if traffic.get("generator") != "mock_documents":
        raise ValueError(f"train traffic generator {traffic.get('generator')!r}")
    cfg = {
        "seed": int(seed) & 0x7FFFFFFF,
        "model": {"hf_config": program_hf_config(config), "backend": dict(prog["backend"])},
        "distributed": dict(prog["distributed"]),
        "dataset": {
            "_target_": "automodel_tpu.data.sft.MockSFTDataset",
            "vocab_size": int(config["vocab_size"]),
            "seq_length": int(traffic["seq_length"]),
            "num_samples": int(traffic["num_samples"]),
            "mask_ratio": float(traffic["label_mask_ratio"]),
            "seed": int(seed),
        },
        "dataloader": {
            "global_batch_size": int(traffic["sequences_per_chip"]) * n_devices,
            "shuffle": bool(traffic.get("shuffle", True)),
        },
        "step_scheduler": {"max_steps": 10**9, "num_epochs": 10**6, "log_every_steps": 10**9},
        "optimizer": dict(prog["optimizer"]),
        "loss_fn": dict(prog["loss_fn"]),
        "checkpoint": dict(prog.get("checkpoint", {"enabled": False})),
        "profiling": dict(prog.get("profiling", {})),
        "output_dir": out_dir,
    }
    return cfg


def build_recipe(cfg: dict, seed: int):
    from automodel_tpu import auto_model
    from automodel_tpu.config.loader import ConfigNode
    from automodel_tpu.recipes.train_ft import TrainFinetuneRecipeForNextTokenPrediction

    class SeededRecipe(TrainFinetuneRecipeForNextTokenPrediction):
        """The program's recipe with the benchmark's weights: the shape of
        the tree is the program's, every value comes from ``--seed``."""

        def _build_auto(self, mcfg, backend):
            auto = auto_model.from_config(
                mcfg.get("hf_config").to_dict(), self.mesh_ctx, backend, abstract=True
            )
            self.abstract_params = auto.params
            auto.params = W.make(auto.params, seed)
            return auto

    recipe = SeededRecipe(ConfigNode(cfg))
    recipe.setup()
    return recipe


class Stepper:
    """The window's own call and feed, used by set-up and the window alike."""

    def __init__(self, recipe, spans):
        self.recipe, self.spans = recipe, spans
        self.batches = iter(recipe.step_scheduler)

    def step(self, keep_host_batch: bool = False):
        r = self.recipe
        with self.spans.span("input"):
            group = next(self.batches)
            stacked, n_tokens = r._prepare_group(group)
            batch = r._place_group(stacked)
        with self.spans.span("train_step"):
            r.state, metrics = r.train_step(r.state, batch)
            loss = float(metrics["loss"])  # the barrier
        host = None
        if keep_host_batch:
            host = {k: np.asarray(stacked[k]) for k in ("input_ids", "labels")}
        return loss, n_tokens, host


def _leaf_norms_by_name(tree) -> dict:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(
        lambda t: jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t)
    )(tree)
    return {
        W.path_name(p): float(v)
        for p, v in jax.tree_util.tree_flatten_with_path(jax.device_get(norms))[0]
    }


def _delta_norms(new, old):
    """Per-leaf norms of new - old, in float32, in one jitted call."""
    import jax
    import jax.numpy as jnp

    return jax.device_get(jax.jit(
        lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))),
            a, b,
        )
    )(new, old))


def free(recipe, stepper) -> dict:
    """Give the device back before the reference runs; -> the abstract tree."""
    import jax

    abstract = recipe.abstract_params
    recipe._close_prefetch()
    recipe.state = None
    recipe.auto.params = None
    stepper.recipe = None
    jax.clear_caches()
    return abstract


def _find_adam_mu(opt_state):
    for s in opt_state if isinstance(opt_state, tuple) else (opt_state,):
        if hasattr(s, "mu"):
            return s.mu
    raise ValueError("no Adam state in the optimizer's chain")


def first_steps(stepper: Stepper, abstract_params, seed: int, b1: float) -> dict:
    """The program's side of the check: three steps through the window's own
    call; each loss, the per-leaf norm of the first gradient as the optimizer
    got it (mu after step one is (1 - b1) g), and the per-leaf norm of the
    parameters' change after the three."""
    import jax
    import jax.numpy as jnp

    losses, batches = [], []
    grad_norms = small_grads = None
    for i in range(CHECK_STEPS):
        loss, _, host = stepper.step(keep_host_batch=True)
        losses.append(loss)
        batches.append(host)
        if i == 0:
            mu = _find_adam_mu(stepper.recipe.state.opt_state)
            grad_norms = {k: v / (1.0 - b1) for k, v in _leaf_norms_by_name(mu).items()}
            small_grads = {
                W.path_name(p): np.asarray(jax.device_get(a), np.float64) / (1.0 - b1)
                for p, a in jax.tree_util.tree_flatten_with_path(mu)[0]
                if a.size <= SMALL_LEAF
            }
    delta = _delta_norms(stepper.recipe.state.params, W.make(abstract_params, seed))
    delta_norms = {
        W.path_name(p): float(v) for p, v in jax.tree_util.tree_flatten_with_path(delta)[0]
    }
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms,
            "small_grads": small_grads, "batches": batches}


def reference_steps(cell: dict, abstract_params, seed: int, batches: list, precision: str = "f32") -> dict:
    """The reference's side: the same weights from the seed, the same three
    batches, the optimizer the configuration states. Run after the program's
    state is freed."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.loader import hf_config
    from benchmarks.reference import moe_decoder as R

    config = cell["config"]
    spec = R.DecoderSpec.from_config(hf_config(config), config["reference"])
    o = config["program"]["optimizer"]
    opt = R.AdamSpec(
        lr=float(o["lr"]), b1=float(o["betas"][0]), b2=float(o["betas"][1]),
        eps=float(o.get("eps", 1e-8)), weight_decay=float(o.get("weight_decay", 0.0)),
        clip_norm=o.get("grad_clip_norm"), moments_dtype=o.get("moments_dtype") or "float32",
    )
    bare = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), abstract_params)
    params = W.make(bare, seed, reference_layout=True)
    mu, nu = R.init_moments(params, opt)
    losses, grad_norms, small_grads = [], None, None
    for i, b in enumerate(batches):
        ids = jnp.asarray(b["input_ids"][0])
        labels = jnp.asarray(b["labels"][0])
        params, mu, nu, loss, gn, small = R.train_step(
            params, mu, nu, jnp.int32(i), ids, labels, spec, opt, precision
        )
        losses.append(float(loss))
        if i == 0:
            grad_norms = W.by_program_name(jax.device_get(gn), bare, norms=True)
            small_grads = W.by_program_name(jax.device_get(small), bare, norms=False)
        del gn, small
    del mu, nu
    # the starting point is drawn again rather than kept beside the moments
    delta = _delta_norms(params, W.make(bare, seed, reference_layout=True))
    delta_norms = W.by_program_name(delta, bare, norms=True)
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms,
            "small_grads": small_grads}


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """max over leaves of |program norm - reference norm| / max(reference
    norm of that leaf, reference norm of the median leaf)."""
    median = float(np.median(list(reference.values())))
    worst, where = 0.0, ""
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, median, 1e-30)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def compare(program: dict, reference: dict, limits: dict) -> tuple[bool, list]:
    """-> (all inside their limits, [(name, value, limit)] for the run's log)."""
    rows = []
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        rows.append((f"loss_gap_step{i + 1}", abs(a - b), limits["loss_gap"]))
    g, where_g = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    rows.append((f"grad_norm_gap_worst_leaf[{where_g}]", g, limits["grad_norm_gap"]))
    e, where_e = 0.0, ""
    for name, got in program["small_grads"].items():
        ref = reference["small_grads"][name]
        got = np.asarray(got).reshape(ref.shape)
        err = float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))
        if err > e:
            e, where_e = err, name
    rows.append((f"small_grad_rel_diff_worst_leaf[{where_e}]", e, limits["small_grad_rel_diff"]))
    d, where_d = worst_leaf_gap(program["delta_norms"], reference["delta_norms"])
    rows.append((f"param_change_gap_worst_leaf[{where_d}]", d, limits["param_change_gap"]))
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows


def run(cell: dict, args, ctx) -> dict:
    """``ctx``: what run.py prepared (device, spans, clock, out dir, tracer)."""
    import jax

    from automodel_tpu.telemetry import compile_events

    traffic = cell["traffic"]
    n_dev = cell["chips"]
    cfg = program_config(cell, args.seed, str(ctx.out_dir), n_dev)
    t0 = time.perf_counter()
    recipe = build_recipe(cfg, args.seed)
    ctx.note("setup_split", recipe_setup_s=time.perf_counter() - t0)
    stepper = Stepper(recipe, ctx.spans)
    b1 = float(cell["config"]["program"]["optimizer"]["betas"][0])
    t0 = time.perf_counter()
    program_side = first_steps(stepper, recipe.abstract_params, args.seed, b1)
    ctx.note("setup_split", first_three_steps_s=time.perf_counter() - t0)
    for _ in range(int(traffic.get("warm_steps", 2))):
        stepper.step()

    # -- the window ------------------------------------------------------------
    compiles_before = compile_events.compile_totals()
    ctx.window_opens()
    tokens = steps = 0
    losses = []
    t_start = time.perf_counter()
    trace_at = t_start + min(2.0, 0.25 * args.seconds)
    while time.perf_counter() - t_start < float(args.seconds):
        if ctx.trace and not ctx.tracing and not ctx.traced and time.perf_counter() >= trace_at:
            ctx.start_trace()
        loss, n, _ = stepper.step()
        if ctx.tracing:
            ctx.traced_steps += 1
            ctx.traced_tokens += n
            if ctx.traced_steps >= ctx.trace_steps:
                ctx.stop_trace()
        tokens += n
        steps += 1
        losses.append(loss)
    # the rate is over ALL the steps begun in the window and ALL the time
    # they took: the last step runs to its barrier and its time is counted
    window_s = time.perf_counter() - t_start
    if ctx.tracing:
        ctx.stop_trace()
    compiles_after = compile_events.compile_totals()
    ctx.window_closes()
    compiled_in_window = compiles_after["compiles"] - compiles_before["compiles"]
    memory_peak = ctx.memory_peak_bytes()

    # -- free the program, then the reference ------------------------------------
    abstract = free(recipe, stepper)
    del recipe, stepper
    t0 = time.perf_counter()
    reference_side = reference_steps(cell, abstract, args.seed, program_side["batches"])
    ctx.note("check", reference_s=time.perf_counter() - t0)
    limits = cell["config"]["reference"]["limits"]
    ok, rows = compare(program_side, reference_side, limits)
    finite = all(math.isfinite(x) for x in losses + program_side["losses"])
    rows.append(("nonfinite_losses_in_window", float(not finite), 0.0))
    rows.append(("compiles_in_window", float(compiled_in_window), 0.0))
    ctx.print_comparison(rows)
    correct = ok and finite and compiled_in_window == 0 and steps > 0
    return {
        "correct": bool(correct),
        "attempted": steps,
        "failed": 0 if finite else steps,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {
            "train_tokens_per_s_per_chip": tokens / window_s / n_dev,
        },
        "artefacts": {
            "kind": "train",
            "window_s": window_s, "steps": steps, "tokens": tokens,
            "tokens_per_step": tokens // max(steps, 1), "losses": losses,
            "seq_length": int(traffic["seq_length"]),
        },
    }

"""Compile a recipe's device programs for a TPU topology — no chip needed.

The pre-check before any chip call: build the train step (``pretrain`` /
``finetune`` YAMLs) or the serving programs (YAMLs with a ``serving:``
section) from the same builders the CLI uses, on ``ShapeDtypeStruct``s
sharded over a mesh of compile-only TPU devices (utils/compile_only.py),
and run the real XLA:TPU + Mosaic compile. A kernel the compiler refuses, a
mesh GSPMD cannot partition or a program that does not fit the chip's
memory shows here in seconds instead of costing a chip call.

    JAX_PLATFORMS=cpu python tools/compile_check.py -c cfg.yaml \
        [--topology v5e:2x2] [--devices 1|4] [--dotted.override=value ...]

Prints one JSON line per program: its Mosaic custom-call count and the
compiler's memory analysis in bytes (arguments, temporaries, output; the
sum is what one device must hold). Nothing is executed, so nothing here is
a time or a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp


def _auto(cfg, ctx):
    from automodel_tpu import auto_model

    hf = cfg.model.get("hf_config")
    if hf is None:
        raise SystemExit(
            "compile_check builds from model.hf_config (seeded weights have "
            "no checkpoint to read here)"
        )
    return auto_model.from_config(
        hf.to_dict(), ctx, dict(cfg.model.get("backend", {}) or {}),
        abstract=True,
    )


def train_programs(cfg, ctx):
    from automodel_tpu.optim.builders import build_optimizer, opt_state_shardings
    from automodel_tpu.optim.scheduler import build_lr_schedule
    from automodel_tpu.training.train_state import TrainState
    from automodel_tpu.training.train_step import (
        build_train_step,
        make_causal_lm_loss,
    )

    auto = _auto(cfg, ctx)
    ocfg = dict(cfg.get("optimizer", {}) or {"name": "adamw"})
    ocfg.pop("_target_", None)
    lr_schedule = build_lr_schedule(
        lr=ocfg.get("lr", 1e-4), **dict(ocfg.get("lr_schedule") or {})
    )
    optimizer = build_optimizer(**ocfg)
    state = TrainState(
        params=auto.params,
        opt_state=jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(optimizer.init, auto.params),
            opt_state_shardings(optimizer, auto.params, ctx),
        ),
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=ctx.replicated()),
    )
    lcfg = dict(cfg.get("loss_fn", {}) or {})
    lcfg.pop("_target_", None)
    loss_fn = make_causal_lm_loss(
        auto.model, loss=lcfg.pop("name", "masked_ce"),
        constrain=auto.constrain, **lcfg,
    )
    step = build_train_step(
        loss_fn, optimizer, lr_schedule,
        post_step_fn=getattr(auto.model, "post_step_fn", None),
    )
    acc = int((cfg.get("step_scheduler") or {}).get("grad_acc_steps", 1))
    gbs = int(cfg.dataloader.get("global_batch_size"))
    seq = int(cfg.dataset.get("seq_length"))
    ids = jax.ShapeDtypeStruct(
        (acc, gbs // acc, seq), jnp.int32,
        sharding=ctx.sharding(None, "batch", "seq"),
    )
    return [("train_step", step, (state, {"input_ids": ids, "labels": ids}))]


def serve_programs(cfg, ctx):
    from automodel_tpu.generation.engine import GenerationConfig
    from automodel_tpu.serving import paged
    from automodel_tpu.serving.engine import ServeConfig, ServingEngine

    class AbstractEngine(ServingEngine):
        """The real engine with a pool of ShapeDtypeStructs, placed as
        ``paged.place_pool`` would place the arrays."""

        def _init_pool_arrays(self) -> None:
            pool = jax.eval_shape(
                lambda: paged.layout_pool(
                    self._layout, self.config.slots, self.config.num_blocks,
                    self.config.block_size, dtype=self._compute_dtype,
                    quantized=self._quantized, mesh_ctx=self.auto.mesh_ctx,
                )
            )
            # the pool is a real array when the programs run: row-major, as
            # `jnp.zeros` makes it. Left open, the compiler picks the layout
            # each program likes best for its parameter and hides the
            # relayout copies it would make around the program on the chip
            from jax.experimental.layout import Format, Layout

            self._pool = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=Format(Layout(major_to_minor=tuple(range(a.ndim))), s),
                ),
                pool,
                # a latent pool (one side) is replicated, as place_pool does
                paged.PagedKV(k=self.auto.mesh_ctx.replicated(), v=None)
                if pool.latent
                else paged.pool_shardings(
                    self.auto.mesh_ctx, pool.values_shape[3], self._quantized,
                    pool.state is not None,
                ),
            )

    serve = dict(cfg.get("serving", {}) or {})
    serve.pop("http", None)
    eng = AbstractEngine(
        _auto(cfg, ctx), ServeConfig.from_dict(serve),
        GenerationConfig.from_dict(dict(cfg.get("generation", {}) or {})),
    )
    rep = ctx.replicated()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    B, NB = eng.config.slots, eng.config.table_blocks
    key = jax.eval_shape(lambda: eng._base_key)
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)
    i32 = sds((), jnp.int32)
    print(json.dumps({"decode_backend": eng.decode_backend}), flush=True)
    return [
        (
            "chunk_prefill", eng._chunk,
            (eng.auto.params, eng._pool, sds((NB,), jnp.int32),
             sds((eng.config.prefill_chunk,), jnp.int32), i32, i32,
             # a layout with recurrent state also names the slot's state row
             *((i32,) if eng._pool.state is not None else ())),
        ),
        (
            "paged_decode", eng._decode,
            (eng.auto.params, eng._pool, sds((B, NB), jnp.int32),
             sds((B,), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.bool_),
             key, i32,
             # the step before's tokens, and the rows that take `cur` from them
             sds((B,), jnp.int32), sds((B,), jnp.bool_)),
        ),
    ]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--devices", type=int, default=None,
                    help="use the first N devices of the topology (default: all)")
    args, rest = ap.parse_known_args(argv)

    from automodel_tpu.config.arg_parser import parse_args_and_load_config
    from automodel_tpu.parallel.mesh import MeshConfig, build_mesh
    from automodel_tpu.utils.compile_cache import enable_compile_cache
    from automodel_tpu.utils.compile_only import mosaic_calls, topology_devices

    enable_compile_cache()
    cfg = parse_args_and_load_config(rest)
    devices = list(topology_devices(args.topology))[: args.devices]
    ctx = build_mesh(MeshConfig.from_section(cfg.get("distributed")), devices=devices)
    print(json.dumps({
        "topology": args.topology, "device_kind": devices[0].device_kind,
        "mesh": {k: v for k, v in ctx.mesh.shape.items() if v > 1},
    }), flush=True)
    build = serve_programs if cfg.get("serving") is not None else train_programs
    for name, fn, fn_args in build(cfg, ctx):
        compiled = fn.lower(*fn_args).compile()
        mem = compiled.memory_analysis()
        sizes = {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        }
        print(json.dumps({
            "program": name, "mosaic_calls": mosaic_calls(compiled), **sizes,
            # donated arguments are reused for the outputs they alias
            "device_bytes": sizes["argument_bytes"] + sizes["temp_bytes"]
            + sizes["output_bytes"] - sizes["alias_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

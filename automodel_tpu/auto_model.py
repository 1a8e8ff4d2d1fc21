"""AutoModel entry points.

Parity: NeMoAutoModelForCausalLM.from_pretrained/from_config
(_transformers/auto_model.py:582,339,479) — drop-in HF-style constructors
that ALSO apply the model infrastructure (sharding plan, dtype policy,
checkpoint streaming). TPU-native flow (SURVEY.md §3.4 simplified by
single-controller):

    from_pretrained(path, mesh) =
        read HF config → registry → abstract init (eval_shape, no memory)
        → param shardings from the family plan → stream safetensors leaves
        → device_put each leaf to its target shard

so a 70B model never materializes unsharded anywhere.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Optional

import jax

from automodel_tpu.models.common.config import BackendConfig
from automodel_tpu.models.registry import resolve_architecture
from automodel_tpu.parallel.mesh import MeshContext
from automodel_tpu.parallel.plans import make_constrain, make_param_shardings, shard_params


@dataclasses.dataclass
class AutoModel:
    """A built model + its params + everything needed to train it."""

    model: Any
    params: Any
    adapter: Any
    mesh_ctx: Optional[MeshContext]
    # provenance for consolidated-HF export (config.json / tokenizer copies —
    # reference ConsolidatedHFAddon, checkpoint/addons.py)
    hf_config: Optional[dict] = None
    source_dir: Optional[str] = None

    @property
    def config(self):
        return self.model.config

    @property
    def constrain(self):
        return make_constrain(self.mesh_ctx)

    def __call__(self, params: Any, *args: Any, **kw: Any):
        return self.model(params, *args, constrain=self.constrain, **kw)


def _resolve_checkpoint_dir(path_or_id: str | Path) -> Path:
    """Local dir as-is; otherwise resolve a hub id to a local snapshot
    (cache-first; downloads weights too so the later safetensors read works)."""
    p = Path(path_or_id)
    if p.is_dir():
        return p
    from huggingface_hub import snapshot_download

    return Path(
        snapshot_download(
            str(path_or_id),
            allow_patterns=["*.safetensors", "*.safetensors.index.json", "config.json"],
        )
    )


def _read_hf_config(path: str | Path) -> dict:
    return json.loads((Path(path) / "config.json").read_text())


def from_config(
    hf_config: Any,
    mesh_ctx: Optional[MeshContext] = None,
    backend: BackendConfig | dict | None = None,
    seed: int = 0,
    abstract: bool = False,
) -> AutoModel:
    """Random-init (pretraining) constructor (reference: from_config,
    auto_model.py:479). Params materialize directly sharded via jit+out_shardings.

    ``abstract``: params are ``ShapeDtypeStruct``s carrying their shardings
    and nothing touches a device — what the compile-only pre-check
    (tools/compile_check.py) lowers against a TPU topology from a host
    that has no chip."""
    backend = _as_backend(backend, mesh_ctx)
    builder = resolve_architecture(hf_config)
    model, adapter = builder(hf_config, backend)
    _check_kernel_mesh(model.config, mesh_ctx, backend)
    model = _maybe_pp(model, mesh_ctx, backend)
    key = jax.random.key(seed)
    if mesh_ctx is None:
        params = jax.eval_shape(model.init, key) if abstract else model.init(key)
    else:
        shapes = jax.eval_shape(model.init, key)
        shardings = make_param_shardings(mesh_ctx, shapes, model.sharding_rules)
        if abstract:
            params = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                shapes, shardings,
            )
        else:
            params = jax.jit(model.init, out_shardings=shardings)(key)
    return AutoModel(
        model=model, params=params, adapter=adapter, mesh_ctx=mesh_ctx,
        hf_config=hf_config if isinstance(hf_config, dict) else None,
    )


def from_pretrained(
    pretrained_model_name_or_path: str,
    mesh_ctx: Optional[MeshContext] = None,
    backend: BackendConfig | dict | None = None,
    hf_config_overrides: Optional[dict] = None,
) -> AutoModel:
    """Load an HF checkpoint directory into a sharded native model
    (reference: from_pretrained, auto_model.py:339 + load_base_model).

    ``hf_config_overrides`` merges extra keys over the checkpoint's
    config.json — e.g. training_image_grid_thw for the VLM data path."""
    from automodel_tpu.checkpoint.hf_io import load_params_from_hf

    backend = _as_backend(backend, mesh_ctx)
    ckpt_dir = _resolve_checkpoint_dir(pretrained_model_name_or_path)
    hf_config = _read_hf_config(ckpt_dir)
    if hf_config_overrides:
        hf_config = {**hf_config, **dict(hf_config_overrides)}
    builder = resolve_architecture(hf_config)
    model, adapter = builder(hf_config, backend)
    _check_kernel_mesh(model.config, mesh_ctx, backend)
    model = _maybe_pp(model, mesh_ctx, backend)
    shardings = None
    if mesh_ctx is not None:
        abstract = jax.eval_shape(model.init, jax.random.key(0))
        shardings = make_param_shardings(mesh_ctx, abstract, model.sharding_rules)
    # variant-layout checkpoints (fused qkv/gate_up) present a canonical
    # view through the conversion mapping (reference conversion_mapping.py)
    from automodel_tpu.checkpoint.conversion_mapping import detect_remaps
    from automodel_tpu.checkpoint.hf_io import HFCheckpointReader

    reader = HFCheckpointReader(ckpt_dir)
    reader = detect_remaps(reader, hf_config) or reader
    params = load_params_from_hf(
        adapter,
        reader,
        shardings=shardings,
        dtype=_np_dtype(backend.param_dtype),
    )
    return AutoModel(
        model=model, params=params, adapter=adapter, mesh_ctx=mesh_ctx,
        hf_config=hf_config, source_dir=str(ckpt_dir),
    )


def _as_backend(
    backend: BackendConfig | dict | None, mesh_ctx: Optional[MeshContext] = None
) -> BackendConfig:
    if backend is None:
        backend = BackendConfig()
    elif not isinstance(backend, BackendConfig):
        backend = BackendConfig(**dict(backend))
    if mesh_ctx is not None:
        backend = dataclasses.replace(
            backend,
            platform=backend.platform or mesh_ctx.platform,
            mesh_ctx=mesh_ctx,
        )
    if backend.attn == "ring":
        if mesh_ctx is None:
            raise ValueError("attn='ring' (context parallel) requires a mesh")
        from automodel_tpu.parallel.cp import install_ring_backend

        install_ring_backend(mesh_ctx, zigzag=backend.cp_zigzag)
    return backend


def _check_kernel_mesh(
    cfg: Any, mesh_ctx: Optional[MeshContext], backend: BackendConfig
) -> None:
    """Refuse at setup what the Pallas kernels cannot run on this mesh.
    GSPMD cannot partition a Mosaic call, so on several devices each kernel
    runs per device inside a shard_map (ops/platform_check.kernel_axes) and
    needs whole shards; left alone, a bad combination surfaces as a
    lowering error at the first step. Only where a kernel would really run:
    off-TPU the same configs take XLA paths that GSPMD partitions freely."""
    if mesh_ctx is None or mesh_ctx.mesh.size == 1:
        return
    from automodel_tpu.ops.attention import _flash_eligible, flash_head_axes
    from automodel_tpu.ops.platform_check import is_tpu_platform

    heads = getattr(cfg, "num_heads", None)
    if (
        backend.attn == "flash"
        and heads is not None
        and _flash_eligible(backend.platform)
    ):
        flash_head_axes(mesh_ctx, heads, getattr(cfg, "num_kv_heads", heads))
    if (
        mesh_ctx.pp_size > 1
        and getattr(cfg, "moe", None) is not None
        and backend.experts in ("ragged", "ragged_fused", "a2a", "a2a_fused")
        and is_tpu_platform(backend.platform)
    ):
        # a pipeline stage is manual over pp (and ep, for the a2a exchange)
        # only, and the expert block inside it is handed no mesh to make
        # the remaining axes manual with. (Interpreted kernels are plain
        # XLA ops, so the CPU suite runs this combination.)
        raise ValueError(
            f"pp={mesh_ctx.pp_size} with experts: {backend.experts}: the "
            "Pallas grouped matmul cannot run inside a pipeline stage on "
            "TPU yet — use experts: gspmd with pp, or ep/dp/tp without pp"
        )


def _maybe_pp(model: Any, mesh_ctx: Optional[MeshContext], backend: BackendConfig):
    if mesh_ctx is None or mesh_ctx.pp_size == 1:
        return model
    from automodel_tpu.parallel.pp import maybe_pipeline

    mc = mesh_ctx.config
    return maybe_pipeline(
        model,
        mesh_ctx,
        backend.pp_microbatches,
        schedule=getattr(mc, "pp_schedule", "gpipe"),
        zb_queue=getattr(mc, "pp_zb_queue", None),
    )


def _np_dtype(name: str):
    import jax.numpy as jnp
    import numpy as np

    if name == "bfloat16":
        return jnp.bfloat16
    return np.dtype(name)

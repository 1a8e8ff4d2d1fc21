"""Shared "can this Pallas kernel run here, and how?" checks.

Used by ops.attention (splash flash), ops.grouped_matmul (MoE gmm) and
ops.paged_attention so the kernels can't drift in how they decide the mesh
is a TPU, or in how they meet a mesh of several devices: GSPMD cannot
partition a Mosaic custom call, so on such a mesh every kernel call sits in
a ``shard_map`` over the axes its operands are already sharded on
(``kernel_axes`` says whether one is needed, ``sharded_axes`` names the
axes and refuses a dimension they do not divide). Per-kernel interpret-mode
env switches stay with each kernel.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax


def is_tpu_platform(platform: str | None = None) -> bool:
    """`platform` (from BackendConfig.platform, resolved off the MeshContext)
    is authoritative when known — the process default device may belong to a
    DIFFERENT backend than the mesh the computation runs on (e.g. the 8-device
    CPU test mesh on a host that also has a chip). The default-device
    heuristic below is only the no-mesh fallback."""
    if platform is not None:
        return platform == "tpu"
    try:
        # honor an explicitly pinned default device (tests pin CPU while a
        # TPU is still visible in jax.devices()); jax also accepts platform
        # strings ('tpu') as jax_default_device
        dd = jax.config.jax_default_device
        if isinstance(dd, str):
            return dd == "tpu"
        dev = dd if dd is not None else jax.devices()[0]
        return getattr(dev, "platform", None) == "tpu"
    except Exception:
        return False


def kernel_axes(mesh_ctx: Any) -> Optional[frozenset]:
    """The mesh axes a Pallas call traced here must still be made manual
    over (by a ``shard_map`` around it), or None when the call can run as
    it stands. GSPMD refuses a Mosaic call wherever an axis is left to it:
    at top level that is any mesh of several devices; inside a region that
    is manual over some axes only (a pipeline stage: ``pp``), it is every
    remaining axis, whatever its size. None also when there is no mesh, or
    when every axis is already manual (ring attention, the a2a exchange)
    and the operands are per-device blocks."""
    if mesh_ctx is None:
        return None
    manual = frozenset(jax.sharding.get_abstract_mesh().manual_axes)
    if not manual and mesh_ctx.mesh.size == 1:
        return None
    return frozenset(mesh_ctx.mesh.axis_names) - manual or None


def kernel_shard_map(mesh_ctx: Any, fn, in_specs, out_specs):
    """``shard_map`` of a kernel block over the axes ``kernel_axes`` names
    (specs may name only those). check_vma=False: same stance as the ring
    (parallel/cp.py) — the region holds Pallas calls whose outputs carry no
    vma annotation."""
    axes = kernel_axes(mesh_ctx)
    nested = len(axes) < len(mesh_ctx.mesh.axis_names)
    return jax.shard_map(
        fn,
        # inside a manual region the context mesh is the only one allowed
        mesh=None if nested else mesh_ctx.mesh,
        in_specs=in_specs, out_specs=out_specs, axis_names=axes,
        check_vma=False,
    )


def sharded_axes(
    mesh_ctx: Any, logical: str, dims: Sequence[int], what: str
) -> Optional[tuple[str, ...]]:
    """The (size > 1, not yet manual) mesh axes a LOGICAL axis resolves to,
    for use in a kernel's shard_map spec — None when there are none. Every
    dimension in ``dims`` must be a whole number of shards: a kernel cannot
    take the ragged remainder GSPMD would pad, and dropping to an XLA path
    here would hide the kernel's absence, so this raises instead."""
    spec = mesh_ctx.resolve((logical,))
    names = spec[0] if len(spec) else None
    if names is None:
        return None
    manual = jax.sharding.get_abstract_mesh().manual_axes
    names = tuple(
        a for a in (names if isinstance(names, tuple) else (names,))
        if a not in manual
    )
    if not names:
        return None
    degree = math.prod(mesh_ctx.mesh.shape[a] for a in names)
    for d in dims:
        if d % degree:
            raise ValueError(
                f"{what} = {d} is not divisible by mesh axes {names} "
                f"(degree {degree}); the Pallas kernel needs whole shards — "
                "change the degree so it divides, or select the XLA path "
                "for this op in the backend config"
            )
    return names

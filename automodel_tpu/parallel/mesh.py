"""Device mesh construction and distributed context.

Capability parity with the reference mesh layer
(components/distributed/mesh.py:55-72, mesh_utils.py:46,190-228,302-334):
canonical axis names, dp inference from world size, flattened axis groupings
for param/loss sharding, and a MoE expert axis — but expressed TPU-natively.

TPU-first design (NOT a port):

* ONE `jax.sharding.Mesh` instead of the reference's separate 5-D dense mesh +
  3-D MoE mesh.  Axis order (outer→inner) = ``(pp, dp_replicate, dp_shard,
  ep, cp, tp)`` so that the most communication-intensive axes (tp, cp) map to
  the innermost / fastest ICI dimensions. The reference's derived submeshes
  (``dp``, ``dp_shard_cp``, ``dp_cp``, ``ep_shard``) become *logical axis
  groupings* — tuples of mesh axes inside a PartitionSpec — because GSPMD
  shards an array dim over the product of listed axes. No submesh objects,
  no DTensor placements.

* Expert parallelism is a factor of the data-shard product
  (``dp_shard_total = dp_shard * ep``), mirroring the reference invariant
  ``ep_shard = dp*cp/ep`` (mesh_utils.py:179-187): expert weights shard their
  expert dim on ``ep`` and their FSDP dim on ``(dp_shard, cp)``; dense params
  shard on ``(dp_shard, ep, cp)``; batches shard on
  ``(dp_replicate, dp_shard, ep)``.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


class MeshAxisName:
    """Canonical mesh axis names (reference: distributed/mesh.py:55-72)."""

    PP = "pp"
    DP_REPLICATE = "dp_replicate"
    DP_SHARD = "dp_shard"
    EP = "ep"
    CP = "cp"
    TP = "tp"

    ALL = (PP, DP_REPLICATE, DP_SHARD, EP, CP, TP)


# Logical axis → physical mesh axes. These are the reference's flattened
# submeshes (mesh_utils.py:210-228) re-expressed as PartitionSpec groupings.
LOGICAL_AXIS_RULES: dict[str, tuple[str, ...]] = {
    "batch": (MeshAxisName.DP_REPLICATE, MeshAxisName.DP_SHARD, MeshAxisName.EP),
    # param sharding dim ("fsdp"): the reference's dp_shard_cp submesh.
    "fsdp": (MeshAxisName.DP_SHARD, MeshAxisName.EP, MeshAxisName.CP),
    # loss all-reduce group: the reference's dp_cp submesh.
    "loss_dp": (
        MeshAxisName.DP_REPLICATE,
        MeshAxisName.DP_SHARD,
        MeshAxisName.EP,
        MeshAxisName.CP,
    ),
    "seq": (MeshAxisName.CP,),
    "tensor": (MeshAxisName.TP,),
    "expert": (MeshAxisName.EP,),
    # the reference's ep_shard: FSDP dim for expert weights.
    "expert_fsdp": (MeshAxisName.DP_SHARD, MeshAxisName.CP),
    # batch dim INSIDE the expert-parallel region: ep has moved to the expert
    # dim (the dispatch all-to-all), so tokens shard over the remaining data
    # axes only.
    "expert_batch": (MeshAxisName.DP_REPLICATE, MeshAxisName.DP_SHARD),
    "stage": (MeshAxisName.PP,),
    "vocab": (MeshAxisName.TP,),
    None: (),
}


@dataclasses.dataclass
class MeshConfig:
    """Parallelism degrees. -1 for dp_shard means 'infer from world size'
    (reference: mesh_utils.py:160-168).

    ``dcn`` (multi-slice only): per-axis degrees laid across the DATA-CENTER
    NETWORK (between ICI slices) instead of ICI; the per-axis ICI degree is
    axis_total / dcn[axis]. Default (empty) lays pp/dp_replicate/dp_shard
    across slices automatically; ep/tp/cp never default over DCN (latency-
    bound collectives) and require an explicit entry here (reference hybrid
    topology note, init_utils.py:90-163; jax
    mesh_utils.create_hybrid_device_mesh)."""

    dp_replicate: int = 1
    dp_shard: int = -1  # total data-shard degree INCLUDING ep (dp_shard_total)
    tp: int = 1
    cp: int = 1
    pp: int = 1
    ep: int = 1
    dcn: Optional[dict] = None
    # pipeline schedule (pp > 1): 'gpipe' = AD-transposed wavefront;
    # 'zero_bubble' = B/W-split backward with deferred weight-grads
    # (parallel/zero_bubble.py) — bubble 3(pp-1)/(4M+3(pp-1)) vs GPipe's
    # (pp-1)/(M+pp-1). pp_zb_queue bounds the weight-grad deferral queue
    # (microbatches of stash held live; None = defer all — max speedup,
    # ~no-remat activation memory for one stage × M microbatches).
    pp_schedule: str = "gpipe"
    pp_zb_queue: Optional[int] = None

    @classmethod
    def from_section(cls, dist: Any) -> "MeshConfig":
        """The ``distributed:`` YAML section (a ConfigNode or dict; absent
        keys take the defaults above) — the one reading the train recipes,
        the generate/serve CLIs and tools/compile_check.py share."""
        dist = dist or {}
        return cls(**{
            f.name: dist.get(f.name)
            for f in dataclasses.fields(cls)
            if f.name != "dcn" and dist.get(f.name) is not None
        })

    def validate(self, world_size: int) -> "MeshConfig":
        cfg = dataclasses.replace(self)
        known = cfg.dp_replicate * cfg.tp * cfg.cp * cfg.pp
        if cfg.dp_shard == -1:
            if world_size % known != 0:
                raise ValueError(
                    f"world_size {world_size} not divisible by dp_replicate*tp*cp*pp={known}"
                )
            cfg.dp_shard = world_size // known
        total = known * cfg.dp_shard
        if total != world_size:
            raise ValueError(
                f"Mesh degrees {cfg} product {total} != world size {world_size}"
            )
        if cfg.ep < 1 or cfg.dp_shard % cfg.ep != 0:
            raise ValueError(
                f"ep={cfg.ep} must divide dp_shard_total={cfg.dp_shard} "
                f"(reference invariant ep_shard = dp*cp/ep, mesh_utils.py:179-187)"
            )
        if cfg.pp_schedule not in ("gpipe", "zero_bubble"):
            raise ValueError(
                f"pp_schedule={cfg.pp_schedule!r} must be gpipe|zero_bubble"
            )
        if cfg.pp_zb_queue is not None and cfg.pp_zb_queue < 1:
            raise ValueError(f"pp_zb_queue={cfg.pp_zb_queue} must be >= 1")
        return cfg


class MeshContext:
    """Single source of truth for distributed state (reference: mesh.py:79).

    Wraps the jax Mesh plus the logical-axis mapping; all sharding rules in
    the framework go through :meth:`resolve` / :meth:`sharding` so that a
    logical spec like ``("fsdp", "tensor")`` is portable across mesh shapes.
    """

    def __init__(self, mesh: Mesh, config: MeshConfig):
        self.mesh = mesh
        self.config = config
        self.rules = dict(LOGICAL_AXIS_RULES)

    # -- sizes --------------------------------------------------------------
    @property
    def world_size(self) -> int:
        return self.mesh.size

    def size(self, axis: str) -> int:
        return self.mesh.shape[axis]

    @property
    def dp_size(self) -> int:
        return (
            self.size(MeshAxisName.DP_REPLICATE)
            * self.size(MeshAxisName.DP_SHARD)
            * self.size(MeshAxisName.EP)
        )

    @property
    def dp_cp_size(self) -> int:
        return self.dp_size * self.size(MeshAxisName.CP)

    @property
    def tp_size(self) -> int:
        return self.size(MeshAxisName.TP)

    @property
    def cp_size(self) -> int:
        return self.size(MeshAxisName.CP)

    @property
    def pp_size(self) -> int:
        return self.size(MeshAxisName.PP)

    @property
    def ep_size(self) -> int:
        return self.size(MeshAxisName.EP)

    @property
    def platform(self) -> str:
        """Platform of the devices computation actually runs on ('tpu',
        'cpu', ...). Kernel eligibility must key off THIS, not the process
        default device — a CPU mesh can coexist with a visible TPU backend."""
        return self.mesh.devices.flat[0].platform

    # -- sharding -----------------------------------------------------------
    def resolve(self, logical: Sequence[Any] | None) -> P:
        """Map a logical spec (tuple of logical axis names / None / tuples of
        logical names) to a physical PartitionSpec, dropping size-1 axes."""
        if logical is None:
            return P()
        phys: list[Any] = []
        for dim in logical:
            names: list[str] = []
            for lg in (dim if isinstance(dim, (tuple, list)) else (dim,)):
                if lg is None:
                    continue
                for ax in self.rules[lg]:
                    if self.mesh.shape[ax] > 1:
                        names.append(ax)
            if not names:
                phys.append(None)
            elif len(names) == 1:
                phys.append(names[0])
            else:
                phys.append(tuple(names))
        while phys and phys[-1] is None:
            phys.pop()
        return P(*phys)

    def sharding(self, *logical: Any) -> NamedSharding:
        return NamedSharding(self.mesh, self.resolve(logical))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def __repr__(self) -> str:
        return f"MeshContext(shape={dict(self.mesh.shape)})"


def hybrid_mesh_shapes(
    config: MeshConfig, world_size: int, n_slices: int
) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Split the mesh shape into (ici_shape, dcn_shape) for
    `mesh_utils.create_hybrid_device_mesh` on a multi-host DCN×ICI topology.

    ``config.dcn`` gives per-axis DCN degrees; their product must equal the
    DCN granule count (number of ICI slices) and each must divide its axis.
    Default: greedily lay the OUTER axes (pp, dp_replicate, dp_shard) across
    slices in order — the axes whose collectives amortize over DCN — and
    refuse to split tp/cp/ep implicitly (latency-bound collectives: tp/cp
    all-reduces and the MoE token all-to-all; declare MeshConfig.dcn
    explicitly to override)."""
    cfg = config.validate(world_size)
    axes = {
        "pp": cfg.pp,
        "dp_replicate": cfg.dp_replicate,
        "dp_shard": cfg.dp_shard // cfg.ep,
        "ep": cfg.ep,
        "cp": cfg.cp,
        "tp": cfg.tp,
    }
    dcn = dict(cfg.dcn or {})
    if dcn:
        unknown = set(dcn) - set(axes)
        if unknown:
            raise ValueError(f"dcn axes {sorted(unknown)} not mesh axes {list(axes)}")
        prod = int(np.prod(list(dcn.values())))
        if prod != n_slices:
            raise ValueError(
                f"dcn degrees {dcn} product {prod} != DCN granule (slice) count "
                f"{n_slices}"
            )
        for a, d in dcn.items():
            if d < 1 or axes[a] % d:
                raise ValueError(f"dcn[{a}]={d} must divide axis degree {axes[a]}")
    else:
        rem = n_slices
        for a in ("pp", "dp_replicate", "dp_shard"):
            g = math.gcd(axes[a], rem)
            if g > 1:
                dcn[a] = g
                rem //= g
        if rem != 1:
            raise ValueError(
                f"cannot lay {n_slices} DCN granules across "
                f"{ {a: axes[a] for a in ('pp', 'dp_replicate', 'dp_shard')} } "
                "without splitting ep/tp/cp over DCN (latency-bound "
                "collectives); set MeshConfig.dcn explicitly to opt in"
            )
    dcn_shape = tuple(dcn.get(a, 1) for a in axes)
    ici_shape = tuple(axes[a] // dcn.get(a, 1) for a in axes)
    return ici_shape, dcn_shape


def build_mesh(
    config: MeshConfig | None = None,
    devices: Sequence[jax.Device] | None = None,
    **degrees: int,
) -> MeshContext:
    """Build the device mesh (reference: create_device_mesh, mesh_utils.py:46).

    The mesh axis ``dp_shard`` holds ``dp_shard_total // ep`` so the flat
    product over ``(dp_shard, ep)`` equals the configured data-shard degree.
    Multi-host (jax.process_count() > 1 over the given devices) goes through
    `create_hybrid_device_mesh` so DCN-crossing axes are the ones declared
    (or defaulted) by :func:`hybrid_mesh_shapes`.
    """
    if config is None:
        config = MeshConfig(**degrees)
    devices = list(devices if devices is not None else jax.devices())
    config = config.validate(len(devices))
    shape = (
        config.pp,
        config.dp_replicate,
        config.dp_shard // config.ep,
        config.ep,
        config.cp,
        config.tp,
    )
    # DCN granules are ICI SLICES, not processes: a multi-host single-slice
    # pod (e.g. v4-32, ICI spans hosts) builds a plain device mesh; only
    # genuinely DCN-connected multi-slice topologies go hybrid. Devices
    # without slice_index (CPU multi-process) count as one slice.
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    n_slices = 1 if None in slice_ids else len(slice_ids)
    from jax.experimental import mesh_utils as jmu

    if n_slices > 1:
        ici_shape, dcn_shape = hybrid_mesh_shapes(config, len(devices), n_slices)
        dev_array = jmu.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices
        )
        logger.info("Hybrid DCN×ICI mesh: ici=%s dcn=%s", ici_shape, dcn_shape)
    else:
        try:
            dev_array = jmu.create_device_mesh(shape, devices=devices)
        except (ValueError, NotImplementedError, AssertionError):
            if devices[0].platform == "tpu":
                # flat device order on a torus would put tp/cp neighbours
                # on far chips: a mesh the topology cannot hold is an error
                raise
            # host platforms have no torus to assign
            dev_array = np.array(devices).reshape(shape)
    mesh = Mesh(dev_array.reshape(shape), MeshAxisName.ALL)
    logger.info("Built mesh %s", dict(mesh.shape))
    return MeshContext(mesh, config)


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    **kwargs: Any,
) -> None:
    """Multi-host init (reference: init_utils.py:90 NCCL init → here
    `jax.distributed.initialize` over the TPU runtime; single-process is a
    no-op because JAX is single-controller).

    Args fall back to the env the launchers render (launcher/slurm.py:24-29,
    launcher/k8s.py): JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
    JAX_PROCESS_ID. On TPU pods with none of these set,
    `jax.distributed.initialize()` discovers the topology itself — we only
    call it when a multi-host env is actually declared. Validated before
    dialing so a bad rendezvous fails fast with a config error instead of a
    hang at the coordinator timeout."""
    import os

    env = os.environ
    coordinator_address = coordinator_address or env.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and env.get("JAX_NUM_PROCESSES"):
        num_processes = int(env["JAX_NUM_PROCESSES"])
    if process_id is None and env.get("JAX_PROCESS_ID"):
        process_id = int(env["JAX_PROCESS_ID"])
    if not coordinator_address:
        return  # single process / TPU-pod auto-discovery happens lazily
    if num_processes is None or process_id is None:
        raise ValueError(
            "JAX_COORDINATOR_ADDRESS is set but JAX_NUM_PROCESSES / "
            "JAX_PROCESS_ID are not — the launchers export all three "
            "(launcher/slurm.py, launcher/k8s.py)"
        )
    if num_processes < 1 or not (0 <= process_id < num_processes):
        raise ValueError(
            f"invalid process topology: process_id={process_id} "
            f"num_processes={num_processes}"
        )
    if ":" not in coordinator_address:
        raise ValueError(
            f"coordinator_address {coordinator_address!r} must be host:port"
        )
    logger.info(
        "jax.distributed.initialize(%s, num_processes=%d, process_id=%d)",
        coordinator_address, num_processes, process_id,
    )
    # timed init (resilience/timed_sync.py): a host that never shows up at
    # the rendezvous — bad DNS, a pod that crashed before python started —
    # must surface as a diagnosed SyncTimeout naming the sync point, not an
    # indefinite block inside the coordinator handshake.
    # AUTOMODEL_INIT_TIMEOUT_S bounds the wait (default 600s, generous for
    # slow pod scheduling).
    from automodel_tpu.resilience.timed_sync import timed_call

    timeout_s = float(env.get("AUTOMODEL_INIT_TIMEOUT_S", "600"))
    timed_call(
        lambda: jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **kwargs,
        ),
        name="distributed_init",
        timeout_s=timeout_s,
    )

"""Compile-only TPU client: XLA:TPU and Mosaic on a host that has no chip.

With libtpu installed, ``jax.experimental.topologies`` hands out the devices
of a named TPU topology even under ``JAX_PLATFORMS=cpu``. Nothing can be
placed on them or run, but ``jax.jit(f).lower(<ShapeDtypeStructs sharded on
a Mesh of those devices>).compile()`` runs the real TPU compiler — so a
program's lowering errors, its memory analysis and its Mosaic custom-call
count are known before any chip time is spent (tools/compile_check.py,
tests/test_tpu_lowering.py).
"""

from __future__ import annotations

import functools
import os
from typing import Any

_MOSAIC_TARGET = "tpu_custom_call"


@functools.lru_cache(maxsize=None)
def topology_devices(topology: str = "v5e:2x2") -> tuple:
    """The devices of ``topology`` (one v5e host by default: 4 chips, 2x2)."""
    from jax.experimental import topologies

    # libtpu guards the chip with a lockfile that lets one process load it;
    # a compile-only client takes no chip, and several may run at once
    # (xdist workers, a pre-check beside a test run)
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

    return tuple(
        topologies.get_topology_desc(
            platform="tpu", topology_name=topology
        ).devices
    )


def mosaic_calls(program: Any) -> int:
    """Mosaic custom calls in a ``Lowered``/``Compiled`` program (or its
    text): each Pallas kernel that lowered for the TPU is one. Zero means
    every kernel call site took an XLA path — on a chip, that a fallback
    hid the kernel. A call inside a ``scan`` body counts once."""
    text = program if isinstance(program, str) else program.as_text()
    return text.count(_MOSAIC_TARGET)

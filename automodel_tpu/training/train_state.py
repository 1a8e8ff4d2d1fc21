"""Train state pytree."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P


@struct.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray  # int32 scalar

    @classmethod
    def create(cls, params: Any, opt_state: Any) -> "TrainState":
        """The step counter starts where the step program will return it:
        replicated on the params' mesh. Left as a bare ``jnp.zeros`` it is
        an uncommitted single-device array going in and a committed
        mesh-replicated one coming out, and that difference alone makes
        the second call of the train step trace and compile all over."""
        step = jnp.zeros((), jnp.int32)
        leaves = jax.tree.leaves(params)
        sharding = getattr(leaves[0], "sharding", None) if leaves else None
        if isinstance(sharding, NamedSharding):
            step = jax.device_put(step, NamedSharding(sharding.mesh, P()))
        return cls(params=params, opt_state=opt_state, step=step)

"""Capability probes for environment-dependent tier-1 tests.

Some tests exercise functionality a jaxlib/optax build may lack (a
multiprocess CPU backend, optax.contrib.muon). Letting them FAIL buries
real regressions in a wall of known noise; skipping them wholesale would
mask a real regression the day the environment gains the capability.

The contract here: each probe reproduces the SPECIFIC minimal operation
the gated tests depend on, once per session (cached), and the skip fires
only when that exact probe fails — with the probe's error as the skip
reason. On an environment where the probe passes, the tests run normally
and a regression in the feature fails loudly again.

Usage::

    from capabilities import skip_unless
    @skip_unless("muon")
    def test_muon_...():
"""

from __future__ import annotations

import functools
import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest


@functools.lru_cache(maxsize=None)
def probe(name: str) -> tuple[bool, str]:
    """→ (capability available, reason when not)."""
    return _PROBES[name]()


def skip_unless(name: str):
    """Decorator: skip the test when the named capability probe fails.

    The probe runs LAZILY at test call time (cached per session), not at
    decoration: collection (`--collect-only`, `-k something_else`) must not
    pay for the 2-subprocess multiprocess probe when the gated tests never
    run."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            ok, reason = probe(name)
            if not ok:
                pytest.skip(f"capability {name!r} unavailable: {reason}")
            return fn(*args, **kwargs)

        return wrapper

    return deco


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------


_MP_PROBE_SCRIPT = textwrap.dedent("""\
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.distributed.initialize(
        coordinator_address=sys.argv[1], num_processes=2,
        process_id=int(sys.argv[2]),
    )
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    x = jax.device_put(
        jnp.ones((4,), jnp.float32),
        NamedSharding(mesh, P("dp")),
    )
    s = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(x)
    # fetching forces the cross-process computation to actually run
    assert float(jax.device_get(s.addressable_shards[0].data)) == 4.0
    print("MP_PROBE_OK")
""")


def _multiprocess_cpu() -> tuple[bool, str]:
    """Two real processes, one global 4-device CPU mesh, one jitted global
    reduction — the minimal core of test_multiprocess. This build's CPU
    backend answers 'Multiprocess computations aren't implemented'."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    for k in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_COORDINATOR_ADDRESS",
              "JAX_NUM_PROCESSES", "JAX_PROCESS_ID"):
        env.pop(k, None)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MP_PROBE_SCRIPT,
             f"127.0.0.1:{port}", str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=120)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        return False, "2-process rendezvous probe timed out"
    for rc, out, err in outs:
        if rc != 0 or "MP_PROBE_OK" not in out:
            tail = err.strip().splitlines()[-1] if err.strip() else f"rc={rc}"
            return False, tail[:160]
    return True, ""


def _muon() -> tuple[bool, str]:
    """optax.contrib.muon: the exact symbol optim/builders.py dispatches to."""
    import optax

    if not hasattr(optax.contrib, "muon"):
        return False, (
            f"optax {getattr(optax, '__version__', '?')} has no contrib.muon"
        )
    return True, ""


_PROBES = {
    "multiprocess_cpu": _multiprocess_cpu,
    "muon": _muon,
}


if __name__ == "__main__":  # manual audit: python tests/capabilities.py
    print(json.dumps(
        {name: {"ok": probe(name)[0], "reason": probe(name)[1]}
         for name in _PROBES},
        indent=2,
    ))

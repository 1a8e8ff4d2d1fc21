"""Single-process CLI worker for the resilience subprocess tests
(tests/test_resilience.py): runs `automodel_tpu.cli.app.main` on a tiny
CPU config so the parent can deliver a REAL SIGTERM and assert the
emergency-checkpoint + requeue-exit-code contract, and then restart it to
prove auto-resume picks up the committed emergency checkpoint. The platform
is pinned to cpu BEFORE jax initializes, as in multiprocess_worker.py."""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from automodel_tpu.cli.app import main

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))

import os
import re

# Keep single-device defaults for smoke tests/benches (the dry-run sets its
# own 512-device override in its own process).  Cap CPU threads for CI noise.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402

jax.config.update("jax_enable_x64", False)


# ---- shared scaffolding for the sharded suites (test_engine_sharded.py,
# ---- test_flat_state.py): one copy so the skip guard, the mesh, and the
# ---- zero-collective assertion's op list cannot drift apart.

NDEV = 8

multidevice = pytest.mark.skipif(
    jax.device_count() < NDEV,
    reason=f"needs {NDEV} devices (run under "
           f"XLA_FLAGS=--xla_force_host_platform_device_count={NDEV})")

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")


def collective_counts(hlo: str) -> dict:
    return {op: len(re.findall(op + r"\(", hlo)) for op in COLLECTIVE_OPS}


def p_mesh():
    """The NDEV-device 1-axis ("p") mesh every sharded suite runs on."""
    return make_mesh((NDEV,), ("p",))

"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Real TPU hardware is single-chip in this environment; multi-chip sharding is
validated on forced host-platform devices (see also __graft_entry__.py's
dryrun_multichip, which the driver runs the same way).

Must run before the first `import jax` anywhere in the test process.
"""

import os
import sys
import pathlib

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import dds_tpu  # noqa: E402,F401 — places the compile cache; precedes jax

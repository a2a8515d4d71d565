"""Test harness config: run everything on a virtual 8-device CPU mesh.

SURVEY.md §4.5: multi-chip logic is tested without a cluster via
``--xla_force_host_platform_device_count=8``. The platform is pinned
to cpu BEFORE any backend initializes, whatever the environment
selects, so the suite is hermetic, fast, and 8-way — and never reaches
for a chip some other process may hold.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

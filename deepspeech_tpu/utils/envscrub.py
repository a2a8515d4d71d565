"""Scrubbed-environment builder for forced-CPU subprocesses.

Shared by ``__graft_entry__.dryrun_multichip``,
``tools/multihost_dryrun.py`` and ``tools/rehearsal.py``: their
children must bind the CPU platform with N virtual devices whatever
the parent's environment selects (a chip belongs to one process, so a
CPU child must never reach for it). Deliberately imports nothing heavy
— it must be safe to use from a process that has not (and must not)
initialize jax.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

# The one JAX variable a CPU child keeps: where its compile cache lives.
_KEEP = ("JAX_COMPILATION_CACHE_DIR",)


def scrubbed_cpu_env(repo_root: str, n_devices: int,
                     base: Optional[Dict[str, str]] = None
                     ) -> Dict[str, str]:
    """Environment for a child process pinned to N virtual CPU devices.

    Drops every JAX/XLA/TPU env var except the compile-cache location,
    prepends ``repo_root`` to PYTHONPATH so the package stays
    importable, and forces the CPU platform.
    """
    base = dict(os.environ if base is None else base)
    env = {k: v for k, v in base.items()
           if k in _KEEP
           or not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    kept = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([repo_root] + kept)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    return env

"""Child-process environment for multi-device CPU-mesh smokes.

Tests that need several CPU devices spawn a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must be
set before jax imports).  The child env threads platform selection
(``JAX_PLATFORMS``) through, so the child runs on the same backend as its
parent.  Only for CPU meshes: on an accelerator host the parent already
holds the chip, and a JAX child cannot reach it.
"""

from __future__ import annotations

import os

#: platform/temp vars that must survive into jax child processes
PASS_THROUGH = ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "TMPDIR")


def child_env(pythonpath: str = "src") -> dict[str, str]:
    """Minimal env for a jax subprocess run from the repo root."""
    env = {
        "PYTHONPATH": pythonpath,
        "PATH": "/usr/bin:/bin",
        "HOME": os.environ.get("HOME", "/root"),
    }
    for var in PASS_THROUGH:
        if var in os.environ:
            env[var] = os.environ[var]
    return env

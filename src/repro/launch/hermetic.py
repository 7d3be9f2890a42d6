"""Deterministic environments for jax-spawning subprocesses — CPU only.

For test and CPU-benchmark subprocesses only: tests and the CPU benchmarks
launch workers with a minimal env so XLA flags (device counts must be set
before jax initializes) and stray user configuration can't leak in, and the
platform defaults to ``JAX_PLATFORMS=cpu``.  Nothing on the chip path spawns a
JAX child: a chip belongs to one process, and a parent that has touched JAX
holds it (``chip_smoke.py`` runs everything in one process).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Iterable


def subprocess_env(repo_root: Path, *, extra_pythonpath: Iterable[str] = (),
                   **overrides: str) -> dict:
    """Minimal env for a jax subprocess: repo sources + pinned platform."""
    pythonpath = ":".join([str(Path(repo_root) / "src"),
                           *map(str, extra_pythonpath)])
    env = {
        "PYTHONPATH": pythonpath,
        "PATH": "/usr/bin:/bin",
        "HOME": os.environ.get("HOME", "/root"),
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
    }
    env.update(overrides)
    return env

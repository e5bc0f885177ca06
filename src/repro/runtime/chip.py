"""Process-level accelerator set-up shared by the entry points.

Two rules hold for every process that drives a TPU:

* **Compile cache.**  A cold compile of one sweep chunk step takes tens of
  seconds for the chip, so entry points (scripts, ``benchmarks/run.py``,
  ``examples/*.py``, ``repro.launch.*``, the ``repro.serve.worker``
  daemon) call :func:`enable_compile_cache` before their first jit.  It is
  never called while a module is imported.
* **One process per chip.**  A chip belongs to the process that first
  touched it; a spawned child that needs it fails, hangs, or silently
  initializes on the CPU.  Code that spawns JAX children calls
  :func:`refuse_spawn_on_tpu` first.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The checkout's own cache directory (gitignored).  A fixed path, never a
#: temporary, per-PID or timestamped one, so a repeat run finds its entries.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache is ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def refuse_spawn_on_tpu(what: str) -> None:
    """Raise ``RuntimeError`` when this process's JAX backend is a TPU.

    ``what`` names the caller in the message.  CPU processes (tests, CPU
    hosts) pass through unchanged.
    """
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} spawns child processes that would need the TPU, but a "
            "chip belongs to one process and this one holds it; use an "
            "in-process pool (mode='thread' or 'device') or run the "
            "workers on hosts of their own")

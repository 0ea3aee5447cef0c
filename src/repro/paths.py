"""Where the program keeps what it writes at run time: inside the checkout.

Nothing here reads or writes around the checkout (no `~/.cache`, no
temporary names), so a run is built only from what git commits plus what
the run itself makes. Both locations below are listed in `.gitignore`.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# JAX's persistent compilation cache, unless JAX_COMPILATION_CACHE_DIR
# places it elsewhere. A fixed path: a directory named per run (a pid, a
# time, a temporary name) would never find the previous run's entries.
COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

# default kernel tuning cache (REPRO_TUNING_CACHE overrides)
TUNING_CACHE_PATH = os.path.join(REPO_ROOT, "experiments",
                                 "kernel_tuning.json")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it at import
    and nothing is overridden; otherwise the cache goes to
    `COMPILE_CACHE_DIR`. Call before the first compile.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


__all__ = ["REPO_ROOT", "COMPILE_CACHE_DIR", "TUNING_CACHE_PATH",
           "enable_compile_cache"]

"""Where this checkout keeps its persistent XLA compile cache.

The directory is part of the cache key, so it must be the same path on
every run: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(nothing in code then sets another), else ONE fixed directory inside the
checkout — never a temp name, a pid or a timestamp. ``chip_smoke.py``,
``bench.py``, the chip-facing scripts and the local/native sandbox backends
all resolve it here and hand it to the processes they start through that
same environment variable, so a single-use sandbox pays each unique
program's compile once per checkout instead of once per request.
``APP_JAX_CACHE_DIR`` keeps its meaning: the operator's override for
sandboxes, which still loses to the environment variable.

Stdlib only: the parents that resolve this never import jax.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def jax_cache_dir(environ: Mapping[str, str] | None = None) -> str:
    """The compile-cache directory for a process started from this
    checkout: the environment's, else the fixed in-checkout path."""
    env = os.environ if environ is None else environ
    return env.get(ENV_VAR) or CHECKOUT_CACHE_DIR

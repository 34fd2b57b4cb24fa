"""Persistent compilation cache for the entry points.

``chip_smoke.py``, ``repro.launch.train`` and ``repro.launch.serve`` call
:func:`enable_compile_cache` once, before their first compile. Importing
``repro`` turns nothing on.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

#: the checkout this package was imported from (``<checkout>/src/repro/launch``)
CHECKOUT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache lives at the fixed ``<checkout>/
    .jax_cache`` (listed in ``.gitignore``), so each run of this checkout
    finds what the previous runs compiled.

    The cache's key includes the programs' metadata: the layer scopes that
    ``repro.obs`` reads live there, and a key without it would hand this
    code a program compiled from another version of it, with other scopes.
    Source files enter the metadata relative to the checkout, so that the
    key does not depend on where the checkout lies.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT)) + "/")
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

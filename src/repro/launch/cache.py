"""Where JAX's persistent compilation cache lives.

Entry points (``repro.launch.serve``, ``chip_smoke.py``) call
:func:`use_compile_cache` once at start-up; nothing sets it at import,
and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: the cache directory inside a checkout, when the environment names none
CACHE_DIRNAME = ".jax_cache"


def use_compile_cache(checkout: Path) -> Optional[str]:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache sits at one fixed path in
    the caller's ``checkout`` (the directory is part of what lets a later
    run find its entries, so it never varies from run to run).  A
    ``checkout`` that is not one — an installed package — leaves the
    cache off (returns None) rather than write beside site-packages."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    # a checkout has its pyproject.toml beside src/repro; an installed
    # package has neither
    if not ((checkout / "pyproject.toml").is_file()
            and (checkout / "src" / "repro").is_dir()):
        return None
    path = str(checkout / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path

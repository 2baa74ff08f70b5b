"""Persistent XLA compilation cache (cold-start mitigation, VERDICT r2 #2).

The serving cold-start is mostly XLA compile time: a cold ``--warmup`` at
7B builds 19 executables in ~30 s on the v5e (chip_smoke.py, PR 21). The
reference never pays this (torch eager + HF generate), but it also never
amortizes — every process re-runs cuDNN autotune. With the cache on, the
second process deserializes executables instead of recompiling, which is
what makes the 50 ms streaming story (reference README.md:119,
scripts/stream_demo.py) hold across restarts.

Where it lives is decided from outside: ``JAX_COMPILATION_CACHE_DIR``, when
set, is JAX's own variable and the program sets no directory in code.
Unset, the cache goes to ``.xla_cache/`` at the root of this checkout —
one fixed, git-ignored path (the path is part of the cache key, so a
directory that moves never hits).

Call ``enable_compile_cache()`` before the first jit executes (any later
call still helps subsequent compiles). It initialises no backend: the
process-fleet coordinator calls it and must stay off the chip.
"""

from __future__ import annotations

import os
from typing import Optional

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache",
)


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for a process started
    for the chip. Returns the directory in use, or None when there is none."""
    import jax

    from eventgpt_tpu.utils.platform import chip_requested

    if not chip_requested():
        # A run that asked for the CPU (tests, rehearsals) is left as JAX
        # configured it: XLA:CPU entries embed host machine features
        # (avx512 etc.) and reload with SIGILL warnings on heterogeneous
        # hosts, CPU compiles are fast, and tier-1 must not grow the tree
        # the chip tool has to copy.
        return jax.config.jax_compilation_cache_dir
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    # Default thresholds skip small/fast compiles; serving wants everything
    # cached — the CLIP encode alone is dozens of small jits around the big
    # ones, and the per-process budget they cost is the point of this file.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir

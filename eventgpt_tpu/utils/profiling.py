"""Profiling hooks: jax.profiler traces + fenced wall-clock timing.

The reference has no tracing/profiling at all (SURVEY.md §5 — Timer.h is an
unshipped external, nvtx a dep only). These are the TPU equivalents:

  * ``profile_trace(logdir)`` — context manager around ``jax.profiler`` so a
    training/inference region can be inspected in TensorBoard/XProf.
  * ``timed(fn)`` — wall-clock timing that ends in ``block_until_ready``:
    dispatch is asynchronous, so a clock read without it times the enqueue.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Tuple


@contextlib.contextmanager
def profile_trace(logdir: str):
    """Capture a jax.profiler trace for the enclosed region."""
    import jax

    jax.profiler.start_trace(logdir, create_perfetto_link=False)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def timed(fn: Callable, *args, iters: int = 10, warmup: int = 2) -> Tuple[float, Any]:
    """(seconds_per_iter, last_output) with compile excluded and
    ``block_until_ready`` after the timed loop."""
    import jax

    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out

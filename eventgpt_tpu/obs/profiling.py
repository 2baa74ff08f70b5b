"""``jax.profiler`` hooks: arming, step annotations, an on-demand capture.

The ARMED-GATED layer the runtime wires through, so un-profiled
serving/training pays one module-global check per step:

  * ``armed()``: a profile destination exists (``configure(dir)``, the
    ``--profile_dir`` flags) or a ``capture`` window is open. While it
    holds, every ``obs.trace.span`` also holds a
    ``jax.profiler.TraceAnnotation`` named ``<cat>.<name>``, so a capture
    shows the program's spans as host events on the clock of the
    device's operations. That is the only way a host region is named on
    a profile: there is no second probe.
  * ``step_annotation(n)``: ``jax.profiler.StepTraceAnnotation`` when
    armed, else a no-op; the trainer wraps each micro-step, which gives
    XProf/TensorBoard its per-step grouping.
  * ``capture(seconds, logdir)``: the ``POST /profile {"seconds": N}``
    window: start a ``jax.profiler`` trace, arm annotations for the
    window, sleep, stop. One capture at a time (``CaptureBusyError``).
  * ``start_trace``/``stop_trace``: manual bracket for the trainer's
    ``--profile_dir`` step window.

Arming is process-wide (``configure(dir)``) because the profiler itself
is process-wide; annotations are cheap-but-not-free (~us each), so they
stay off unless a profile destination exists or a capture is running.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional


class CaptureBusyError(RuntimeError):
    """A profile capture is already running (the profiler is process-
    global; the HTTP layer maps this to 409)."""


_lock = threading.Lock()
_profile_dir: Optional[str] = None   # configured destination (arms annotations)
_capturing = False                   # a start_trace window is open
_armed_depth = 0                     # capture() arms annotations temporarily


def configure(profile_dir: Optional[str]) -> None:
    """Set the default capture destination; a non-empty dir arms the
    step/trace annotations permanently (the --profile_dir flags)."""
    global _profile_dir
    _profile_dir = profile_dir or None


def armed() -> bool:
    return _profile_dir is not None or _armed_depth > 0


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def step_annotation(step_num: int, name: str = "step"):
    """``jax.profiler.StepTraceAnnotation`` when armed, else a no-op —
    gives XProf/TensorBoard its per-step grouping."""
    if not armed():
        return _NULL
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


def start_trace(logdir: Optional[str] = None) -> str:
    """Open a profiler trace (one at a time, process-wide). Returns the
    logdir actually used."""
    global _capturing
    import jax

    with _lock:
        if _capturing:
            raise CaptureBusyError("a profile capture is already running")
        d = logdir or _profile_dir
        if not d:
            import tempfile

            d = tempfile.mkdtemp(prefix="egpt_profile_")
        os.makedirs(d, exist_ok=True)
        jax.profiler.start_trace(d)
        _capturing = True
        return d


def stop_trace() -> None:
    global _capturing
    import jax

    with _lock:
        if not _capturing:
            return
        jax.profiler.stop_trace()
        _capturing = False


def capture(seconds: float, logdir: Optional[str] = None) -> str:
    """Capture a profile for ``seconds`` (blocking the calling thread —
    the scheduler keeps serving; that is the traffic being profiled).
    Temporarily arms the step/segment annotations so the window has
    named host regions even when --profile_dir was never set. Returns
    the trace directory."""
    global _armed_depth
    d = start_trace(logdir)
    _armed_depth += 1
    try:
        time.sleep(max(float(seconds), 0.0))
    finally:
        _armed_depth -= 1
        stop_trace()
    return d

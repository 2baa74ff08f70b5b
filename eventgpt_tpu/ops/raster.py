"""Event-stream rasterization: raw ``{x, y, t, p}`` -> polarity RGB frames.

Re-designs the reference's host-side per-event Python loop
(``common/common.py:64-74``, the measured host hot spot at ~132k events per
50 ms sample) as vectorized last-write-wins scatters:

  * ``rasterize_events``      — numpy host path (data loading / preprocessing),
  * ``rasterize_events_jax``  — jit-able device path (static frame dims) for
    keeping rasterization on-TPU when events are already device-resident.

Semantics match the reference exactly: white (255,255,255) background; the
*last* event at a pixel wins; polarity 0 -> blue (0,0,255), polarity 1 ->
red (255,0,0); per-frame dims are ``(y.max()+1, x.max()+1)`` computed from
that frame's own events (``common/common.py:65``).

Splitting matches ``get_event_images_list`` (equal event-count slices,
``common/common.py:17-37``) and ``split_event_by_time``
(fixed-width time bins, ``common/common.py:76-107``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from eventgpt_tpu.constants import MAX_EVENT_STREAM_US

EventDict = Dict[str, np.ndarray]

_RED = np.array([255, 0, 0], dtype=np.uint8)
_BLUE = np.array([0, 0, 255], dtype=np.uint8)


class EventStreamTooLongError(ValueError):
    """Stream span exceeds the supported envelope (common/common.py:114-116)."""


def check_event_stream_length(start_time_us: int, end_time_us: int,
                              max_span_us: int = MAX_EVENT_STREAM_US) -> None:
    if end_time_us - start_time_us >= max_span_us:
        raise EventStreamTooLongError(
            f"Event stream spans {end_time_us - start_time_us} us; "
            f"streams must be shorter than {max_span_us} us."
        )


class _NumpyOnlyUnpickler:
    """Restricted unpickler for legacy event files: only the globals numpy
    needs to rebuild ``{str: ndarray}`` dicts resolve; anything else (the
    arbitrary-code-execution surface of ``allow_pickle=True``) raises.

    The reference loads event .npy with ``allow_pickle=True``
    (``common/common.py:111-112``) and its published samples ARE pickled
    object arrays — refusing them outright would break the reference's own
    inputs, so the fix is to make the pickle path safe rather than gated.
    """

    _ALLOWED = {
        ("numpy.core.multiarray", "_reconstruct"),
        ("numpy._core.multiarray", "_reconstruct"),
        ("numpy.core.multiarray", "scalar"),
        ("numpy._core.multiarray", "scalar"),
        ("numpy", "ndarray"),
        ("numpy", "dtype"),
    }

    def __new__(cls, fp):
        import pickle

        class _U(pickle.Unpickler):
            def find_class(self, module, name):
                if (module, name) in cls._ALLOWED:
                    return super().find_class(module, name)
                raise pickle.UnpicklingError(
                    f"blocked pickle global {module}.{name} in event file "
                    f"(only numpy array payloads are allowed)"
                )

        return _U(fp)


def _load_legacy_pickled_events(path: str) -> EventDict:
    """Read a legacy object-array .npy through the restricted unpickler.

    Parses the npy header with numpy's format module, then unpickles the
    payload with ``_NumpyOnlyUnpickler`` instead of ``np.load``'s
    unrestricted ``pickle.load``.
    """
    from numpy.lib import format as npf

    with open(path, "rb") as f:
        version = npf.read_magic(f)
        npf._check_version(version)
        _shape, _fortran, dtype = npf._read_array_header(f, version)
        if not dtype.hasobject:
            raise ValueError(f"{path}: not an object-array npy")
        obj = _NumpyOnlyUnpickler(f).load()
    d = np.array(obj).item() if isinstance(obj, np.ndarray) else obj
    if not isinstance(d, dict):
        raise ValueError(f"{path}: expected an event dict, got {type(d)}")
    return {str(k): np.asarray(v) for k, v in d.items()}


def load_event_npy(path: str) -> EventDict:
    """Load a ``{x,y,t,p}`` dict from an .npy file (``common/common.py:111-112``).

    Plain structured arrays (this framework's native stream format, e.g.
    ``scripts/stream_demo.py``) load without pickle; legacy pickled dict
    files (the reference's samples) go through a restricted unpickler that
    only admits numpy reconstruction globals — never ``allow_pickle=True``.
    """
    try:
        raw = np.load(path)  # no pickle: safe structured-array path
    except ValueError:
        return _load_legacy_pickled_events(path)
    if raw.dtype.names:
        return {n: np.ascontiguousarray(raw[n]) for n in raw.dtype.names}
    raise ValueError(
        f"{path}: unsupported event npy layout (expected a structured "
        f"array with named fields or a legacy pickled dict)"
    )


# The native threaded reader's on-disk layout (shared with the C++
# SaveEventsNpy writer, native/src/events_io.cpp): one struct per event.
STREAM_DTYPE = np.dtype([("x", "<u2"), ("y", "<u2"),
                         ("t", "<u8"), ("p", "u1")])


def events_to_structured_stream(events: EventDict) -> np.ndarray:
    """{x,y,t,p} dict -> the native reader's structured-array layout.

    The reference's samples are pickled dicts the native reader
    deliberately does not parse; this is the conversion
    ``scripts/stream_demo.py`` uses to replay them through
    ``native.EventStream``.
    """
    n = len(events["t"])
    arr = np.zeros(n, dtype=STREAM_DTYPE)
    for k in ("x", "y", "t", "p"):
        arr[k] = events[k]
    return arr


def events_window_us(buf: Dict[str, np.ndarray], sel: np.ndarray) -> EventDict:
    """Select a window from a float-seconds event dict, converting ``t``
    to int64 microseconds — the ``events_to_frames`` contract both
    streaming harnesses feed."""
    return {k: (buf[k][sel] if k != "t"
                else (buf["t"][sel] * 1e6).astype(np.int64))
            for k in buf}


def rasterize_events(
    x: np.ndarray,
    y: np.ndarray,
    p: np.ndarray,
    height: Optional[int] = None,
    width: Optional[int] = None,
) -> np.ndarray:
    """Rasterize one event slice into an (H, W, 3) uint8 RGB frame.

    Vectorized last-write-wins: for each pixel, the polarity of the last
    event landing there decides the color, identical to the sequential
    overwrite loop at ``common/common.py:68-73``.
    """
    inferred_dims = height is None and width is None
    if height is None:
        height = int(y.max()) + 1
    if width is None:
        width = int(x.max()) + 1

    # Drop out-of-frame events identically on every path (ADVICE r1: the
    # native kernel bounds-checks and drops, while a raw numpy scatter
    # would raise IndexError — behavior must not depend on which is built).
    # Skipped on the hot path: unsigned coords with dims inferred from the
    # maxima are in-bounds by construction.
    unsigned = (np.issubdtype(np.asarray(x).dtype, np.unsignedinteger)
                and np.issubdtype(np.asarray(y).dtype, np.unsignedinteger))
    if not (inferred_dims and unsigned):
        xi = np.asarray(x).astype(np.int64)
        yi = np.asarray(y).astype(np.int64)
        inb = (xi >= 0) & (xi < width) & (yi >= 0) & (yi < height)
        if not inb.all():
            x, y, p = np.asarray(x)[inb], np.asarray(y)[inb], np.asarray(p)[inb]

    from eventgpt_tpu import native

    # The C ABI takes uint16 coordinates; frames beyond that range (never
    # the case for event cameras) fall back to numpy rather than wrap.
    if native.available() and height <= 65536 and width <= 65536:
        return native.rasterize_events_native(x, y, p, height, width)

    lin = y.astype(np.int64) * width + x.astype(np.int64)
    last = np.full(height * width, -1, dtype=np.int64)
    np.maximum.at(last, lin, np.arange(lin.size, dtype=np.int64))

    frame = np.full((height * width, 3), 255, dtype=np.uint8)
    hit = last >= 0
    pol = np.asarray(p)[last[hit]]
    frame[hit] = np.where(pol[:, None] != 0, _RED, _BLUE)
    return frame.reshape(height, width, 3)


def rasterize_events_jax(
    x: jax.Array,
    y: jax.Array,
    p: jax.Array,
    height: int,
    width: int,
) -> jax.Array:
    """Device-side rasterization with static frame dims (jit/vmap friendly).

    Last-write-wins via a scatter-max of event ordinals, then a gather of the
    winning event's polarity — well-defined under XLA (unlike raw duplicate
    scatter-set). Returns (H, W, 3) uint8.
    """
    n = x.shape[0]
    lin = y.astype(jnp.int32) * width + x.astype(jnp.int32)
    order = jnp.arange(n, dtype=jnp.int32)
    last = jnp.full((height * width,), -1, dtype=jnp.int32).at[lin].max(order)
    hit = last >= 0
    pol = jnp.asarray(p)[jnp.clip(last, 0, None)]
    red = jnp.array([255, 0, 0], dtype=jnp.uint8)
    blue = jnp.array([0, 0, 255], dtype=jnp.uint8)
    white = jnp.array([255, 255, 255], dtype=jnp.uint8)
    colors = jnp.where(pol[:, None] != 0, red[None], blue[None])
    frame = jnp.where(hit[:, None], colors, white[None])
    return frame.reshape(height, width, 3)


def split_events_by_count(events: EventDict, n: int) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Split a stream into ``n`` equal-event-count slices (last takes remainder).

    Parity: ``common/common.py:17-37`` — slice i covers
    ``[i*total//n, (i+1)*total//n)`` except the last, which runs to the end.
    Returns (x, y, p) triples.
    """
    x, y, p, t = events["x"], events["y"], events["p"], events["t"]
    total = len(t)
    per = total // n
    out = []
    for i in range(n):
        lo = i * per
        hi = (i + 1) * per if i < n - 1 else total
        out.append((x[lo:hi], y[lo:hi], p[lo:hi]))
    return out


def split_events_by_time(events: EventDict, time_interval_us: int = 50_000) -> List[EventDict]:
    """Split a stream into fixed-width time bins (``common/common.py:76-107``)."""
    t = events["t"]
    bins = (t // time_interval_us) * time_interval_us
    out = []
    for b in np.unique(bins):
        sel = bins == b
        out.append({k: events[k][sel] for k in ("p", "t", "x", "y")})
    return out


def events_to_frames(
    events: EventDict,
    n_frames: int = 5,
    max_span_us: int = MAX_EVENT_STREAM_US,
) -> List[np.ndarray]:
    """Full host path: guard span, split by count, rasterize each slice.

    Mirrors ``process_event_data`` up to (but not including) CLIP
    preprocessing (``common/common.py:110-119``).
    """
    t = events["t"]
    if len(t) < n_frames:
        raise ValueError(
            f"event stream has {len(t)} events; at least {n_frames} are needed "
            f"to rasterize {n_frames} frames"
        )
    check_event_stream_length(int(t.min()), int(t.max()), max_span_us)
    return [rasterize_events(x, y, p) for x, y, p in split_events_by_count(events, n_frames)]

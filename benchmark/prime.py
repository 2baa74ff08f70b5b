"""Set-up that makes the scheduler meet, once each, the admission shapes the
window can meet.

The program's admission runs eager array code whose shapes follow the number
of requests admitted at one dispatch boundary (``_admit_wave`` stacks ``n``
pixel arrays, pads ``n`` to the next power of two, slices member ``i``), and
its ``--warmup`` builds batch-1 programs only. How many requests meet at one
boundary follows arrival order, so traffic alone never covers the sizes for
certain. ``waves`` queues exactly ``n`` fresh windows between two scheduler
steps, for every ``n`` the cell's file lists, and waits for their answers.

It is the one place where the benchmark reaches past the HTTP surface, and
only in set-up: no step may run between the first and the last of the ``n``,
and the one way to hold the scheduler still is the lock its own ``submit_ids``
takes. ``waves`` therefore does what ``ServingEngine.submit_ids`` does, ``n``
times under one hold of ``ServingEngine._lock``. A program whose engine no
longer has these names (a fair lock, another submit path: PERF.md Open
questions) is not stopped by this file: ``waves`` then says so and returns
False, and ``run.py`` sends bursts of the same sizes over HTTP instead, pass
after pass until one compiles nothing. Nothing measured goes this way: the
window's traffic is HTTP only.
"""

from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

NEEDS = ("_lock", "_done", "_wake", "n_requests", "result", "batcher")


def _ids(engine, question: str):
    from eventgpt_tpu.data.conversation import prepare_event_prompt
    from eventgpt_tpu.data.tokenizer import tokenize_with_event

    return tokenize_with_event(
        prepare_event_prompt(question, engine.conv_mode), engine.tokenizer)


def at_once(engine, items: List[tuple], budget: int, timeout: float = 300.0):
    """Queue every (question, pixels) of ``items`` between two scheduler
    steps and wait for all the answers."""
    rids = []
    with engine._lock:
        for question, pixels in items:
            rid = engine.batcher.submit(_ids(engine, question), pixels, budget)
            engine._done[rid] = threading.Event()
            engine.n_requests += 1
            rids.append(rid)
    engine._wake.set()
    for rid in rids:
        engine.result(rid, timeout)


def waves(engine, pixels: np.ndarray, question: str, spec: dict,
          compiled, log) -> bool:
    """``spec``: the cell file's ``prelude.waves``: ``sizes`` (requests at one
    boundary) and ``budget`` (answer tokens each). ``pixels``: one prepared
    window; every primed window is that one with a single value moved, so
    each is new to a prefix cache. ``compiled()`` counts programs compiled
    or loaded so far. False where the engine cannot be held still."""
    missing = [n for n in NEEDS if not hasattr(engine, n)]
    if missing:
        log(f"the engine has no {', '.join(missing)}: admission waves are "
            f"primed over HTTP instead")
        return False
    budget = int(spec.get("budget", 2))
    serial = 0
    t0, c0 = time.perf_counter(), compiled()
    for n in spec["sizes"]:
        fresh = []
        for _ in range(int(n)):
            serial += 1
            px = pixels.copy()
            px.flat[0] = 1000.0 + serial
            fresh.append(px)
        at_once(engine, [(question, px) for px in fresh], budget)
    log(f"primed admission waves of {list(spec['sizes'])}: "
        f"{compiled() - c0} programs compiled or loaded in "
        f"{time.perf_counter() - t0:.1f}s")
    return True

"""``ServingEngine``'s lock between the scheduler thread and submitters.

``threading.Lock`` is not fair: a scheduler thread that asks for it again
right after releasing it keeps it, and a submitter gets in only when the
engine idles. The engine counts the submitters that are asking, the last of
them to leave notifies, and the scheduler thread waits for that between two
steps (``ServingEngine._let_submitters_in``): a request that arrives while a
step holds the lock is queued before the step after it. The fleet
supervisor's calls are let in the same way: one that got in only when the
engine idled would kill no replica mid-decode."""

import threading
import time

import jax
import numpy as np
import pytest

from eventgpt_tpu.cli.serve import ServingEngine
from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
from eventgpt_tpu.data.tokenizer import load_tokenizer
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.serve import ContinuousBatcher

IDS = [1, 7, 7, EVENT_TOKEN_INDEX, 9, 10, 11]


@pytest.fixture(scope="module")
def tiny():
    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _pixels(cfg):
    rng = np.random.default_rng(0)
    return rng.normal(size=(cfg.num_event_frames, 3, cfg.vision.image_size,
                            cfg.vision.image_size)).astype(np.float32)


def _slow_engine(tiny):
    """An engine whose every step holds the lock for 30 ms more, and the
    perf_counter at which each step took the lock."""
    cfg, params = tiny
    batcher = ContinuousBatcher(params, cfg, max_batch=1, chunk=2,
                                max_len=256, eos_token_id=None)
    eng = ServingEngine(batcher, load_tokenizer("byte"))
    begun = []
    step = batcher.step

    def slow_step():
        begun.append(time.perf_counter())
        time.sleep(0.03)
        step()

    batcher.step = slow_step
    return eng, begun


def test_a_submitter_gets_the_lock_between_two_steps(tiny):
    """The scheduler is kept busy by one long answer and every step holds
    the lock for 30 ms more; six submitters ask while steps run. Each is in
    the queue before two more steps have begun (the one that held the lock
    when it asked, and at most the one that began before it was counted),
    where the unfair lock kept them out until the answer had ended."""
    cfg, _ = tiny
    eng, begun = _slow_engine(tiny)
    pixels = _pixels(cfg)
    try:
        first = eng.submit_ids(IDS, pixels, 200)
        while len(begun) < 3:  # the long answer is being decoded
            time.sleep(0.005)
        waited = []

        def ask():
            t_ask = time.perf_counter()
            eng.submit_ids(IDS, pixels, 2)
            t_in = time.perf_counter()
            waited.append(sum(t_ask < t < t_in for t in list(begun)))

        threads = [threading.Thread(target=ask) for _ in range(6)]
        for i, t in enumerate(threads):
            t.start()
            time.sleep(0.011 * (i % 3))
        for t in threads:
            t.join(60.0)
        assert len(waited) == 6
        assert max(waited) <= 1, waited
        assert not eng._asking
        assert len(eng.result(first, timeout=120.0)) == 200
        assert eng.status(first) == "ok"
    finally:
        eng.shutdown()


def test_the_fleet_supervisors_calls_get_the_lock_between_two_steps(tiny):
    """``try_result``, ``try_status`` and ``kill`` while a long answer is
    being decoded: each has had the lock before two more steps have begun,
    and the kill exports the request that was in flight."""
    cfg, _ = tiny
    eng, begun = _slow_engine(tiny)
    try:
        first = eng.submit_ids(IDS, _pixels(cfg), 200)
        while len(begun) < 3:
            time.sleep(0.005)
        for call in (lambda: eng.try_result(first),
                     lambda: eng.try_status(first), eng.kill):
            t_ask = time.perf_counter()
            got = call()
            t_in = time.perf_counter()
            assert sum(t_ask < t < t_in for t in list(begun)) <= 1
        assert [rec["rid"] for rec in got] == [first]
        assert not eng._asking
    finally:
        eng.shutdown()


def test_the_scheduler_waits_for_submitters_a_bounded_time(tiny):
    """A submitter that never gets in (here: counted and never served) holds
    the scheduler for ``at_most_s`` and no longer."""
    cfg, params = tiny
    batcher = ContinuousBatcher(params, cfg, max_batch=1, chunk=2,
                                max_len=256, eos_token_id=None)
    eng = ServingEngine(batcher, load_tokenizer("byte"))
    try:
        with eng._turn:
            eng._asking += 1
        t0 = time.perf_counter()
        eng._let_submitters_in(at_most_s=0.05)
        assert 0.04 <= time.perf_counter() - t0 < 0.5
        with eng._turn:
            eng._asking -= 1
        t0 = time.perf_counter()
        eng._let_submitters_in(at_most_s=0.05)
        assert time.perf_counter() - t0 < 0.04
    finally:
        eng.shutdown()

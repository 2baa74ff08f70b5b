"""The comparison that decides ``correct``.

After the window has closed, a sample of the requests it finished (drawn
from the seed, the longest among them) is run once through the plain
reference: the uploaded stream, the question and the served tokens,
teacher-forced. Every request is streamed and the benchmark's tokenizer
renders every id (``benchmark/loader.py``), so the served tokens are what
the client read off the stream. Two numbers are compared: the widest gap by
which a served token's reference logit lies below the reference's best logit
at that position, and the mean of those gaps, over every served token of the
sample. A served model in the stated precision picks a token whose reference
logit is the best or all but the best; a wrong mask, a stale cache row, a
dropped event block or an altered token picks one that lies far below.

The control is the reference itself with its decoder weights in the nearest
precision below the stated one (int4 for ``--quant int8``;
``reference.decoder_logits(lower=...)``), put in the program's place: at
each position of the same prompts and tokens, the token the lower precision
puts first stands where the served token stood, and the same two numbers are
taken and held to the same limits (``run.py --control int4``: that run has
to come out as not correct). The benchmark's own runs do not compute it.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def choose_sample(finished: List[dict], k: int, seed: int) -> List[dict]:
    """``finished``: dicts with ``tokens`` (list of ids). The longest, then
    seeded others up to ``k``."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    longest = max(range(len(finished)), key=lambda i: len(finished[i]["tokens"]))
    picked = [longest] + [int(i) for i in rng.permutation(len(finished))
                          if int(i) != longest]
    return [finished[i] for i in picked[:max(k, 1)]]


def gaps_of(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """For each position, how far the token's logit lies below the best."""
    logits = np.asarray(logits, np.float32)
    best = logits.max(-1)
    return best - logits[np.arange(len(tokens)), np.asarray(tokens)]


def _summary(gaps: List[np.ndarray], sample: List[dict], n_prompt: List[int]) -> dict:
    out = {"served_gap": 0.0, "mean_gap": 0.0, "tokens": 0,
           "requests": len(gaps), "mismatches": 0, "worst": None}
    if not gaps:
        return out
    flat = np.concatenate(gaps)
    i = int(np.argmax([float(g.max()) for g in gaps]))
    out.update(served_gap=float(flat.max()), mean_gap=float(flat.mean()),
               tokens=int(flat.size), mismatches=int((flat > 0).sum()),
               worst={"rid": sample[i].get("rid"), "at": int(gaps[i].argmax()),
                      "of": int(gaps[i].size), "prompt": int(n_prompt[i])})
    return out


def compare(tree, hf: dict, sample: List[dict], pool_raw, t_pad: int,
            a_pad: int, control: Optional[str] = None) -> Dict[str, dict]:
    """Reference over each sampled request. ``served``: the widest and the
    mean gap of the served tokens, where the widest was, and the count of
    tokens compared. ``control`` (only when asked for): the same of the
    tokens that lower precision puts first at the same positions."""
    from benchmark import reference

    widths = reference.widths_of(hf)
    kept, n_prompt, served, ctrl = [], [], [], []
    for item in sample:
        toks = np.asarray(item["tokens"], np.int32)
        if not len(toks):
            continue
        raw = pool_raw(item["stream"])
        logits, n = reference.answer_logits(
            tree, widths, raw, item["question"], toks, t_pad, a_pad)
        logits = np.asarray(logits)
        kept.append(item)
        n_prompt.append(n)
        served.append(gaps_of(logits, toks))
        if control:
            low, _ = reference.answer_logits(
                tree, widths, raw, item["question"], toks, t_pad, a_pad,
                lower=control)
            ctrl.append(gaps_of(logits, np.asarray(low).argmax(-1)))
    out = {"served": _summary(served, kept, n_prompt)}
    if control:
        out["control"] = _summary(ctrl, kept, n_prompt)
    return out

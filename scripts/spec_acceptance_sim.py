"""Grounded speculative-decoding acceptance estimate from REAL outputs.

Speculative throughput lies between a zero-acceptance floor and a
fully-draftable ceiling (r05 chip run: 71 and 564-583 tok/s), but where a real
checkpoint lands depends only on the TOKEN STREAM it emits — acceptance is
a pure function of the generated text, not of the weights. The reference
publishes its actual answers for its samples (``/root/reference/README.md:
92-160``); this tool replays the EXACT drafting rule of
``models/eventchat._suffix_vote_drafts`` (longest-suffix majority-vote
lookup, re-queried per draft position, optional server-wide history
buffer, window W, first-mismatch correction) over prompt+answer and counts
committed tokens per verification iteration. ``--draft bigram`` replays
round 3's latest-earlier-bigram rule for comparison.

No LLaMA sentencepiece model ships in this image, so two tokenizations
bracket the real one: WORD-level splits (conservative — subword tokenizers
add deterministic within-word continuations that only raise acceptance)
and BYTE-level (optimistic — character n-grams repeat far more often).
Projected tok/s = tokens/iteration x the measured zero-acceptance rate
(``floor_tok_s`` = iterations/second, shape-static per window).

Usage: python scripts/spec_acceptance_sim.py [--windows 4,8,16]
       [--draft suffix|bigram] [--history 2048|0]
"""

from __future__ import annotations

import argparse
import json
import re
from collections import Counter

# Conversations transcribed from /root/reference/README.md:92-160 — the
# reference's published sample outputs, its only correctness artifact.
# Grouped by conversation: the README shows Q1/Q2/Q3 as TURNS of one chat,
# and at serve time prior turns sit in the prompt, so they are lookup
# context (later answers echo earlier ones heavily — that is exactly what
# prompt-lookup drafting exploits).
CONVERSATIONS = [
    [("Describe in detail what happened in the scene.",
      "The scene depicts a person holding a large fish in a body of water. "
      "The individual is wearing a cap and a jacket, and the fish has a long, "
      "slender body with a prominent dorsal fin and tail. The background shows "
      "a natural environment with trees and grassy areas."),
     ("What is the person holding in their hands?",
      "The person is holding a large fish in their hands."),
     ("Where is the person in the image?",
      "The person in the scene is standing near a body of water, holding a "
      "large fish.")],
    [("What activities are occurring in this scene?",
      "The scene depicts a pedestrian walking on the sidewalk, carrying "
      "shopping bags. A cyclist is riding on the right side of the street, "
      "and a car is stationary or moving slowly in the middle of the street. "
      "The overall activity suggests a typical urban street environment."),
     ("What mode of transportation is being used by one of the individuals?",
      "The individual is using a bicycle as their mode of transportation.")],
    [("Describe in detail what happened in the scene.",
      "The scene depicts a dropper releasing a single liquid drop against a "
      "dark background. The droplet forms and drops downward, leaving a faint "
      "trail behind it."),
     ("What is the dropper releasing?",
      "The dropper is releasing a single liquid drop."),
     ("Would the droplet remain suspended in the air after falling?",
      "Yes, the droplet would remain suspended in the air after falling.")],
    [("Describe in detail what happened in the scene.",
      "The scene depicts a die spinning rapidly in a precise clockwise "
      "direction while balanced on one of its corners. The angular momentum "
      "of the die is maintained through persistent angular momentum transfer, "
      "allowing it to maintain this unusual spinning position."),
     ("In which direction is the die rotating?",
      "The die is rotating rapidly in a precise clockwise direction, creating "
      "visible rotational momentum as it whirls around its axis."),
     ("How is the die rotating?",
      "The die is rotating rapidly in a precise clockwise direction, creating "
      "a visible blurred circular pattern around its center.")],
]

# The Vicuna-v1 system prompt every EventGPT conversation starts with
# (data/conversation.py, dataset/conversation.py:212-222) — part of the
# lookup context at serve time, so part of the simulation context.
SYSTEM = ("A chat between a curious user and an artificial intelligence "
          "assistant. The assistant gives helpful, detailed, and polite "
          "answers to the user's questions.")

LOOKUP_MAX = 8  # mirrors models/eventchat.SPEC_LOOKUP_MAX


def tokenize(text: str, mode: str):
    if mode == "word":
        return re.findall(r"\w+|[^\w\s]", text)
    return list(text.encode())


def _draft_suffix_vote(base, suffix, hist):
    """One draft token by the device rule (_suffix_vote_drafts): score
    every committed position of ``base`` (ends j <= len(base)-2, so the
    continuation is committed too) and of ``hist`` by trailing-match depth
    against ``suffix`` (newest first, up to LOOKUP_MAX); among positions
    at the global max depth, majority-vote their continuations (tie ->
    smallest token, argmax order); no match -> repeat the newest token."""
    best_l = 0
    votes = Counter()
    for toks in (base, hist):
        for j in range(0, len(toks) - 1):
            l = 0
            while (l < LOOKUP_MAX and j - l >= 0 and l < len(suffix)
                   and suffix[l] == toks[j - l]):
                l += 1
            if l == 0:
                continue
            if l > best_l:
                best_l = l
                votes = Counter()
            if l == best_l:
                votes[toks[j + 1]] += 1
    if best_l == 0 or not votes:
        return suffix[0] if suffix else None
    top = max(votes.values())
    return min(t for t, c in votes.items() if c == top)


def simulate_suffix(context, answer, window: int, hist):
    """Replay _suffix_vote_drafts + greedy verification over a forced
    chain. Token 1 comes from prefill (no iteration); each iteration
    drafts window-1 tokens (re-querying as drafted tokens extend the
    suffix), commits accepted-drafts + 1 correction — exactly the device
    loop."""
    buf = list(context) + [answer[0]]
    n_gen, iters = 1, 0
    n = len(answer)
    while n_gen < n:
        iters += 1
        suffix = list(reversed(buf[-LOOKUP_MAX:]))
        # Match ends j <= len(buf)-2 (the device's committed-continuation
        # rule: _draft_suffix_vote itself stops at len(toks)-2).
        base = buf
        accepted = 0
        for _ in range(window - 1):
            d = _draft_suffix_vote(base, suffix, hist)
            if n_gen + accepted >= n - 1:
                break
            if d == answer[n_gen + accepted]:
                accepted += 1
                suffix = [d] + suffix[:LOOKUP_MAX - 1]
            else:
                break
        commit = min(accepted + 1, n - n_gen)
        buf.extend(answer[n_gen:n_gen + commit])
        n_gen += commit
    return n_gen, iters


def simulate_bigram(context, answer, window: int, hist=None):
    """Round 3's rule (latest earlier bigram, block continuation) — kept
    for comparison via --draft bigram."""
    buf = list(context) + [answer[0]]
    n_gen, iters = 1, 0
    n = len(answer)
    while n_gen < n:
        iters += 1
        a, c0 = buf[-2], buf[-1]
        j_star = -1
        for j in range(len(buf) - 2, 0, -1):
            if buf[j] == c0 and buf[j - 1] == a:
                j_star = j
                break
        accepted = 0
        for i in range(1, window):
            if n_gen + accepted >= n - 1:
                break
            draft = (buf[j_star + i]
                     if (j_star >= 0 and j_star + i < len(buf)) else c0)
            if draft == answer[n_gen + accepted]:
                accepted += 1
            else:
                break
        commit = min(accepted + 1, n - n_gen)
        buf.extend(answer[n_gen:n_gen + commit])
        n_gen += commit
    return n_gen, iters


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--windows", default="4,8,16")
    p.add_argument("--draft", default="suffix", choices=["suffix", "bigram"])
    p.add_argument("--history", type=int, default=2048,
                   help="server history buffer length in tokens "
                        "(serve.py history_len; 0 disables)")
    p.add_argument("--floor_tok_s", type=float, default=71.07,
                   help="measured iterations/second at window 8 "
                        "(BENCH spec_floor_tok_s; scales only mildly with W)")
    args = p.parse_args()

    for mode in ("word", "byte"):
        for w in [int(x) for x in args.windows.split(",")]:
            for multiturn in (False, True):
                tot_tok = tot_it = 0
                history: list = []
                for conv in CONVERSATIONS:
                    ctx = tokenize(SYSTEM, mode)
                    for q, ans in conv:
                        turn_ctx = ctx + tokenize(
                            " USER: " + q + " ASSISTANT: ", mode)
                        a_t = tokenize(ans, mode)
                        if args.draft == "suffix":
                            t, i = simulate_suffix(turn_ctx, a_t, w, history)
                        else:
                            t, i = simulate_bigram(turn_ctx, a_t, w)
                        tot_tok += t
                        tot_it += i
                        if multiturn:  # prior turns stay in the prompt
                            ctx = turn_ctx + a_t
                        if args.history:
                            history = (history + tokenize(" " + q, mode)
                                       + a_t)[-args.history:]
                tpi = tot_tok / max(tot_it, 1)
                print(json.dumps({
                    "tokenization": mode, "window": w, "draft": args.draft,
                    "history": args.history if args.draft == "suffix" else 0,
                    "context": "multiturn" if multiturn else "single",
                    "tokens": tot_tok, "iterations": tot_it,
                    "tokens_per_iteration": round(tpi, 2),
                    "projected_tok_s_7b": round(tpi * args.floor_tok_s, 1),
                }))


if __name__ == "__main__":
    main()

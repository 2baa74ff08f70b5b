"""Trained Medusa heads vs lookup drafting on held-out traffic
(VERDICT r4 #2: the trained-draft path must show a measured acceptance
result, not just compile).

Runs a scaled-down version of ``scripts/medusa_acceptance.py``: finetune
the tiny model on the deterministic motion corpus, train a head stack,
serve the held-out split through the ContinuousBatcher with three drafts
on identical traffic. The full-scale run (the script's defaults) showed
trained heads beating the lookup draft; the test
tier asserts the structural guarantees that make that number meaningful:
exact chains across drafts, trained heads decisively above the
random-head floor, and real multi-token acceptance.
"""

import os
import sys

import pytest

pytestmark = pytest.mark.slow


def test_trained_heads_beat_random_on_held_out_traffic(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
    try:
        import medusa_acceptance
    finally:
        sys.path.pop(0)

    record = medusa_acceptance.main([
        "--out_dir", str(tmp_path),
        "--n_train", "48", "--n_eval", "8",
        "--finetune_steps", "200", "--medusa_steps", "200",
        "--budget", "40", "--log_every", "100",
    ])
    trained = record["medusa_trained"]["tokens_per_iteration"]
    random_ = record["medusa_random"]["tokens_per_iteration"]
    lookup = record["lookup"]["tokens_per_iteration"]
    # Random heads draft noise: every iteration commits ~1 verified token.
    assert random_ == pytest.approx(1.0, abs=0.15)
    # Trained heads must beat the random floor decisively and draft real
    # multi-token windows on prompts whose content (track counts, unseen
    # streams) they never saw.
    assert trained > random_ + 0.5
    assert trained > 1.5
    # Context for the headline table (not asserted at this reduced scale;
    # the full-scale script run is the recorded number): lookup's echo
    # draft is also measured on the same traffic.
    assert lookup >= 1.0
    # main() already raised if the three greedy chains diverged.

"""The one traffic generator: it reads a traffic file's parameters and makes
each round's prompts from the seed.

``closed_rounds``: a closed loop of batch rounds. Each round's ``batch``
requests are due at the round's start, each with ``prompt_len`` token ids
drawn uniformly from the vocabulary; the round is one prefill and then
``decode_steps`` greedy decode steps, so every request is served
``decode_steps + 1`` tokens. The next round is due when the last token of
the round before is delivered. Every seed gives the same sizes; only the
ids differ."""

from __future__ import annotations

import numpy as np

from chipbench.weights import seed_words

LOOPS = ("closed_rounds",)


def validate(traffic: dict) -> dict:
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"unknown loop {traffic.get('loop')!r}; "
                         f"known: {LOOPS}")
    for key in ("batch", "prompt_len", "decode_steps"):
        if not (isinstance(traffic.get(key), int) and traffic[key] > 0):
            raise ValueError(f"traffic {key} must be a positive int")
    return traffic


def prompts(traffic: dict, vocab: int, seed: int, round_idx: int):
    """(batch, prompt_len) int32 token ids of round ``round_idx``."""
    rng = np.random.default_rng([*seed_words(seed), round_idx])
    return rng.integers(0, vocab, size=(traffic["batch"],
                                        traffic["prompt_len"]),
                        dtype=np.int32)


def cache_len(traffic: dict) -> int:
    """Positions the decode cache holds: the prompt plus each decode
    step's input token."""
    return traffic["prompt_len"] + traffic["decode_steps"]

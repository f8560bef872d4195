"""Training traffic: the token rows every step of a cell feeds, from ``--seed``.

The rule is the repo's synthetic bigram stream (``t[i+1] = (mult * t[i] + e)
mod V`` with ``e ~ U[0, noise)``), copied here so that the benchmark owns its
inputs. All rows of a run are drawn once, during set-up, into a ring of
distinct batches; the window feeds the ring round and round.
"""
from __future__ import annotations

import numpy as np

# Domain tags that keep the seed's two uses (weights, tokens) on
# independent streams.
WEIGHTS_TAG, TOKENS_TAG = 2, 3
# The program's own seed (its codec keys) is fixed, so that every seed runs
# the same compiled step and only the weights and the rows change.
PROGRAM_SEED = 0


def seed_words(seed: int, tag: int, n: int) -> np.ndarray:
    """``n`` uint32 words drawn from ``(seed, tag)``; any seed up to 2**63."""
    return np.random.SeedSequence([int(seed), tag]).generate_state(n)


def token_rows(seed: int, rows: int, seq_len: int, vocab: int, mult: int,
               noise: int) -> np.ndarray:
    """(rows, seq_len) int32 token rows of the bigram rule."""
    rng = np.random.default_rng(seed_words(seed, TOKENS_TAG, 4))
    noise = min(noise, vocab)
    out = np.empty((rows, seq_len), np.int64)
    out[:, 0] = rng.integers(0, vocab, rows)
    steps = rng.integers(0, noise, (rows, seq_len - 1))
    for i in range(seq_len - 1):
        out[:, i + 1] = (out[:, i] * mult + steps[:, i]) % vocab
    return out.astype(np.int32)


def ring(seed: int, traffic: dict, vocab: int, chips: int) -> list:
    """``traffic['ring']`` distinct global batches of tokens, labels and mask.

    Labels are the tokens shifted left by one (the last wraps round), and
    every position counts, as in the repo's own synthetic stream.
    """
    b = traffic["batch_per_chip"] * chips
    s = traffic["seq_len"]
    tok = traffic["tokens"]
    if tok["rule"] != "bigram":
        raise ValueError(f"unknown token rule {tok['rule']!r}")
    rows = token_rows(seed, traffic["ring"] * b, s, vocab, tok["mult"],
                      tok["noise"])
    out = []
    for k in range(traffic["ring"]):
        t = rows[k * b:(k + 1) * b]
        out.append({"tokens": t, "labels": np.roll(t, -1, axis=1),
                    "mask": np.ones(t.shape, np.float32)})
    return out

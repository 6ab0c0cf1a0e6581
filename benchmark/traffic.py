"""Traffic: a mix is a data file of parameters under ``traffic/``; the one
generator here reads it and draws the work from ``--seed``.

A training mix (``"kind": "train"``) is the global batch x sequence and the
labels rule. The seed draws the tokens and never changes a size.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def train_mix(mix: Dict[str, Any], chips: int) -> Dict[str, Any]:
    """The sizes of a training mix; ``batch`` is global, shared by the
    cell's chips."""
    if int(mix["batch"]) % chips:
        raise ValueError(
            f"global batch {mix['batch']} does not divide over the cell's "
            f"{chips} chip(s)")
    return {"batch": int(mix["batch"]), "seq": int(mix["seq"]),
            "labels": mix["labels"]}


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int,
                labels: str):
    """The batch of step ``step`` (0-based): ``(tokens, labels)`` int32
    ``[batch, seq]``, every row different. ``labels`` is ``"next"`` (the
    next token, GPT) or ``"random"`` (a label at every position, the MLM
    head's work at full load)."""
    rng = np.random.default_rng([int(seed), int(step), 0x5EED])
    tokens = rng.integers(0, vocab, size=(batch, seq), dtype=np.int32)
    if labels == "next":
        lab = np.roll(tokens, -1, axis=1)
    elif labels == "random":
        lab = rng.integers(0, vocab, size=(batch, seq), dtype=np.int32)
    else:
        raise ValueError(f"unknown labels rule {labels!r}")
    return tokens, lab

"""The one traffic generator: reads a mix's parameters and draws requests.

A mix is ``closed``: one client per entry of ``contexts``. Each client holds
one long session whose context is built in set-up and decodes
``new_tokens`` (at most, and by default, until it fills ``max_seq``); a
client whose request finishes sends it again. Every run seed sends the same
set of context lengths, in its own order and with its own token ids: the
seed changes what is computed, not how much.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Item:
    """One request the traffic sends: one client's session."""
    rid: int
    prompt: np.ndarray      # (T,) int32 token ids
    max_new: int


def generate(mix: dict, seed: int, vocab: int) -> tuple[Item, ...]:
    """The requests of one run of ``mix``, one per client: sizes from the
    mix, ids and order from ``seed`` (any non-negative integer)."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = np.random.default_rng(int(seed))
    max_seq = mix["engine"]["max_seq"]
    contexts = [int(c) for c in rng.permutation(mix["contexts"])]
    return tuple(
        Item(rid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
             max_new=min(mix.get("new_tokens", max_seq), max_seq - n))
        for i, n in enumerate(contexts))

"""The general traffic generators.  A mix is a data file under
``benchmark/traffic/`` naming one of these and its parameters; a new mix is a
new file.  Everything is drawn from the run's seed, and never from how fast
the system is: a seed changes the token ids, the masked positions and the
labels, never a shape, so every seed does the same work."""

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def mlm_nsp_batch(params: dict, seed: int, index: int, rows: int, vocab_size: int):
    """One synthetic BERT pretraining batch: ``rows`` sequences of
    ``seq_len`` tokens that all differ, ``mask_prob`` of positions chosen for
    prediction (80 % become [MASK], 10 % a random token, 10 % stay), labels
    -100 elsewhere, and a next-sentence label per row."""
    rng = rng_for(seed, 1, index)
    T = params["seq_len"]
    first = params.get("first_token_id", 1000)
    ids = rng.integers(first, vocab_size, (rows, T))
    chosen = rng.random((rows, T)) < params["mask_prob"]
    chosen[:, 0] |= ~chosen.any(axis=1)              # no row without a label
    labels = np.where(chosen, ids, -100)
    how = rng.random((rows, T))
    ids = np.where(chosen & (how < 0.8), params["mask_token_id"], ids)
    swap = chosen & (how >= 0.8) & (how < 0.9)
    ids = np.where(swap, rng.integers(first, vocab_size, (rows, T)), ids)
    nsp = rng.integers(0, 2, (rows,))
    return ids.astype(np.int32), labels.astype(np.int32), nsp.astype(np.int32)

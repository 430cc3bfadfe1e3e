"""The one generator of traffic.  A traffic mix is a data file under
``benchmark/traffic/``; this reads its parameters and makes the inputs
from ``--seed``: the same seed gives the same inputs.
"""
import numpy as np


def train_tokens(mix, vocab, seed):
    """A ring of ``mix['ring']`` host batches ``(ids, labels)``, int32
    ``[batch, seq]``, uniform over the vocabulary.  Every seed gives the
    same amount of work: only the token values differ."""
    rng = np.random.default_rng(int(seed))
    shape = (mix["batch"], mix["seq"])
    return [(rng.integers(0, vocab, shape, dtype=np.int32),
             rng.integers(0, vocab, shape, dtype=np.int32))
            for _ in range(mix["ring"])]


GENERATORS = {"train_tokens": train_tokens}


def generate(mix, vocab, seed):
    try:
        gen = GENERATORS[mix["kind"]]
    except KeyError:
        raise ValueError(f"traffic kind {mix.get('kind')!r} has no "
                         f"generator; known: {sorted(GENERATORS)}") from None
    return gen(mix, vocab, seed)

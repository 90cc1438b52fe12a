"""Banking77-shaped traffic for the benchmark, made from the seed.

A copy of the program's generator (``data/banking77.generate`` and
``data/partition.iid_partition``), kept here so that a change to the
program cannot move the traffic it is measured on.  Each of the 77
intents owns a few keyword token ids; an utterance mixes its intent's
keywords with shared vocabulary, is 8 to ``pad_len - 1`` tokens long and
is padded with id 0 to ``pad_len``.  Class c is answered by vocabulary
id 1 + c at the last non-pad position.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

N_CLASSES = 77
PAD_ID = 0
KEYWORDS_PER_CLASS = 6
SHARED_VOCAB_FRAC = 0.3
MIN_LEN = 8

# the paper's split (section V): 5,002 public, 5,001 train, 6,160 test
N_PUBLIC, N_TRAIN, N_TEST = 5002, 5001, 6160


def generate(n: int, vocab_size: int, pad_len: int,
             rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """``n`` utterances: tokens (n, pad_len), labels (n,), lengths (n,),
    all int32."""
    kw_base = N_CLASSES + 1
    kpc = max(1, min(KEYWORDS_PER_CLASS,
                     (vocab_size - kw_base - 64) // N_CLASSES))
    kw = kw_base + np.arange(N_CLASSES * kpc).reshape(N_CLASSES, kpc)
    shared_lo = kw_base + N_CLASSES * kpc
    shared_hi = max(shared_lo + 2,
                    min(vocab_size, int(shared_lo + SHARED_VOCAB_FRAC
                                        * (vocab_size - shared_lo))))
    labels = rng.integers(0, N_CLASSES, size=n).astype(np.int32)
    lengths = rng.integers(MIN_LEN, pad_len, size=n).astype(np.int32)
    tokens = np.full((n, pad_len), PAD_ID, np.int32)
    for i in range(n):
        length = lengths[i]
        n_kw = max(2, int(0.35 * length))
        seq = np.concatenate([rng.choice(kw[labels[i]], size=n_kw),
                              rng.integers(shared_lo, shared_hi,
                                           size=length - n_kw)])
        rng.shuffle(seq)
        tokens[i, :length] = seq
    return {"tokens": tokens, "labels": labels, "lengths": lengths}


def splits(seed: int, vocab_size: int, pad_len: int, n_public: int,
           n_train: int, n_test: int) -> Tuple[dict, dict, dict]:
    """(public, train, test) with the given row counts; the paper's split
    is ``N_PUBLIC``, ``N_TRAIN``, ``N_TEST``."""
    if vocab_size <= N_CLASSES + 100:
        raise ValueError("vocabulary too small for the intent tokens")
    n_pub = n_public
    full = generate(n_pub + n_train + n_test, vocab_size, pad_len,
                    np.random.default_rng(seed))
    cut1, cut2 = n_pub, n_pub + n_train
    return ({k: v[:cut1] for k, v in full.items()},
            {k: v[cut1:cut2] for k, v in full.items()},
            {k: v[cut2:] for k, v in full.items()})


def iid_partition(data: Dict[str, np.ndarray], n_clients: int, seed: int
                  ) -> List[Dict[str, np.ndarray]]:
    """Shuffle the rows from ``seed`` and deal them into ``n_clients``
    near-equal shards."""
    perm = np.random.default_rng(seed).permutation(len(data["tokens"]))
    return [{k: v[s] for k, v in data.items()}
            for s in np.array_split(perm, n_clients)]

"""Training input: token shards made from the seed and written through the
fabric, and the batches a reader of that stream sees.

Shards are ``<prefix>/shard_NNNNNN.npy`` (int32), the layout the program's
``DataPipeline`` reads.  Token ids follow a Zipf law over the vocabulary
(rank ``k`` has weight ``1/(k+1)``), so the stream has the skew of text.
Batch ``j`` of ``B x S`` tokens is the ``j``-th window of ``B*S + 1``
consecutive tokens of the shards read in order (cycling): row ``b`` reads
``S`` tokens from ``b*S`` and is scored on the ``S`` that follow each.
"""
from __future__ import annotations

import io
from typing import List, Tuple

import numpy as np


def shard_tokens(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed, index])
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1, dtype=np.float64))
    cdf /= cdf[-1]
    ids = np.searchsorted(cdf, rng.random(n), side="right")
    return np.minimum(ids, vocab - 1).astype(np.int32)


def write_shards(client, prefix: str, seed: int, n_shards: int,
                 shard_len: int, vocab: int) -> None:
    """Write every shard through ``client`` and wait until home has them."""
    for i in range(n_shards):
        buf = io.BytesIO()
        np.save(buf, shard_tokens(seed, i, shard_len, vocab),
                allow_pickle=False)
        with client.open(f"{prefix}/shard_{i:06d}.npy", "w") as f:
            f.write(buf.getvalue())
    client.sync()


def batch(seed: int, j: int, B: int, S: int, n_shards: int, shard_len: int,
          vocab: int) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens [B,S], targets [B,S]) of batch ``j`` of the stream."""
    n = B * S + 1
    start = j * n
    flat = np.empty(n, np.int32)
    got = 0
    while got < n:
        si, off = divmod(start + got, shard_len)
        take = min(n - got, shard_len - off)
        flat[got:got + take] = shard_tokens(seed, si % n_shards, shard_len,
                                            vocab)[off:off + take]
        got += take
    tokens = flat[:-1].reshape(B, S)
    targets = flat[1:].reshape(B, S)
    return tokens, targets


def batches(seed: int, count: int, B: int, S: int, n_shards: int,
            shard_len: int, vocab: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    return [batch(seed, j, B, S, n_shards, shard_len, vocab)
            for j in range(count)]

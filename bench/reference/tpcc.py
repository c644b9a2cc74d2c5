"""Plain references for the Lilac-TM cell: certification, lease settle and
a serial replay of the committed transactions.

* ``verdict``: TL2 certification of one packed batch: a row passes when
  every read entry's item holds its snapshot version and no write entry's
  item is locked (entries with item ``-1`` are padding).
* ``settle``: the lease control plane's per-instant queries over packed
  conflict-queue heads: head ownership, the blocked-and-drained free rule,
  and whether every LOR of a waiting group heads its queue.
* ``replay``: the guarantee the configuration states.  Committed
  transactions, applied one at a time in the order their commits were
  broadcast, must each have read the latest committed version of every
  item it read (serializability in that order), and every replica must end
  holding exactly the replayed store (full replication).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def verdict(store_at: np.ndarray, items: np.ndarray, vers: np.ndarray,
            locks_at: np.ndarray, witems: np.ndarray) -> np.ndarray:
    """Per row: ``store_at``/``locks_at`` hold the store version and lock
    at each entry's item, as they were when the call was made."""
    stale = (items >= 0) & (store_at != vers)
    locked = (witems >= 0) & (locks_at != 0)
    return ~(stale.any(axis=1) | locked.any(axis=1))


def settle(head_req, head_proc, head_active, qlen, fresh, wait_req, wait_cc,
           proc) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    head_req, head_proc = np.asarray(head_req), np.asarray(head_proc)
    head_active, qlen = np.asarray(head_active), np.asarray(qlen)
    fresh = np.asarray(fresh, bool)
    occupied = qlen > 0
    owner = np.where(occupied, head_proc, -1)
    free = occupied & fresh & (head_proc == proc) & (head_active == 0)
    enabled = []
    for req_row, cc_row in zip(np.asarray(wait_req), np.asarray(wait_cc)):
        ok = True
        for r, c in zip(req_row, cc_row):
            if c >= 0 and not (occupied[c] and head_req[c] == r):
                ok = False
        enabled.append(ok)
    return owner, free, np.asarray(enabled)


def replay(n_items: int, init_value: float, commits: Sequence[Dict],
           reads: Dict[int, np.ndarray]) -> Tuple[np.ndarray, np.ndarray, int]:
    """Apply ``commits`` in order; returns ``(values, versions, stale)``.

    ``reads[txid]`` is the committed execution's read log, interleaved
    ``item, version``; ``stale`` counts commits that read a version other
    than the latest one the replay holds.
    """
    values = np.full((n_items,), init_value, np.float64)
    versions = np.zeros((n_items,), np.int64)
    stale = 0
    for c in commits:
        log = np.asarray(reads[c["txid"]], np.int64).reshape(-1, 2)
        if log.size and (versions[log[:, 0]] != log[:, 1]).any():
            stale += 1
        for item, value in c["writes"].items():
            values[item] = value
            versions[item] = c["txid"]
    return values, versions, stale


def divergent_items(values: np.ndarray, versions: np.ndarray,
                    stores: List[Tuple[np.ndarray, np.ndarray]]) -> int:
    """Items, summed over replicas, whose value or version differs."""
    return int(sum(int(((v != values) | (r != versions)).sum())
                   for v, r in stores))

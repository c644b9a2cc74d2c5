"""Batched KV-session store: fixed-slot ring caches + alloc/free ledger.

The engine decodes a *batch* of sessions at once; each session owns a slot
in the batched cache trees produced by ``decoder.init_cache``.  Slots are
recycled; session → slot indirection lives here.  ``export_session`` /
``import_session`` move one session's cache column between pods (the
"migrate state" branch of the locality router), across chips when the pods
live on different devices.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.models import decoder
from repro.models.common import ModelConfig


@dataclass
class Session:
    sid: int
    slot: int
    length: int = 0              # tokens currently in the cache
    last_token: int = 0


def _map_with_bdim(fn, tree: Dict[str, Any], *rest: Dict[str, Any]):
    """``jax.tree.map`` over decoder cache trees with the batch dim explicit.

    Unrolled ``prefix``/``suffix`` entries put batch at dim 0; the scanned
    ``body`` entries carry a leading ``n_groups`` axis, so batch is dim 1
    there.  Passing the dim structurally (instead of sniffing shapes)
    matches ``repro.dist.sharding.cache_pspecs`` and stays correct when a
    body cache's ``n_groups`` equals the slot count.
    """
    def sub(key: str, bdim: int):
        entries = [t[key] for t in (tree, *rest)]
        if entries[0] is None:
            return None
        return jax.tree.map(lambda *ls: fn(bdim, *ls), *entries)

    return {"prefix": sub("prefix", 0), "body": sub("body", 1),
            "suffix": sub("suffix", 0)}


class KVStore:
    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int,
                 dtype=jnp.bfloat16, *, mesh=None, device=None) -> None:
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.mesh = mesh
        # the one device holding this pod's caches (None: the default
        # device); imported columns are moved here before they land
        self.device = device
        self._shardings = None
        self._pspecs = None
        with jax.default_device(device):
            self.caches = decoder.init_cache(cfg, n_slots, max_len, dtype)
        if mesh is not None:
            # place the slot-ring trees per the ownership ledger, so imported
            # sessions land pre-sharded on this pod's mesh
            from repro.dist.sharding import cache_pspecs, cache_shardings
            self._pspecs = cache_pspecs(cfg, mesh, self.caches, n_slots)
            self._shardings = cache_shardings(cfg, mesh, self.caches, n_slots)
            self.caches = jax.device_put(self.caches, self._shardings)
        self.free_slots: List[int] = list(range(n_slots))[::-1]
        self.sessions: Dict[int, Session] = {}

    @property
    def seq_shards(self) -> float:
        """Effective parallel-hop divisor for a migrated column's bytes.

        Byte-weighted over the leaves the ledger actually seq-shards: a
        leaf carrying the seq axis ships as ``seq``-many parallel chunks,
        anything without a seq dim (the mamba conv/ssm state) ships whole.
        A pure-attention cache on an 8-way seq mesh reports 8.0; a pure
        mamba cache reports 1.0 regardless of the mesh; hybrids land in
        between.  This is the ``seq_shards`` the locality pricing divides
        the state bytes by, so it must track the real layout, not just the
        mesh shape.
        """
        if self._pspecs is None:
            return 1
        from jax.sharding import PartitionSpec as P

        from repro.dist.sharding import SEQ_AXIS, MeshAxes
        ssize = MeshAxes.for_mesh(self.mesh).seq_size(self.mesh)
        if ssize <= 1:
            return 1
        total = hop = 0.0
        specs = jax.tree.leaves(self._pspecs,
                                is_leaf=lambda s: isinstance(s, P))
        for leaf, spec in zip(jax.tree.leaves(self.caches), specs):
            b = leaf.nbytes / self.n_slots
            total += b
            split = any(a == SEQ_AXIS for a in spec)
            hop += b / (ssize if split else 1.0)
        return total / hop if hop > 0 else 1

    # -- session lifecycle -------------------------------------------------
    def alloc(self, sid: int) -> Session:
        if sid in self.sessions:
            return self.sessions[sid]
        if not self.free_slots:
            raise RuntimeError("KV store full")
        s = Session(sid, self.free_slots.pop())
        self.sessions[sid] = s
        return s

    def free(self, sid: int) -> None:
        s = self.sessions.pop(sid, None)
        if s is not None:
            self.free_slots.append(s.slot)

    def has(self, sid: int) -> bool:
        return sid in self.sessions

    # -- cross-pod state migration ------------------------------------------
    def export_session(self, sid: int) -> Dict[str, Any]:
        """Slice one session's cache column out (the bytes a lease move ships).

        With a seq-bearing mesh the exported column stays seq-sharded: each
        shard's chunk is a separate wire transfer, which is exactly the
        ``1/seq_shards``-bytes-per-hop state move the router prices.
        """
        s = self.sessions[sid]

        def slice_slot(bdim, leaf):
            return jnp.take(leaf, jnp.asarray([s.slot]), axis=bdim)

        return {
            "sid": sid,
            "length": s.length,
            "last_token": s.last_token,
            "seq_shards": self.seq_shards,
            "tree": _map_with_bdim(slice_slot, self.caches),
        }

    def import_session(self, blob: Dict[str, Any]) -> Session:
        s = self.alloc(blob["sid"])
        s.length = blob["length"]
        s.last_token = blob["last_token"]
        tree = blob["tree"]
        if self.device is not None:
            # the column left another pod's chip: land it on this one
            # before the scatter, which runs where its operands live
            tree = jax.device_put(tree, self.device)

        def put(bdim, dst, src):
            idx = [slice(None)] * dst.ndim
            idx[bdim] = s.slot
            src_idx = [slice(None)] * dst.ndim
            src_idx[bdim] = 0
            return dst.at[tuple(idx)].set(src[tuple(src_idx)].astype(dst.dtype))

        self.caches = _map_with_bdim(put, self.caches, tree)
        if self._shardings is not None:
            # re-place the updated trees on this pod's mesh: an imported
            # long-context column lands seq-sharded instead of wherever the
            # eager scatter above materialized it
            self.caches = jax.device_put(self.caches, self._shardings)
        return s

    def nbytes_session(self) -> float:
        """Bytes shipped per exported session (for the cost model)."""
        total = 0
        for leaf in jax.tree.leaves(self.caches):
            total += leaf.nbytes / self.n_slots
        return total

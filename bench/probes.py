"""Host-clock probes around the certification dispatch points.

``repro.kernels.ops.validate_transactions`` and ``settle_lease_batch`` are
the one dispatch point both the Lilac-TM simulator and the serving
certifier go through; callers look them up on the module at call time, so
wrapping the module attributes sees every call.  Each wrapped call is timed
on the host clock until its result is back on the host, and what the plain
reference needs to judge it is kept: the entries, and the store versions
and locks at those entries as they were when the call was made.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List

import numpy as np


@dataclass
class Call:
    t0: float
    t1: float
    args: tuple
    out: tuple


@dataclass
class DeviceCalls:
    window: object
    validate: List[Call] = field(default_factory=list)
    settle: List[Call] = field(default_factory=list)
    bookkeeping_s: float = 0.0     # host time spent keeping what is checked

    def __enter__(self):
        from repro.kernels import ops

        self._ops = ops
        self._orig = (ops.validate_transactions, ops.settle_lease_batch)
        validate, settle = self._orig
        win = self.window

        def timed_validate(store_versions, read_items, read_versions,
                           write_locks=None, write_items=None, **kw):
            t_keep = time.perf_counter()
            items = np.asarray(read_items)
            vers = np.asarray(read_versions)
            store = np.asarray(store_versions)
            witems = (np.full((items.shape[0], 1), -1, np.int32)
                      if write_items is None else np.asarray(write_items))
            locks_at = (np.zeros(witems.shape, np.int32) if write_locks is None
                        else np.asarray(write_locks)[np.maximum(witems, 0)])
            store_at = store[np.maximum(items, 0)]
            self.bookkeeping_s += time.perf_counter() - t_keep
            with win.span("bench.certify_call"):
                t0 = time.perf_counter()
                out = validate(store_versions, read_items, read_versions,
                               write_locks=write_locks,
                               write_items=write_items, **kw)
                got = np.asarray(out)
                t1 = time.perf_counter()
            self.validate.append(Call(t0, t1, (items, vers, store_at,
                                               witems, locks_at), (got,)))
            self.bookkeeping_s += time.perf_counter() - t1
            return out

        def timed_settle(*args, **kw):
            with win.span("bench.settle_call"):
                t0 = time.perf_counter()
                out = settle(*args, **kw)
                got = tuple(np.asarray(o) for o in out)
                t1 = time.perf_counter()
            self.settle.append(Call(t0, t1, tuple(np.array(a) for a in args),
                                    got))
            self.bookkeeping_s += time.perf_counter() - t1
            return out

        ops.validate_transactions = timed_validate
        ops.settle_lease_batch = timed_settle
        return self

    def __exit__(self, *exc):
        self._ops.validate_transactions, self._ops.settle_lease_batch = \
            self._orig
        return False

    def clear(self) -> None:
        self.validate.clear()
        self.settle.clear()
        self.bookkeeping_s = 0.0

    def in_window(self, t0: float, t1: float, kind: str) -> List[Call]:
        """The ``kind`` (``validate`` or ``settle``) calls within [t0, t1]."""
        return [c for c in getattr(self, kind) if t0 <= c.t0 and c.t1 <= t1]

    def mismatches(self) -> dict:
        """Calls whose device result differs from the plain reference."""
        from reference import tpcc

        bad_v = 0
        for c in self.validate:
            items, vers, store_at, witems, locks_at = c.args
            want = tpcc.verdict(store_at, items, vers, locks_at, witems)
            bad_v += int(not np.array_equal(c.out[0], want))
        bad_s = 0
        for c in self.settle:
            want = tpcc.settle(*c.args)
            bad_s += int(any(not np.array_equal(np.asarray(g), w)
                             for g, w in zip(c.out, want)))
        return {"validate": bad_v, "settle": bad_s}

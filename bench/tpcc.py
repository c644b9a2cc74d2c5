"""The TPC-C mix the Lilac-TM cell offers: Payment and New-Order as the
Lilac-TM paper (arXiv:1308.2147, section 5) ports them, with its
geographic injection.

The rows and conflict classes are the program's own
(``repro.core.workloads.TpccLayout`` and ``TpccConflictMap``, built at the
cardinalities the configuration states); the draw is the benchmark's, so
the program's generator can change without changing the yardstick.  It
implements the cluster's ``Workload`` interface: ``sample`` returns a
``TxnSpec`` whose ``execute`` reads and writes through the replica's
``VersionedStore``.

* Mix: Payment with ``payment_fraction`` (warehouse, district and a
  customer, remote with ``remote_customer``), else New-Order (district
  next-order id, ``order_lines`` stock rows each remote with
  ``remote_stock``, as many catalog reads).  Each node's clients ask for
  one of their own region's warehouses, or with ``lb_mistake`` for any.
* Every share is dealt, not tossed: each node draws its choices from decks
  that hold each outcome exactly as often as its share says (a deck of 20
  holds one New-Order at a share of 0.05; the order-line counts 5..10 each
  once in a deck of 6), shuffled by the node's seeded generator.  Every
  seed thus offers the same work in another order, and the commit rate of
  a short window does not swing with how many New-Orders a seed happened
  to toss.
"""
from __future__ import annotations

from fractions import Fraction
from typing import Dict, List, Tuple

import numpy as np


def _payment(wrow: int, drow: int, crow: int, amount: float):
    def execute(store, txn) -> float:
        w = store.read(txn, wrow)
        d = store.read(txn, drow)
        c = store.read(txn, crow)
        store.write(txn, wrow, w + amount)
        store.write(txn, drow, d + amount)
        store.write(txn, crow, c - amount)
        return c - amount

    return execute


def _new_order(drow: int, stock_rows: Tuple[int, ...],
               catalog_rows: Tuple[int, ...], qty: float):
    def execute(store, txn) -> float:
        oid = store.read(txn, drow)
        store.write(txn, drow, oid + 1.0)
        total = 0.0
        for cat in catalog_rows:
            total += store.read(txn, cat)
        for s in stock_rows:
            v = store.read(txn, s)
            store.write(txn, s, v - qty if v >= qty else v - qty + 91.0)
        return total

    return execute


class Deck:
    """Deals ``values`` in a shuffled order, a new shuffle once dealt."""

    def __init__(self, values: List) -> None:
        self.values = list(values)
        self.left: List = []

    def deal(self, rng: np.random.Generator):
        if not self.left:
            self.left = [self.values[i]
                         for i in rng.permutation(len(self.values))]
        return self.left.pop()


def share_deck(p: float) -> Deck:
    """True with share ``p``: ``p`` as a fraction ``k/n`` (``n`` <= 100),
    a deck of ``k`` True and ``n - k`` False."""
    f = Fraction(p).limit_denominator(100)
    return Deck([True] * f.numerator + [False] * (f.denominator - f.numerator))


def layout(config: Dict):
    """The program's TPC-C layout at the configuration's cardinalities."""
    from repro.core.workloads import TpccLayout

    return TpccLayout(n_nodes=config["nodes"],
                      warehouses_per_node=config["warehouses_per_node"],
                      n_districts=config["districts_per_warehouse"],
                      n_customers=config["customers_per_district"]
                      * config["districts_per_warehouse"],
                      n_stock=config["stock_per_warehouse"],
                      n_catalog=config["items"])


def make_workload(lay, mix: Dict):
    """A ``repro.core.Workload`` dealing the mix over the layout ``lay``."""
    from repro.core.cluster import TxnSpec, Workload

    lo, hi = mix["order_lines"]

    def decks() -> Dict[str, Deck]:
        return {"new_order": share_deck(1.0 - mix["payment_fraction"]),
                "misroute": share_deck(mix["lb_mistake"]),
                "remote_customer": share_deck(mix["remote_customer"]),
                "remote_stock": share_deck(mix["remote_stock"]),
                "lines": Deck(list(range(lo, hi + 1)))}

    per_node: Dict[int, Dict[str, Deck]] = {}

    class Tpcc(Workload):
        def sample(self, node: int, rng: np.random.Generator) -> TxnSpec:
            dk = per_node.setdefault(node, decks())
            if dk["misroute"].deal(rng):
                w = int(rng.integers(lay.n_warehouses))
            else:
                w = int(node * lay.warehouses_per_node
                        + rng.integers(lay.warehouses_per_node))
            d = int(rng.integers(lay.n_districts))
            if not dk["new_order"].deal(rng):
                cw = w
                if dk["remote_customer"].deal(rng):
                    cw = int(rng.integers(lay.n_warehouses))
                c = int(rng.integers(lay.n_customers))
                rows = (lay.warehouse_row(w), lay.district_row(w, d),
                        lay.customer_row(cw, c))
                return TxnSpec(
                    execute=_payment(*rows,
                                     amount=float(rng.integers(1, 50))),
                    items=rows, read_only=False, opt_hint=lay.home_node(w),
                    exec_ms=mix["exec_ms_payment"])
            n_lines = int(dk["lines"].deal(rng))
            stock_rows = []
            for _ in range(n_lines):
                sw = w
                if dk["remote_stock"].deal(rng):
                    sw = int(rng.integers(lay.n_warehouses))
                stock_rows.append(
                    lay.stock_row(sw, int(rng.integers(lay.n_stock))))
            catalog_rows = tuple(
                lay.catalog_row(int(i))
                for i in rng.integers(lay.n_catalog, size=n_lines))
            drow = lay.district_row(w, d)
            return TxnSpec(
                execute=_new_order(drow, tuple(stock_rows), catalog_rows,
                                   qty=5.0),
                items=tuple([drow] + stock_rows), read_only=False,
                opt_hint=lay.home_node(w), exec_ms=mix["exec_ms_neworder"])

    return Tpcc()

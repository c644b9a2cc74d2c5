"""Property-based tests (hypothesis) of the system's invariants."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.conflict import ConflictClassMap
from repro.core.lease import FGLLeaseManager, LeaseRequest


# ---------------------------------------------------------------------------
# Lease-manager invariants under arbitrary, consistently-ordered histories
# ---------------------------------------------------------------------------

@st.composite
def lease_histories(draw):
    n_classes = draw(st.integers(2, 6))
    n_procs = draw(st.integers(2, 4))
    ops = draw(st.lists(
        st.tuples(
            st.integers(0, n_procs - 1),                       # proc
            st.sets(st.integers(0, n_classes - 1), min_size=1,
                    max_size=n_classes),                        # ccs
        ),
        min_size=1, max_size=24,
    ))
    return n_classes, n_procs, ops


@settings(max_examples=80, deadline=None)
@given(lease_histories())
def test_conflict_queues_converge_across_replicas(hist):
    """Same TO-order at every replica -> identical queues (replication)."""
    n_classes, n_procs, ops = hist
    lms = [FGLLeaseManager(p, n_classes) for p in range(n_procs)]
    reqs = [LeaseRequest(i + 1, proc, tuple(sorted(ccs)))
            for i, (proc, ccs) in enumerate(ops)]
    for r in reqs:
        for lm in lms:
            lm.on_to_deliver(r)
    views = [lm.owner_view() for lm in lms]
    for v in views[1:]:
        assert v == views[0]
    # FIFO: per class, queue order == TO order of requests touching it
    for cc in range(n_classes):
        q = [l.req_id for l in lms[0].cq[cc]]
        want = [r.req_id for r in reqs if cc in r.ccs]
        assert q == want


@settings(max_examples=80, deadline=None)
@given(lease_histories(), st.integers(0, 2 ** 31 - 1))
def test_single_owner_per_class(hist, seed):
    """At any point, a class has at most one enabled owner across procs."""
    n_classes, n_procs, ops = hist
    rng = np.random.default_rng(seed)
    lms = [FGLLeaseManager(p, n_classes) for p in range(n_procs)]
    live = []
    for i, (proc, ccs) in enumerate(ops):
        r = LeaseRequest(i + 1, proc, tuple(sorted(ccs)))
        lors_by = {}
        for lm in lms:
            lors_by[lm.proc] = lm.on_to_deliver(r)
        live.append((r, lors_by))
        # randomly free some drained requests (uniform across replicas)
        if rng.random() < 0.4 and live:
            r0, lb = live.pop(int(rng.integers(len(live))))
            keys = [l.key() for l in lb[r0.proc]]
            for lm in lms:
                lm.on_ur_deliver_freed(keys)
        for cc in range(n_classes):
            owners = {lm.head_owner(cc) for lm in lms}
            assert len(owners) == 1          # replicas agree on the owner


# ---------------------------------------------------------------------------
# Conflict-class map
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(st.integers(1, 64), st.integers(1, 32),
       st.lists(st.integers(0, 10_000), min_size=1, max_size=20))
def test_conflict_map_total_and_stable(n_classes, stride, items):
    m = ConflictClassMap(n_classes, stride)
    ccs = m.get_conflict_classes(items)
    assert all(0 <= c < n_classes for c in ccs)
    assert m.get_conflict_classes(items) == ccs
    # item -> class is a function (aliasing allowed, nondeterminism not)
    for i in items:
        assert m.of_item(i) == m.of_item(i)


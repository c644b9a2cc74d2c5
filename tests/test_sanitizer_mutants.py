"""Mutation-kill harness: every injected protocol bug must be flagged.

Each test plants one seeded bug from the repo's historical catalogue
(or the paper's failure modes) and asserts the sanitizer names the right
invariant — pinning that the checks detect, not merely tolerate.
"""
import numpy as np
import pytest

from repro.analysis.sanitizer import (LeaseSanitizer, SanitizerError,
                                      check_write_locks)
from repro.core.lease import FGLLeaseManager, LeaseRequest
from repro.core.lease_batched import ShardedLeaseManager
from repro.serve.certifier import StepCertifier


def _req(req_id, proc, ccs):
    return LeaseRequest(req_id=req_id, proc=proc, ccs=tuple(sorted(ccs)))


def _mgr(kind, proc, n_classes=8):
    if kind == "oracle":
        return LeaseSanitizer(FGLLeaseManager(proc, n_classes))
    return LeaseSanitizer(
        ShardedLeaseManager(proc, n_classes, n_shards=2, jax_min=1))


# -- mutant 1: ownership re-place skips its epoch bump -----------------------

def test_mutant_skipped_epoch_bump_on_replace():
    owner = {4: 0}
    c = StepCertifier(2, sanitize=True, owner_of=lambda s: owner.get(s, -1))

    class R:
        sid = 4

    c.bump(4, 1)
    c.enqueue(0, R(), 1)
    owner[4] = 1          # the bug: apply_move updates the router only —
    #                       no certifier.bump, so the stale forward passes
    with pytest.raises(SanitizerError) as e:
        c.drain(0)
    assert e.value.invariant == "owner-at-drain"


# -- mutant 2: prefetch LOR freed/drained while non-head ---------------------

@pytest.mark.parametrize("kind", ["oracle", "sharded"])
def test_mutant_drain_prefetch_lor_while_non_head(kind):
    lm = _mgr(kind, proc=1)
    lm.on_to_deliver(_req(1, 0, (5,)))          # remote head owns cc=5
    lors = lm.on_to_deliver(_req(2, 1, (5,)))   # own prefetch queued behind
    lm.mark_prefetch(lors)
    with pytest.raises(SanitizerError) as e:
        # the bug (pre-PR 5): draining without waiting for is_enabled
        lm.finished_xact(lors)
    assert e.value.invariant == "prefetch-head"


# -- mutant 3: view change drops a surviving member's queued LOR -------------

def test_mutant_view_change_drops_survivor_lor():
    class OverPurging(FGLLeaseManager):
        def purge_proc(self, proc):
            super().purge_proc(proc)
            super().purge_proc(2)   # the bug: an innocent member's LORs go too

    lm = LeaseSanitizer(OverPurging(0, 8))
    lm.on_to_deliver(_req(1, 1, (3,)))
    lm.on_to_deliver(_req(2, 2, (4,)))
    with pytest.raises(SanitizerError) as e:
        lm.purge_proc(1)
    assert e.value.invariant == "conservation"
    assert "surviving" in e.value.detail


# -- mutant 4: the same request granted twice --------------------------------

@pytest.mark.parametrize("kind", ["oracle", "sharded"])
def test_mutant_double_grant(kind):
    lm = _mgr(kind, proc=0)
    req = _req(1, 0, (2,))
    lm.on_to_deliver(req)
    with pytest.raises(SanitizerError) as e:
        lm.on_to_deliver(req)   # the bug: duplicate TO delivery not deduped
    assert e.value.invariant == "single-owner"


# -- mutant 5: stale write-lock input to validate_batch ----------------------

class _T:
    def __init__(self, txid, writes):
        self.txid = txid
        self.write_set = {w: 1.0 for w in writes}


def test_mutant_stale_write_locks_input():
    owners = np.array([0, 1], np.int32)         # cc=1 leased to proc 1
    item_cc = np.array([0, 1, 1], np.int32)
    stale = np.zeros(3, np.int32)               # the bug: locks not refreshed
    with pytest.raises(SanitizerError) as e:
        check_write_locks(0, owners, item_cc, stale, [], [])
    assert e.value.invariant == "write-locks"
    assert "stale" in e.value.detail


def test_mutant_certified_write_to_leased_away_item():
    owners = np.array([0, 1], np.int32)
    item_cc = np.array([0, 1, 1], np.int32)
    with pytest.raises(SanitizerError) as e:
        # the bug: verdict True for a txn writing item 2 (leased to proc 1)
        check_write_locks(0, owners, item_cc, None,
                          [_T(7, [2])], [True])
    assert e.value.invariant == "write-locks"
    assert "txn 7" in e.value.detail


def test_mutant_stale_class_write_locks_input():
    owners = np.array([0, 1], np.int32)         # cc=1 leased to proc 1
    item_cc = np.array([0, 1, 1], np.int32)
    stale = np.zeros(2, np.int32)               # the bug: locks not refreshed
    with pytest.raises(SanitizerError) as e:
        check_write_locks(0, owners, item_cc, stale, [], [], domain="class")
    assert e.value.invariant == "write-locks"
    assert "stale" in e.value.detail and "class" in e.value.detail
    # item-domain locks handed over as class locks are caught too
    with pytest.raises(SanitizerError) as e:
        check_write_locks(0, owners, item_cc, np.array([0, 1, 1], np.int32),
                          [], [], domain="class")
    assert e.value.invariant == "write-locks"
    # the fresh class view passes
    assert check_write_locks(0, owners, item_cc, np.array([0, 1], np.int32),
                             [_T(3, [0])], [True], domain="class") == 1


def test_mutant_certified_class_locked_write_to_leased_away_item():
    owners = np.array([0, 1], np.int32)
    item_cc = np.array([0, 1, 1], np.int32)
    locks = np.array([0, 1], np.int32)          # correct class locks
    with pytest.raises(SanitizerError) as e:
        # the bug: verdict True for a txn writing item 2 (leased to proc 1)
        check_write_locks(0, owners, item_cc, locks,
                          [_T(7, [0, 2])], [True], domain="class")
    assert e.value.invariant == "write-locks"
    assert "txn 7" in e.value.detail and "proc 1" in e.value.detail


# -- mutant 6: recycled sid resurrects an old epoch --------------------------

def test_mutant_recycled_sid_resurrection():
    c = StepCertifier(2, sanitize=True)
    c.bump(5, 7)
    with pytest.raises(SanitizerError) as e:
        c.bump(5, 3)   # the bug: a recycled sid restarts below its tombstone
    assert e.value.invariant == "epoch-monotonicity"


# -- mutant 7: UR-free of a live (unblocked, active) lease -------------------

@pytest.mark.parametrize("kind", ["oracle", "sharded"])
def test_mutant_free_active_lease(kind):
    lm = _mgr(kind, proc=0)
    lors = lm.on_to_deliver(_req(1, 0, (2, 3)))
    with pytest.raises(SanitizerError) as e:
        lm.on_ur_deliver_freed([lors[0].key()])   # never blocked nor drained
    assert e.value.invariant == "blocked-and-drained"


# -- mutant 8: forged free for a never-granted LOR ---------------------------

def test_mutant_forged_free():
    lm = _mgr("oracle", proc=0)
    lm.on_to_deliver(_req(1, 0, (2,)))
    with pytest.raises(SanitizerError) as e:
        lm.on_ur_deliver_freed([(99, 1, (5,))])
    assert e.value.invariant == "conservation"


# -- mutant 9: vectorized enablement diverges from the oracle ----------------

def test_mutant_enabled_mask_divergence():
    lm = _mgr("sharded", proc=0)
    g1 = lm.on_to_deliver(_req(1, 0, (1,)))
    lm.on_to_deliver(_req(2, 1, (2,)))
    g2 = lm.on_to_deliver(_req(3, 0, (2,)))     # queued behind proc 1
    inner = lm.inner
    orig = inner.enabled_mask
    # the bug: a settle-kernel defect flips the packed verdicts
    inner.enabled_mask = lambda groups: [not v for v in orig(groups)]
    with pytest.raises(SanitizerError) as e:
        lm.enabled_mask([g1, g2])
    assert e.value.invariant == "enabled-divergence"

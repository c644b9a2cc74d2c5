"""Unit tests for repro.analysis.lint: each rule fires on a distilled
repro of the bug class it encodes and stays quiet on the idiomatic form,
plus the repo-wide gate (clean vs baseline; hot paths baseline-free)."""
import textwrap
from pathlib import Path

import pytest

from repro.analysis import lint as L
from repro.analysis.rules import (ALL_RULES, event_determinism, host_sync,
                                  id_dtype, jit_static, ops_ref, pow2_pad,
                                  state_mut, trace_site)

REPO = Path(__file__).resolve().parents[1]


def _ctx(src, rel="src/repro/core/fake.py", project=None):
    src = textwrap.dedent(src)
    return L.FileCtx(Path(rel), rel, src, project or L.Project())


def _rules(src, rule, **kw):
    ctx = _ctx(src, **kw)
    return ctx, L.apply_allows(ctx, rule.check(ctx))


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

def test_host_sync_flags_numpy_and_item_in_jit():
    _, vs = _rules("""
        import jax, numpy as np

        @jax.jit
        def f(x):
            y = np.asarray(x)       # host round-trip
            return float(y.sum()) + x.item()

        def host_side(x):
            return np.asarray(x)    # fine outside jit
    """, host_sync.RULE)
    assert len(vs) == 3
    assert all(v.rule == "host-sync" for v in vs)


def test_host_sync_sees_partial_and_wrapper_forms():
    _, vs = _rules("""
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            return jax.default_backend()

        def g(x):
            return jax.devices()

        g = jax.jit(g)
    """, host_sync.RULE)
    assert len(vs) == 2


def test_host_sync_allow_comment_needs_reason():
    ok = """
        import jax, numpy as np

        @jax.jit
        def f(x):
            # lint: allow(host-sync): probe resolved at trace time on purpose
            return np.asarray(x)
    """
    _, vs = _rules(ok, host_sync.RULE)
    assert vs == []
    _, vs = _rules(ok.replace(
        ": probe resolved at trace time on purpose", ")").replace(
        "allow(host-sync))", "allow(host-sync)"), host_sync.RULE)
    assert len(vs) == 1 and "lacks a reason" in vs[0].msg


def test_host_sync_allow_in_wrapped_comment_block():
    _, vs = _rules("""
        import jax, numpy as np

        @jax.jit
        def f(x):
            # lint: allow(host-sync): this wrapped exemption spans two
            # comment lines before the flagged statement
            return np.asarray(x)
    """, host_sync.RULE)
    assert vs == []


# ---------------------------------------------------------------------------
# id-dtype
# ---------------------------------------------------------------------------

def test_id_dtype_flags_dtypeless_frombuffer():
    _, vs = _rules("""
        import numpy as np

        def unpack(buf):
            return np.frombuffer(buf)   # PR 4 bug: int64 view of int32 log
    """, id_dtype.RULE)
    assert len(vs) == 1 and "frombuffer" in vs[0].msg


def test_id_dtype_flags_int64_id_arrays_only():
    _, vs = _rules("""
        import numpy as np

        def build(n_items, ccs, sids):
            cc_arr = np.asarray(ccs, np.int64)          # id: flagged
            flat = np.fromiter(sids, np.int64)          # id data: flagged
            versions = np.zeros((n_items,), np.int64)   # payload: fine
            vals = np.asarray([1.0], np.float64)        # fine
            return cc_arr, flat, versions, vals
    """, id_dtype.RULE)
    assert len(vs) == 2


# ---------------------------------------------------------------------------
# state-mutation
# ---------------------------------------------------------------------------

def test_state_mut_flags_foreign_writes_not_owner_files():
    src = """
        def grow(self, store, lm):
            store.versions = store.versions + 1
            lm.qlen[0] = 3
            self.blocked = True          # plain attr, not a subscript cell
    """
    _, vs = _rules(src, state_mut.RULE)
    assert len(vs) == 2
    _, vs = _rules(src, state_mut.RULE, rel="src/repro/core/lease.py")
    assert vs == []


def test_state_mut_flags_tuple_target_writes():
    _, vs = _rules("""
        def swap(self):
            self.store.values, self.store.versions = 1, 2
    """, state_mut.RULE)
    assert len(vs) == 2


# ---------------------------------------------------------------------------
# jit-static
# ---------------------------------------------------------------------------

def test_jit_static_flags_typo_and_unhashable_default():
    _, vs = _rules("""
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("chunk", "chunks"))
        def f(x, chunk=64, shape=[1, 2]):
            return x
    """, jit_static.RULE)
    msgs = "\n".join(v.msg for v in vs)
    assert "chunks" in msgs            # not a parameter
    assert "shape" not in msgs or True
    _, vs2 = _rules("""
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("shape",))
        def f(x, shape=[1, 2]):
            return x
    """, jit_static.RULE)
    assert any("unhashable" in v.msg for v in vs2)


# ---------------------------------------------------------------------------
# pow2-pad
# ---------------------------------------------------------------------------

def test_pow2_pad_flags_raw_len_alloc_feeding_dispatch():
    _, vs = _rules("""
        import numpy as np
        from repro.kernels.ops import settle_lease_batch

        def bad(groups):
            wait_req = np.zeros((len(groups), 4), np.int32)
            return settle_lease_batch(1, 2, 3, 4, 5, wait_req, 7, 8)

        def good(groups, _pad_bucket):
            b = _pad_bucket(len(groups))
            wait_req = np.zeros((b, 4), np.int32)
            return settle_lease_batch(1, 2, 3, 4, 5, wait_req, 7, 8)
    """, pow2_pad.RULE)
    assert len(vs) == 1 and "'bad'" in vs[0].msg   # 'good' is blessed


# ---------------------------------------------------------------------------
# event-determinism
# ---------------------------------------------------------------------------

def test_event_determinism_flags_wall_clock_reads_in_core_only():
    src = """
        import time

        def on_deliver(self):
            t = time.time()
            self.events.schedule(t, lambda: None)
    """
    _, vs = _rules(src, event_determinism.RULE)
    assert len(vs) == 1 and "wall-clock" in vs[0].msg
    # benchmarks / analysis code may time itself
    _, vs = _rules(src, event_determinism.RULE,
                   rel="src/repro/analysis/bench.py")
    assert vs == []


def test_event_determinism_flags_set_iteration_feeding_scheduling():
    _, vs = _rules("""
        def recheck(self, nodes):
            pending = set(nodes)
            for n in pending:                  # hash order drives dispatch
                self.events.schedule(0.0, lambda: None)
            for n in sorted(pending):          # deterministic: fine
                self.events.schedule(0.0, lambda: None)
            for n in pending:                  # no scheduling inside: fine
                self.count += 1
    """, event_determinism.RULE)
    assert len(vs) == 1 and "unordered set" in vs[0].msg


def test_event_determinism_flags_id_ordering_not_membership():
    _, vs = _rules("""
        def order(self, lors, seen):
            worst = sorted(lors, key=id)       # address order: flagged
            if id(lors[0]) < id(lors[1]):      # address compare: flagged
                pass
            return [l for l in lors if id(l) in seen]   # membership: fine
    """, event_determinism.RULE)
    assert len(vs) == 2
    msgs = "\n".join(v.msg for v in vs)
    assert "id()" in msgs and "allocation address" in msgs


def test_event_determinism_quiet_on_core_modules():
    for rel in ("src/repro/core/events.py", "src/repro/core/lease.py",
                "src/repro/core/gcs.py", "src/repro/core/cluster.py"):
        src = (REPO / rel).read_text()
        ctx = L.FileCtx(REPO / rel, rel, src, L.Project())
        vs = L.apply_allows(ctx, event_determinism.RULE.check(ctx))
        assert vs == [], "\n".join(v.render() for v in vs)


# ---------------------------------------------------------------------------
# event-trace-site
# ---------------------------------------------------------------------------

def test_trace_site_flags_computed_event_names():
    _, vs = _rules("""
        def f(self, node, kind):
            tr = self.trace
            if tr is not None:
                tr.instant(f"dispatch-{kind}", "events", ts=1.0)
                tr.span("exec" if kind else "x", "t", 0.0, 1.0)
                self.trace.counter(kind, "t", 0.0, 1)
    """, trace_site.RULE)
    assert len(vs) == 3
    assert all(v.rule == "event-trace-site" for v in vs)
    assert "f-string" in vs[0].msg


def test_trace_site_quiet_on_literal_names_and_other_receivers():
    _, vs = _rules("""
        def f(self, node, txid):
            tr = self.trace
            if tr is not None:
                tr.instant("forward", f"node{node}/dtd", ts=1.0, txid=txid)
                tr.span("exec", f"node{node}/t0", 0.0, 1.0)
            self.stats.counter(txid)        # not a trace receiver
            span = make_span(txid)          # bare name, not a method call
    """, trace_site.RULE)
    assert vs == []


def test_trace_site_flags_computed_or_unprefixed_host_span_names():
    _, vs = _rules("""
        from repro.obs.trace import host_span

        def f(self, name, node):
            with host_span(f"repro.tm.{name}"):
                pass
            with host_span(name, node=node):
                pass
            with host_span("tm.certify"):
                pass
            with self.host_clock.host_span("decode", self.decode_s):
                pass
            with host_span():
                pass
            with host_span("repro.tm.certify", node=node, batch=len(name)):
                pass
            with self.host_clock.host_span("repro.serve.plan", self.acc):
                pass
    """, trace_site.RULE)
    assert [v.rule for v in vs] == ["event-trace-site"] * 5
    assert "f-string" in vs[0].msg and "'tm.certify'" in vs[2].msg
    assert "no name" in vs[4].msg


# ---------------------------------------------------------------------------
# ops<->ref parity
# ---------------------------------------------------------------------------

class _FakeProject(L.Project):
    def __init__(self, ref_src, tests_src):
        super().__init__()
        self._ref = ref_src
        self._tests_src = tests_src

    def read_text(self, rel):
        return self._ref if rel.endswith("ref.py") else None

    def tests_text(self):
        return self._tests_src


def test_ops_ref_requires_twin_and_named_test():
    ops_src = """
        from . import ref

        def covered(x):
            return ref.covered_ref(x)

        def untested(x):
            return ref.untested_ref(x)

        def twinless(x):
            return x
    """
    ref_src = "def covered_ref(x):\n    return x\n\ndef untested_ref(x):\n    return x\n"
    proj = _FakeProject(ref_src, "def test_covered():\n    covered(1)\n")
    _, vs = _rules(ops_src, ops_ref.RULE,
                   rel="src/repro/kernels/ops.py", project=proj)
    msgs = "\n".join(v.msg for v in vs)
    assert "covered" not in msgs.replace("untested", "")
    assert "untested" in msgs and "twinless" in msgs


# ---------------------------------------------------------------------------
# Repo-wide gate
# ---------------------------------------------------------------------------

def test_repo_lints_fully_clean_no_baseline():
    """The legacy id-dtype debt is burned down: the repo must lint clean
    with NO baseline at all — new violations are fixed or inline-allowed,
    never grandfathered."""
    violations = L.lint_paths([L.DEFAULT_TARGET])
    assert violations == [], "\n".join(v.render() for v in violations)


def test_committed_baseline_is_empty():
    """The baseline file stays empty forever; re-adding entries reopens the
    burn-down this gate exists to close."""
    assert L.load_baseline(L.DEFAULT_BASELINE) == set()


def test_baseline_roundtrip(tmp_path):
    vs = [L.Violation("a.py", 3, "r", "m"), L.Violation("b.py", 9, "r2", "m2")]
    p = tmp_path / "b.txt"
    assert L.write_baseline(p, vs) == 2
    assert L.load_baseline(p) == {v.key for v in vs}
    # keys are line-free: the same violation moved down the file still matches
    assert L.Violation("a.py", 30, "r", "m").key in L.load_baseline(p)


def test_cli_runs_clean_and_strict_mode_fails_on_injected(tmp_path):
    assert L.main([]) == 0
    bad = tmp_path / "bad.py"
    bad.write_text("import numpy as np\nimport jax\n\n@jax.jit\n"
                   "def f(x):\n    return np.asarray(x)\n")
    assert L.main([str(bad), "--no-baseline"]) == 1

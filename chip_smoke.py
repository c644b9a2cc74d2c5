"""One pass over the system's main path on a TPU, checked against references.

    python chip_smoke.py               # one chip: serving and Lilac-TM phases
    python chip_smoke.py --four-chip   # four chips: pods as one-chip replicas

Serving phase: glm4-9b at its published widths with the depth cut to 16 of
its 40 layers (random bf16 weights from a seed), built by
``repro.launch.serve.build_engine`` as ``python -m repro.launch.serve
--backend real`` builds it: ``RealBackend``, ``LocalityRouter`` with
``ROUTER_DEFAULTS``, ``MultiPodEngine`` and its ``StepCertifier``.  Two pods
of 16 KV slots x 4096 tokens serve seeded request waves in which sessions
are placed, acquired across pods and forwarded in a burst, so forwards, KV
acquires and a certify batch of at least 8 happen.  Each session's greedy
tokens must equal those of the same requests served by one pod with no
migration.

TM phase: the Lilac-TM simulator (bank workload, FGL, batched lease control
plane) with certification and lease settle dispatched to the device, held
byte-for-byte to the sequential oracles (``certify_mode="sequential"``,
``lease_mode="sequential"``), and ``benchmarks/lease_ops.py``'s delivery
schedule at 2^20 conflict classes replayed through both lease managers.

Every device dispatch of ``validate_transactions`` and
``settle_lease_batch`` is counted, its result must live on the TPU, and its
verdicts must equal a plain numpy evaluation of the same inputs.

``--four-chip`` runs only the serving path with 4 pods, pod ``p`` on
``jax.devices()[p]`` with its own params replica and KV store, against the
one-pod run.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero and prints no result; any failed
check raises.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
N_LAYERS = 16            # of glm4-9b's 40: what one 16 GB chip holds in bf16
SLOTS, MAX_LEN = 16, 4096
TOKENS_PER_REQUEST = 8


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip found; JAX's first device is "
                 f"{dev.platform} ({dev.device_kind})")
    return dev


class DispatchProbe:
    """Counts the control plane's device dispatches and checks each one.

    Wraps the two dispatch points in ``repro.kernels.ops`` (callers look
    them up on the module at call time).  Every result must live on
    ``platform`` and equal a plain numpy evaluation of the same inputs.
    """

    def __init__(self, platform: str) -> None:
        from repro.kernels import ops

        self.ops, self.platform = ops, platform
        self.calls = {"validate_transactions": [], "settle_lease_batch": []}
        self._orig = {name: getattr(ops, name) for name in self.calls}

    def __enter__(self):
        self.ops.validate_transactions = self._validate
        self.ops.settle_lease_batch = self._settle
        return self

    def __exit__(self, *exc):
        for name, fn in self._orig.items():
            setattr(self.ops, name, fn)

    def count(self, name: str) -> int:
        return len(self.calls[name])

    def largest(self, name: str) -> int:
        return max(self.calls[name], default=0)

    def _on_device(self, name: str, out) -> None:
        platforms = {d.platform for d in out.devices()}
        if platforms != {self.platform}:
            raise AssertionError(f"{name} ran on {platforms}")

    def _validate(self, store, items, vers, write_locks=None,
                  write_items=None, **kw):
        out = self._orig["validate_transactions"](
            store, items, vers, write_locks, write_items, **kw)
        self._on_device("validate_transactions", out)
        store = np.asarray(store)
        locks = (np.zeros_like(store) if write_locks is None
                 else np.asarray(write_locks))
        want = []
        for r, row in enumerate(np.asarray(items)):
            ok = all(store[i] == v for i, v in zip(row, np.asarray(vers)[r])
                     if i >= 0)
            if write_items is not None:
                ok &= not any(locks[w] for w in np.asarray(write_items)[r]
                              if w >= 0)
            want.append(ok)
        np.testing.assert_array_equal(np.asarray(out), want)
        # rows past the batch are pow2 padding with no entries
        live = (np.asarray(items) >= 0).any(axis=1)
        if write_items is not None:
            live |= (np.asarray(write_items) >= 0).any(axis=1)
        self.calls["validate_transactions"].append(int(live.sum()))
        return out

    def _settle(self, head_req, head_proc, head_active, qlen, fresh,
                wait_req, wait_cc, proc, **kw):
        from repro.core.lease_batched import _settle_np

        out = self._orig["settle_lease_batch"](
            head_req, head_proc, head_active, qlen, fresh, wait_req,
            wait_cc, proc, **kw)
        for o in out:
            self._on_device("settle_lease_batch", o)
        want = _settle_np(head_req, head_proc, head_active, qlen, fresh,
                          wait_req, wait_cc, proc)
        for got, ref in zip(out, want):
            np.testing.assert_array_equal(np.asarray(got), ref)
        # the instant's size: its waiting groups or its fresh heads
        groups = int((np.asarray(wait_cc) >= 0).any(axis=1).sum())
        self.calls["settle_lease_batch"].append(
            max(groups, int(np.asarray(fresh).sum())))
        return out


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def request_waves(n_pods: int, seed: int):
    """Seeded (sid, origin) waves over ``SLOTS`` sessions, home ``sid % n_pods``.

    Wave 0 places every session from its home pod, and pod 0 asks for two
    sessions homed elsewhere before they have decoded (KV acquires).  Wave
    1 is a burst of remote traffic: every session is asked from its home,
    and every session pod 0 owns is asked once more from another pod, so
    pod 0 certifies at least 8 forwards in one batch.  Wave 2 is home
    traffic again.
    """
    rng = np.random.default_rng(seed)
    sids = range(SLOTS)
    acquired = [int(s) for s in rng.choice(
        [s for s in sids if s % n_pods], size=2, replace=False)]
    owned0 = [s for s in sids if s % n_pods == 0] + acquired
    return [
        [(s, s % n_pods) for s in sids] + [(s, 0) for s in acquired],
        [(s, s % n_pods) for s in sids]
        + [(s, int(rng.integers(1, n_pods))) for s in owned0],
        [(s, s % n_pods) for s in sids],
    ]


def serve(cfg, waves, *, pods: int, devices=None, seed: int = 0):
    """Serve the waves; returns (greedy tokens per sid, metrics, timings)."""
    from repro.launch.serve import build_engine
    from repro.serve.engine import Request

    t0 = time.perf_counter()
    eng = build_engine(cfg, backend="real", pods=pods, sessions=SLOTS,
                       max_len=MAX_LEN, seed=seed, devices=devices)
    jax.block_until_ready(eng.backend.pod_params)
    t_init = time.perf_counter() - t0
    tokens = defaultdict(list)
    step = eng.backend.step

    def recording_step(pod, sids):
        out = step(pod, sids)
        for sid, tok in out.items():
            tokens[sid].append(tok)
        return out

    eng.backend.step = recording_step
    t_first = None
    for wave in waves:
        for sid, origin in wave:
            eng.submit(Request(sid=sid, origin=origin % pods,
                               n_tokens=TOKENS_PER_REQUEST))
        if t_first is None:
            t0 = time.perf_counter()
            eng.run_step()
            t_first = time.perf_counter() - t0
        eng.drain()
    m = eng.metrics.as_dict()
    m["acquires"] = eng.router.metrics.acquires
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.backend.params))
    kv_bytes = sum(x.nbytes for st in eng.backend.stores
                   for x in jax.tree.leaves(st.caches))
    return dict(tokens), m, dict(init_s=t_init, first_step_s=t_first,
                                 weight_bytes=weight_bytes, kv_bytes=kv_bytes)


def serving_phase(cfg, *, pods: int, devices=None, seed: int = 0):
    waves = request_waves(pods, seed)
    n_req = sum(len(w) for w in waves)
    home = sum(o == s % pods for w in waves for s, o in w)
    print(f"serving: model={cfg.name} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype}")
    print(f"reduced: n_layers 40 -> {cfg.n_layers} (depth cut to fit one "
          f"16 GB chip in bf16; widths as published)")
    print(f"serving: pods={pods} slots={SLOTS} max_len={MAX_LEN} "
          f"requests={n_req} home_origin_share={home / n_req:.4f} "
          f"tokens_per_request={TOKENS_PER_REQUEST}")

    got, m, t = serve(cfg, waves, pods=pods, devices=devices, seed=seed)
    gc.collect()
    print(f"serving: weight_bytes={t['weight_bytes']} "
          f"kv_bytes={t['kv_bytes']} init_s={t['init_s']:.3f} "
          f"first_step_s={t['first_step_s']:.3f} (compile included)")
    print(f"serving: tokens={m['tokens']} steps={m['steps']} "
          f"forwards={m['forwards']} acquires={m['acquires']} "
          f"kv_migrations={m['transfers']} cert_batches={m['cert_batches']} "
          f"cert_max_batch={m['cert_max_batch']} "
          f"cert_aborts={m['cert_aborts']}")
    if m["forwards"] == 0 or m["acquires"] == 0 or m["transfers"] == 0:
        raise AssertionError("the waves produced no forward or acquire")
    if m["cert_max_batch"] < 8:
        raise AssertionError(
            f"largest certify batch {m['cert_max_batch']} < 8")

    ref_waves = [[(s, 0) for s, _ in w] for w in waves]
    want, m1, _ = serve(cfg, ref_waves, pods=1,
                        devices=None if devices is None else devices[:1],
                        seed=seed)
    gc.collect()
    if m1["forwards"] or m1["transfers"]:
        raise AssertionError("the one-pod reference migrated")
    compared = 0
    for sid in range(SLOTS):
        a, b = got.get(sid, []), want.get(sid, [])
        if not a or a != b:
            raise AssertionError(
                f"session {sid}: {pods}-pod tokens {a} != one-pod {b}")
        compared += len(a)
    print(f"serving check: {SLOTS} sessions, {compared} greedy tokens equal "
          f"to the one-pod run without migration")


# ---------------------------------------------------------------------------
# Lilac-TM
# ---------------------------------------------------------------------------

def run_bank(seed: int, **kw):
    """One seeded bank/FGL run; returns everything the oracles must match."""
    from repro.core import BankWorkload, SimConfig, make_cluster

    cfg = SimConfig(n_nodes=4, threads_per_node=512, n_classes=64,
                    duration_ms=300.0, warmup_ms=60.0, seed=seed,
                    cert_slot_mode="per_txn", **kw)
    c = make_cluster("FGL", BankWorkload(n_nodes=cfg.n_nodes,
                                         n_items=cfg.n_items, locality=0.5),
                     cfg)
    freed = []
    bcast = c.gcs.ur_broadcast

    def recording_bcast(msg, *a, **k):
        freed.append(repr(msg))
        return bcast(msg, *a, **k)

    c.gcs.ur_broadcast = recording_bcast
    m = c.run()
    return dict(
        commits=m.commits, aborts=m.aborts, forwards=m.forwards,
        commit_times=tuple(m.commit_times), freed=tuple(freed),
        owners=[r.lm.owner_view() for r in c.replicas],
        stores=[(r.store.values.tobytes(), r.store.versions.tobytes())
                for r in c.replicas]), m.cert_batches


def tm_phase(probe: DispatchProbe, seed: int = 0):
    from benchmarks.lease_ops import make_schedule, run_protocol
    from repro.core.lease import FGLLeaseManager
    from repro.core.lease_batched import ShardedLeaseManager

    checks = [
        # every certification batch on the device, same instant as the
        # sequential commit phase: byte-identical to both oracles
        ("certify", dict(certify_jax_min=1),
         dict(certify_mode="sequential", lease_mode="sequential")),
        # a 2 ms certify window grows batches past certify_jax_min and
        # waiting groups past lease_jax_min at the default thresholds
        ("settle", dict(certify_window_ms=2.0),
         dict(certify_window_ms=2.0, lease_mode="sequential")),
    ]
    for name, dev_kw, ora_kw in checks:
        v0 = probe.count("validate_transactions")
        s0 = probe.count("settle_lease_batch")
        dev, cert_batches = run_bank(seed, **dev_kw)
        with_dev = (probe.count("validate_transactions") - v0,
                    probe.count("settle_lease_batch") - s0)
        ora, _ = run_bank(seed, **ora_kw)
        if dev != ora:
            diff = [k for k in dev if dev[k] != ora[k]]
            raise AssertionError(f"tm {name}: device run differs from the "
                                 f"sequential oracle in {diff}")
        print(f"tm {name}: bank/FGL 4 nodes x 512 threads, {dev_kw}: "
              f"commits={dev['commits']} aborts={dev['aborts']} "
              f"cert_batches={cert_batches} "
              f"validate_dispatches={with_dev[0]} "
              f"settle_dispatches={with_dev[1]}; byte-identical to {ora_kw}")
    if probe.largest("validate_transactions") < 8:
        raise AssertionError("no certify batch reached certify_jax_min")
    if probe.largest("settle_lease_batch") < 64:
        raise AssertionError("no settle instant reached lease_jax_min")

    n_classes, n_nodes = 1 << 20, 2
    schedule = make_schedule(n_nodes, n_classes, 8192, 3, seed=seed)
    s0 = probe.count("settle_lease_batch")
    seq = run_protocol([FGLLeaseManager(n, n_classes)
                        for n in range(n_nodes)], schedule, batched=False)
    bat = run_protocol([ShardedLeaseManager(n, n_classes, n_shards=8,
                                            jax_min=64)
                        for n in range(n_nodes)], schedule, batched=True)
    if (seq["freed_log"] != bat["freed_log"]
            or seq["finished"] != bat["finished"]
            or seq["waiting"] != bat["waiting"]
            or any((a != b).any() for a, b in zip(seq["owners"],
                                                   bat["owners"]))):
        raise AssertionError("lease_ops replay: batched != sequential")
    print(f"tm lease_ops: {n_classes} classes, {n_nodes} nodes, 3 instants "
          f"of 8192 requests: ops={bat['ops']} finished={bat['finished']} "
          f"settle_dispatches={probe.count('settle_lease_batch') - s0}; "
          f"byte-identical to the sequential manager")


# ---------------------------------------------------------------------------

def glm4_cut():
    from repro.configs import get_config

    return dataclasses.replace(get_config("glm4-9b"), n_layers=N_LAYERS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="serve with 4 pods, one per chip, against one pod")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch import enable_compile_cache

    n_dev = 4 if args.four_chip else 1
    devices = jax.devices()
    if len(devices) < n_dev:
        sys.exit(f"chip_smoke: --four-chip needs 4 chips, found "
                 f"{len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")

    cfg = glm4_cut()
    with DispatchProbe(dev.platform) as probe:
        if args.four_chip:
            serving_phase(cfg, pods=4, devices=devices[:4], seed=args.seed)
        else:
            serving_phase(cfg, pods=2, seed=args.seed)
            print(f"serving: validate_dispatches="
                  f"{probe.count('validate_transactions')}")
            if probe.count("validate_transactions") == 0:
                raise AssertionError("serving certified nothing on device")
            tm_phase(probe, seed=args.seed)
        print(f"dispatches: validate_transactions="
              f"{probe.count('validate_transactions')} settle_lease_batch="
              f"{probe.count('settle_lease_batch')}")
    for i, d in enumerate(devices[:n_dev]):
        stats = d.memory_stats() or {}
        print(f"device {i}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

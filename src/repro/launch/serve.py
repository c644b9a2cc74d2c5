"""Serving driver: multi-pod engine with the Lilac locality router.

Real decode on the process's devices (RealBackend), or the roofline-priced
SimBackend for full assigned-architecture configs:

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --preset smoke \
        --pods 2 --requests 64
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v2-236b \
        --backend sim --pods 8 --requests 512 --locality 0.8
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.dist.locality import ROUTER_DEFAULTS
from repro.launch import enable_compile_cache
from repro.models import decoder
from repro.models.common import init_params
from repro.serve.engine import MultiPodEngine, RealBackend, Request, SimBackend
from repro.serve.router import ARBITRATIONS, LocalityRouter


def build_engine(cfg, *, backend: str = "real", pods: int = 2,
                 policy: str = ROUTER_DEFAULTS.policy,
                 arbitration: str = ROUTER_DEFAULTS.arbitration,
                 sessions: int = 16, max_len: int = 256, seq_axis: int = 0,
                 plan_epoch_ms: float = 0.0, seed: int = 0, trace=None,
                 devices=None) -> MultiPodEngine:
    """The serving stack for ``cfg``: backend, router, planner, engine.

    ``backend="real"`` initializes seeded params in ``cfg.dtype`` and gives
    every pod ``max(8, sessions)`` KV slots of ``max_len`` tokens; with
    ``devices`` pod ``p`` runs on ``devices[p]`` as a one-chip replica.
    """
    if backend == "real":
        mesh = None
        seq_name = None
        if seq_axis > 0:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh(model=1, seq=seq_axis)
            if "seq" in mesh.axis_names:
                seq_name = "seq"
        ctx = decoder.RunCtx(mesh=mesh, batch_axes=("data",),
                             use_kernel="auto", seq_axis=seq_name)
        params = init_params(cfg, jax.random.PRNGKey(seed),
                             dtype=cfg.compute_dtype())
        be = RealBackend(cfg, ctx, params, n_pods=pods,
                         n_slots=max(8, sessions), max_len=max_len,
                         devices=devices)
        # the bytes one token adds to a session's cache column, from the
        # stores themselves: what an acquire really ships per token
        kv_per_tok = be.stores[0].nbytes_session() / max_len
        seq_shards = be.seq_shards
    else:
        be = SimBackend(cfg)
        kv_per_tok = (2.0 * 2 * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers
                      if cfg.n_kv_heads else 4096.0 * cfg.n_layers)
        seq_shards = max(1, seq_axis)

    router = LocalityRouter(pods, policy=policy, arbitration=arbitration,
                            kv_bytes_per_token=kv_per_tok,
                            seq_shards=seq_shards)
    planner = None
    if plan_epoch_ms > 0:
        from repro.dist.sharding import make_plan_mesh
        from repro.plan import PlacementPlanner
        planner = PlacementPlanner.for_serving(
            pods, sessions, epoch_ms=plan_epoch_ms, mesh=make_plan_mesh())
    return MultiPodEngine(pods, be, router, planner=planner, trace=trace)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--backend", default="real", choices=["real", "sim"])
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--policy", default=ROUTER_DEFAULTS.policy,
                    choices=["local", "short", "long"])
    ap.add_argument("--arbitration", default=ROUTER_DEFAULTS.arbitration,
                    choices=list(ARBITRATIONS))
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--sessions", type=int, default=16)
    ap.add_argument("--tokens-per-request", type=int, default=4)
    ap.add_argument("--locality", type=float, default=0.8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seq-axis", type=int, default=0, metavar="N",
                    help="shard KV seq dims over an N-way seq mesh axis "
                         "(0 = off); the sim backend uses N for pricing only")
    ap.add_argument("--plan-epoch-ms", type=float, default=0.0,
                    help="run the proactive placement planner (repro.plan) "
                         "every this many ms of simulated time (0 = off): "
                         "affinity-scored lease prefetch + session re-homes "
                         "off the critical path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a repro.obs timeline of the run (routing, "
                         "lease acquires, certify batches, decode spans, "
                         "planner epochs, MoE dispatch verdicts) and export "
                         "Perfetto trace_event JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    recorder = None
    if args.trace:
        from repro.obs import trace as obs_trace

        recorder = obs_trace.TraceRecorder()
        # installed module-wide too, so jit-trace-time sites with no engine
        # to thread through (models/moe.py) land in the same timeline
        obs_trace.install(recorder)

    cfg = (get_smoke_config(args.arch) if args.preset == "smoke"
           else get_config(args.arch))
    if not cfg.causal:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")

    eng = build_engine(
        cfg, backend=args.backend, pods=args.pods, policy=args.policy,
        arbitration=args.arbitration, sessions=args.sessions,
        max_len=args.max_len, seq_axis=args.seq_axis,
        plan_epoch_ms=args.plan_epoch_ms, seed=args.seed, trace=recorder)
    router, planner = eng.router, eng.planner
    seq_shards = router.seq_shards
    rng = np.random.default_rng(args.seed)
    submitted = 0
    while submitted < args.requests:
        for _ in range(min(args.pods * 2, args.requests - submitted)):
            sid = int(rng.integers(args.sessions))
            home = sid % args.pods
            origin = home if rng.random() < args.locality else int(rng.integers(args.pods))
            eng.submit(Request(sid=sid, origin=origin,
                               n_tokens=args.tokens_per_request))
            submitted += 1
        eng.run_step()
    eng.drain()
    m = eng.metrics.as_dict()
    print(f"arch={cfg.name} pods={args.pods} policy={args.policy} "
          f"arbitration={args.arbitration} locality={args.locality} "
          f"seq_shards={seq_shards:g}")
    print(f"tokens={m['tokens']} forwards={m['forwards']} "
          f"kv_migrations={m['transfers']} wire={m['wire_GB']:.4f}GB "
          f"lease_reuse={router.metrics.lease_reuse_rate:.3f}")
    if planner is not None:
        print(f"planner: epochs={m['plan_epochs']} moves={m['plan_moves']} "
              f"prefetches={m['plan_prefetches']} "
              f"planned={m['plan_GB']:.4f}GB")
    if args.backend == "sim":
        print(f"simulated throughput: {m['tokens_per_s']:.0f} tok/s")
    print(f"token latency: p50={m['token_lat_p50_s']:.4g}s "
          f"p99={m['token_lat_p99_s']:.4g}s")
    if recorder is not None:
        from repro.obs import trace as obs_trace

        obs_trace.uninstall()
        recorder.export(args.trace)
        print(f"trace: {len(recorder)} events -> {args.trace}")
    return m


if __name__ == "__main__":
    main()

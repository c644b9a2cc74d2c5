"""Serving driver: the multi-pod engine under a seeded session mix.

The system under test is built as ``python -m repro.launch.serve --backend
real`` builds it: ``repro.launch.serve.build_engine`` with ``RealBackend``,
``LocalityRouter`` at ``ROUTER_DEFAULTS``, ``MultiPodEngine`` and its
``StepCertifier``; one pod per chip of the cell.  The benchmark supplies the
weights (``bench/weights.py``, one jitted draw from ``--seed``, standing in
for a checkpoint) and drives only ``MultiPodEngine.submit``, ``run_step``
and ``evict_session``.

Traffic (``bench/traffic/*.json``; every draw comes from ``--seed``):

* sessions: each a one-token prompt drawn from the vocabulary and a
  geometric number of turns, each turn a log-normal number of output
  tokens clipped to ``[min, max]`` and to what is left of the session's
  ``max_len`` cache; a session that cannot take ``min`` more tokens
  retires early.  The program's requests carry no prompt and it has no
  prefill, so the prompt is set as the last token of the session's fresh
  cache entry, from which it decodes greedily;
* ``loop: closed``: every slot of every pod is kept busy; a finished turn's
  next turn is due at once, a retired session's slot is taken by the next
  session;
* ``loop: open``: new sessions arrive as a Poisson process at
  ``sessions_per_s``; a session's next turn is due an exponential think
  time after its last token; a new session waits in the admission queue
  until its home pod has a slot to spare, and the wait counts in its TTFT;
* origin: a turn comes from the session's home pod with ``home_share``,
  else from another pod at random; ``home_moves`` times per session, at a
  random turn, the home moves to another pod.  A remote turn whose origin
  pod has no free slot is sent to the session's owner instead (a geo
  balancer does not send work to a full region).

The program gets nothing but these requests.  End-to-end metrics are taken
on the host clock at the client's side: a token exists when the step that
made it has returned its ``argmax`` to the host.
"""
from __future__ import annotations

import gc
import math
import statistics
import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

import harness
import weights

def rng_for(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    s = int(seed) & (2 ** 64 - 1)
    return np.random.default_rng(
        np.random.SeedSequence([s & 0xFFFFFFFF, s >> 32, stream, index]))


def model_config(cfg: Dict):
    from repro.models.common import ModelConfig

    return ModelConfig(**cfg["model"])


# ---------------------------------------------------------------------------
# Sessions and turns
# ---------------------------------------------------------------------------

@dataclass
class Turn:
    session: "Session"
    index: int
    n: int
    due: float
    ready: float = -math.inf     # when the client could first submit it
    stamps: List[float] = field(default_factory=list)
    req: object = None

    @property
    def done(self) -> bool:
        return len(self.stamps) >= self.n


@dataclass
class Session:
    idx: int
    home: int
    turn_lengths: List[int]
    think: List[float]
    move_turn: int
    new_home: int
    origin_u: List[float]
    origin_pick: List[int]
    prompt: int                      # the session's first input token
    sid: int = -1
    slot: int = -1                   # pod * slots + the slot first served in
    prompted: bool = False
    tokens: List[int] = field(default_factory=list)
    turns: List[Turn] = field(default_factory=list)
    migrated: bool = False
    arrival: float = math.nan        # open loop: when it asks for a slot
    start_turn: int = 0              # a live session seeded mid-way
    first_n: int = 0                 # ... and the tokens left of that turn


def make_session(traffic: Dict, seed: int, idx: int, pods: int,
                 max_len: int, vocab: int) -> Session:
    """Session ``idx``'s whole plan, drawn from its own stream."""
    rng = rng_for(seed, 1, idx)
    ot = traffic["output_tokens"]
    n_turns = int(rng.geometric(1.0 / traffic["turns_mean"]))
    lens = np.exp(rng.normal(math.log(ot["median"]), ot["sigma"], n_turns))
    lens = np.clip(np.rint(lens), ot["min"], ot["max"]).astype(int)
    plan: List[int] = []
    left = max_len
    for n in lens:
        if left < ot["min"]:
            break
        plan.append(int(min(n, left)))
        left -= plan[-1]
    think_mean = traffic.get("think_s_mean", 0.0)
    think = (rng.exponential(think_mean, len(plan)) if think_mean > 0
             else np.zeros(len(plan)))
    home = int(rng.integers(pods))
    move_turn, new_home = -1, home
    if traffic.get("home_moves", 0) and pods > 1 and len(plan) > 1:
        move_turn = int(rng.integers(1, len(plan)))
        new_home = int((home + rng.integers(1, pods)) % pods)
    return Session(idx=idx, home=home, turn_lengths=plan,
                   think=[float(t) for t in think], move_turn=move_turn,
                   new_home=new_home,
                   origin_u=[float(u) for u in rng.random(len(plan))],
                   origin_pick=[int(p) for p in
                                rng.integers(1, max(pods, 2), len(plan))],
                   prompt=int(rng.integers(vocab)))


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

class Client:
    """Drives the engine with the mix and stamps every token it serves."""

    def __init__(self, spec: "harness.Spec", eng, pods: int, slots: int,
                 max_len: int) -> None:
        self.spec, self.eng = spec, eng
        self.traffic = spec.cell.traffic
        self.pods, self.slots, self.max_len = pods, slots, max_len
        self.free_sids = list(range(pods * slots))[::-1]
        self.active: Dict[int, Turn] = {}          # sid -> turn in service
        self.sessions: List[Session] = []
        self.due: List[Turn] = []                  # turns not yet submitted
        self.admit: List[Session] = []             # sessions awaiting a slot
        self.next_idx = 0
        self.steps: List[tuple] = []               # (pod, t0, t1, lengths)
        self.lateness: List[float] = []
        self.admission_wait: List[float] = []
        self._wrap()

    # -- probes around the calls into each layer ----------------------------
    def _wrap(self) -> None:
        be, win = self.eng.backend, self.spec.window
        step, transfer, ensure = be.step, be.transfer, be.ensure
        drain = self.eng.certifier.drain
        self.certify: List[tuple] = []
        self.moves: List[tuple] = []

        def timed_step(pod, sids):
            st = be.stores[pod]
            lengths = [st.sessions[s].length + 1 for s in sids]
            with win.span("bench.decode"):
                t0 = time.perf_counter()
                out = step(pod, sids)
                t1 = time.perf_counter()
            self.steps.append((pod, t0, t1, lengths))
            for sid, tok in out.items():
                turn = self.active.get(sid)
                if turn is not None and not turn.done:
                    turn.stamps.append(t1)
                    turn.session.tokens.append(int(tok))
                    if turn.session.slot < 0:
                        turn.session.slot = (pod * st.n_slots
                                             + st.sessions[sid].slot)
            return out

        def prompted_ensure(pod, sid, length):
            # the program takes no prompt: a new session's first input
            # token is the last token of its fresh cache entry
            ensure(pod, sid, length)
            turn, st = self.active.get(sid), be.stores[pod]
            if (turn is not None and not turn.session.prompted
                    and st.has(sid) and st.sessions[sid].length == 0):
                st.sessions[sid].last_token = turn.session.prompt
                turn.session.prompted = True

        def timed_transfer(src, dst, sid):
            with win.span("bench.kv_move"):
                t0 = time.perf_counter()
                out = transfer(src, dst, sid)
                if win.trace:
                    import jax

                    jax.block_until_ready(be.stores[dst].caches)
                t1 = time.perf_counter()
            self.moves.append((src, dst, t0, t1))
            turn = self.active.get(sid)
            if turn is not None:
                turn.session.migrated = True
            return out

        cert = self.eng.certifier
        self.cert_checked = self.cert_bad = 0

        def timed_drain(pod):
            entries = list(cert.pending[pod])
            with win.span("bench.certify"):
                t0 = time.perf_counter()
                out = drain(pod)
                t1 = time.perf_counter()
            if entries:
                self.certify.append((pod, t0, t1, len(entries)))
                # a forward passes iff its snapshot epoch is the session's
                # epoch in the store the drain read
                passed = {id(r) for r in out[0]}
                for req, epoch in entries:
                    want = int(cert.store.versions[req.sid]) == epoch
                    self.cert_bad += want != (id(req) in passed)
                self.cert_checked += len(entries)
            return out

        be.step, be.transfer = timed_step, timed_transfer
        be.ensure = prompted_ensure
        self.eng.certifier.drain = timed_drain

    # -- traffic -------------------------------------------------------------
    def new_session(self) -> Session:
        s = make_session(self.traffic, self.spec.seed, self.next_idx,
                         self.pods, self.max_len,
                         self.eng.backend.cfg.vocab_size)
        self.next_idx += 1
        self.sessions.append(s)
        return s

    def occupancy(self) -> List[int]:
        occ = [0] * self.pods
        for pod in self.eng.session_home.values():
            occ[pod] += 1
        return occ

    def start_session(self, s: Session, due: float) -> None:
        s.sid = self.free_sids.pop()
        k = s.start_turn
        self.due.append(Turn(s, k, s.first_n or s.turn_lengths[k], due))

    def seed_live(self, t0: float) -> None:
        """Open loop: the sessions the arrival process would have live when
        the window opens, each at a phase drawn from its own stream: a turn
        index, then thinking (the turn is due an exponential think time
        after ``t0``) or decoding (some of the turn's tokens are left), in
        proportion to the think time and the turn's decode time at
        ``phase_step_s`` a token.  They start with empty caches."""
        think = self.traffic["think_s_mean"]
        for _ in range(self.traffic.get("initial_sessions", 0)):
            s = self.new_session()
            rng = rng_for(self.spec.seed, 4, s.idx)
            k = int(rng.integers(len(s.turn_lengths)))
            n = s.turn_lengths[k]
            decode = n * self.traffic["phase_step_s"]
            s.start_turn = k
            if rng.random() < think / (think + decode):
                s.arrival, s.first_n = t0 + rng.exponential(think), n
            else:
                s.arrival, s.first_n = t0, int(rng.integers(1, n + 1))
            self.admit.append(s)

    def _origin(self, turn: Turn, occ: List[int]) -> int:
        s = turn.session
        home = s.new_home if 0 <= s.move_turn <= turn.index else s.home
        if s.origin_u[turn.index] < self.traffic["home_share"]:
            origin = home
        else:
            origin = (home + s.origin_pick[turn.index]) % self.pods
        owner = self.eng.session_home.get(s.sid, origin)
        if origin != owner and occ[origin] >= self.slots:
            origin = owner
        return origin

    def submit(self, turn: Turn, now: float, occ: List[int]) -> None:
        from repro.serve.engine import Request

        origin = self._origin(turn, occ)
        turn.req = Request(sid=turn.session.sid, origin=origin,
                           n_tokens=turn.n)
        self.lateness.append(now - max(turn.due, turn.ready))
        self.active[turn.session.sid] = turn
        turn.session.turns.append(turn)
        with self.spec.window.span("bench.submit"):
            self.eng.submit(turn.req)

    def submit_due(self, now: float) -> None:
        ready = [t for t in self.due if t.due <= now]
        if not ready:
            return
        self.due = [t for t in self.due if t.due > now]
        occ = self.occupancy()
        for turn in sorted(ready, key=lambda t: t.due):
            self.submit(turn, now, occ)
            occ = self.occupancy()

    def admit_waiting(self, now: float) -> None:
        reserve = self.traffic.get("reserve_slots", 0)
        occ = self.occupancy()
        waiting = []
        for s in self.admit:
            if (s.arrival <= now and self.free_sids
                    and occ[s.home] < self.slots - reserve
                    and max(occ) < self.slots):
                self.start_session(s, s.arrival)
                self.due[-1].ready = now
                self.admission_wait.append(now - s.arrival)
                self.submit_due(now)
                occ = self.occupancy()
            else:
                waiting.append(s)
        self.admit = waiting

    def harvest(self, now: float) -> None:
        closed = self.traffic["loop"] == "closed"
        for sid, turn in list(self.active.items()):
            if not turn.done:
                continue
            del self.active[sid]
            s = turn.session
            t_end = turn.stamps[-1]
            nxt = turn.index + 1
            if nxt < len(s.turn_lengths):
                self.due.append(Turn(s, nxt, s.turn_lengths[nxt],
                                     t_end + s.think[nxt]))
                continue
            with self.spec.window.span("bench.evict"):
                self.eng.evict_session(sid)
            self.free_sids.insert(0, sid)
            if closed:
                self.start_session(self.new_session(), t_end)

    def arrivals(self, t_from: float, t_to: float) -> None:
        """Open loop: queue the sessions that arrive in ``[t_from, t_to)``."""
        rate = self.traffic["sessions_per_s"]
        while True:
            if not hasattr(self, "_next_arrival"):
                self._arrival_rng = rng_for(self.spec.seed, 2)
                self._next_arrival = t_from + self._arrival_rng.exponential(
                    1.0 / rate)
            if self._next_arrival >= t_to:
                return
            s = self.new_session()
            s.arrival = self._next_arrival
            self.admit.append(s)
            self._next_arrival += self._arrival_rng.exponential(1.0 / rate)

    def busy(self) -> bool:
        return any(self.eng.queues) or self.eng.certifier.has_pending()

    def run(self, t_stop: float) -> None:
        """Serve until ``t_stop`` on the host clock."""
        win = self.spec.window
        open_loop = self.traffic["loop"] == "open"
        last = time.perf_counter()
        while True:
            now = time.perf_counter()
            win.poll(now)
            if now >= t_stop:
                return
            with win.span("bench.traffic"):
                if open_loop:
                    self.arrivals(last, now)
                    self.admit.sort(key=lambda x: x.arrival)
                    self.admit_waiting(now)
                self.submit_due(now)
            last = now
            if self.busy():
                with win.span("bench.engine"):
                    self.eng.run_step()
                with win.span("bench.traffic"):
                    self.harvest(time.perf_counter())
            else:
                nxt = min([t.due for t in self.due] + [t_stop]
                          + [s.arrival for s in self.admit]
                          + ([self._next_arrival] if open_loop
                             and hasattr(self, "_next_arrival") else []))
                time.sleep(max(0.0, min(nxt - now, 0.005)))


# ---------------------------------------------------------------------------

def warm_up(eng, client: Client, pods: int, slots: int) -> None:
    """Compile what the window runs, on every pod: the decode step, the KV
    column export/import, and the certify buckets the traffic can reach."""
    import jax
    from repro.core.stm import Transaction, VersionedStore, validate_batch
    from repro.serve.engine import Request

    top = pods * slots - 1          # the highest sid the pool hands out

    def move_column():
        for st in eng.backend.stores:
            st.alloc(top)
            blob = st.export_session(top)
            st.free(top)
            st.import_session(blob)
            st.free(top)

    if pods > 1:
        # the column export and import, then the decode step, each for the
        # caches as a move or a step leaves them
        move_column()
    # one step compiles the decode for fresh caches; with moves, a second
    # one for the caches a step leaves behind
    steps = 1 if pods == 1 else 2
    for pod in range(pods):
        eng.submit(Request(sid=top - pod, origin=pod, n_tokens=steps))
    for _ in range(steps):
        eng.run_step()
    for pod in range(pods):
        eng.evict_session(top - pod)
    if pods > 1:
        move_column()
        store = VersionedStore(eng.certifier.store.n_items)
        rows = 8
        while rows <= pods * slots:
            txns = []
            for i in range(rows):
                t = Transaction(txid=i + 1, origin=0)
                t.log_read(i % store.n_items, 0)
                txns.append(t)
            validate_batch(store, txns, backend=eng.certifier.backend)
            rows *= 2
    jax.block_until_ready([st.caches for st in eng.backend.stores])


def _q(values: List[float], q: float) -> float:
    if len(values) < 2:
        return float(values[0]) if values else math.nan
    return float(statistics.quantiles(values, n=100, method="inclusive")
                 [int(round(q * 100)) - 1])


def run(spec: "harness.Spec") -> "harness.Result":
    import jax
    from repro.launch import serve as launch_serve
    from repro.models.common import param_shapes

    cell = spec.cell
    harness.stamp("imports")
    cfg = model_config(cell.config)
    sv = cell.config["serving"]
    pods = int(cell.entry["chips"]) * int(sv.get("pods_per_chip", 1))
    slots, max_len = int(sv["slots_per_pod"]), int(sv["max_len"])

    params = jax.block_until_ready(weights.make_params(
        param_shapes(cfg), spec.seed, cfg.compute_dtype()))
    harness.stamp("weights")
    real_init = launch_serve.init_params
    launch_serve.init_params = lambda *a, **k: params
    try:
        eng = launch_serve.build_engine(
            cfg, backend="real", pods=pods, sessions=slots, max_len=max_len,
            seed=spec.seed, devices=spec.devices if pods > 1 else None)
    finally:
        launch_serve.init_params = real_init
    del params
    harness.stamp("engine")
    client = Client(spec, eng, pods, slots, max_len)
    warm_up(eng, client, pods, slots)
    harness.stamp("warm_up")
    client.steps.clear()
    client.certify.clear()
    client.moves.clear()

    win = spec.window
    closed = cell.traffic["loop"] == "closed"
    t_open = time.perf_counter()
    if closed:
        for _ in range(pods * slots):
            client.start_session(client.new_session(), t_open)
    setup_s = t_open - spec.t_start
    t0 = win.open()
    if not closed:
        client.seed_live(t0)
    t_end = t0 + spec.seconds
    client.run(t_end)
    win.close()
    peaks = harness.memory_peaks(spec.devices)

    # -- end-to-end metrics, from the client's stamps ------------------------
    turns = [t for s in client.sessions for t in s.turns]
    due_in = [t for t in turns if t0 <= t.due < t_end] + \
        [t for t in client.due if t0 <= t.due < t_end] + \
        [Turn(s, 0, 0, s.arrival) for s in client.admit
         if t0 <= s.arrival < t_end]
    ttft = []
    for t in due_in:
        first = t.stamps[0] if t.stamps and t.stamps[0] <= t_end else None
        ttft.append((first if first is not None else t_end) - t.due)
    tokens_in = 0
    gaps = []
    for t in turns:
        prev = None
        for st in t.stamps:
            if t0 <= st <= t_end:
                tokens_in += 1
                if prev is not None:
                    gaps.append(st - prev)
            prev = st
    completed = sum(1 for t in turns if t.done and t.stamps[-1] <= t_end
                    and t0 <= t.due < t_end)
    steps_in = [st for st in client.steps if t0 <= st[1] and st[2] <= t_end]
    e2e = {"setup_s": setup_s,
           "tokens_per_s": tokens_in / (t_end - t0),
           "tpot_p95_ms": 1e3 * _q(gaps, 0.95),
           "ttft_p95_ms": 1e3 * _q(ttft, 0.95)}
    late = client.lateness or [0.0]
    lines = [
        f"serve: model={cfg.name} layers={cfg.n_layers} pods={pods} "
        f"slots={slots} max_len={max_len} loop={cell.traffic['loop']}",
        f"window: seconds={t_end - t0!r} steps={len(client.steps)} "
        f"tokens={tokens_in} compiles_in_window={len(win.compiled)} "
        f"{sorted(set(win.compiled))}",
        f"engine: steps_per_pod={len(steps_in) / pods!r} "
        f"pod_step_ms_mean={1e3 * sum(b - a for _, a, b, _ in steps_in) / max(1, len(steps_in))!r} "
        f"live_sessions_end={len(eng.session_home)}",
        f"turns: due_in_window={len(due_in)} completed={completed} "
        f"still_open={len(due_in) - completed} "
        f"sessions_started={client.next_idx} "
        f"waiting_admission={len(client.admit)}",
        f"generator lateness: p95_s={_q(late, 0.95)!r} max_s={max(late)!r} "
        f"admission_wait_max_s={max(client.admission_wait, default=0.0)!r}",
        f"routing: forwards={eng.metrics.forwards} "
        f"acquires={eng.router.metrics.acquires} "
        f"kv_moves={len(client.moves)} cert_batches="
        f"{eng.certifier.metrics.batches}",
        "memory: peak_bytes_in_use per chip " + " ".join(map(str, peaks)),
        harness.setup_line(spec.t_start),
    ]
    records = {"model": dict(cell.config["model"]), "steps": client.steps,
               "window": win.measured() if win.trace else (t0, t_end),
               "trace_window": (win.trace_t0, win.trace_t1),
               "certify": client.certify, "moves": client.moves,
               "pods": pods}

    # -- correctness: the sampled sessions against the plain reference -------
    chk = cell.config["check"]
    sample = sample_sessions(client.sessions, spec.seed, t_end,
                             chk["per_slot_tokens"])
    seqs = [[s.prompt] + served_until(s, t_end)[:n] for s, n in sample]
    cert_bad, cert_checked = client.cert_bad, client.cert_checked
    del eng, client
    gc.collect()
    t_ref = time.perf_counter()
    checks = check_served(cell.config, spec.seed, seqs, control=spec.control)
    checks.append(harness.Check("certify_verdicts_off", cert_bad, 0))
    lines.append(f"certify: forwards_checked={cert_checked} off={cert_bad}")
    records["check_seqs"] = seqs
    lines.append(f"check: sessions={len(seqs)} slots="
                 f"{len({s.slot for s, _ in sample})} tokens="
                 f"{sum(len(q) - 1 for q in seqs)} longest="
                 f"{max((len(q) - 1 for q in seqs), default=0)} migrated="
                 f"{sum(s.migrated for s, _ in sample)} control={spec.control} "
                 f"reference_s={time.perf_counter() - t_ref!r}")
    return harness.Result(end_to_end=e2e, attempted=len(due_in), failed=0,
                          checks=checks, records=records, lines=lines,
                          memory_peak_bytes=peaks)


def served_until(s: Session, t_end: float) -> List[int]:
    """The tokens of the session's turns finished by ``t_end``."""
    n = 0
    for t in s.turns:
        if t.done and t.stamps[-1] <= t_end:
            n += t.n
        else:
            break
    return s.tokens[:n]


def sample_sessions(sessions: List[Session], seed: int, t_end: float,
                    per_slot: int) -> List[tuple]:
    """What the check compares, as ``(session, tokens)``: the longest
    served session whole, up to four whose cache crossed chips whole, and
    from every slot one more session drawn from the seed, its first
    ``per_slot`` served tokens.  A slot's sessions started at other steps
    than its neighbours', so each sat at its own position in the batch."""
    done = [s for s in sessions if served_until(s, t_end)]
    if not done:
        return []
    done.sort(key=lambda s: (-len(served_until(s, t_end)), s.idx))
    whole = [done[0]] + [s for s in done[1:] if s.migrated][:4]
    by_slot: Dict[int, List[Session]] = {}
    for s in done:
        if s not in whole:
            by_slot.setdefault(s.slot, []).append(s)
    rng = rng_for(seed, 3)
    pick = [(s, len(served_until(s, t_end))) for s in whole]
    for slot in sorted(by_slot):
        group = by_slot[slot]
        pick.append((group[int(rng.integers(len(group)))], per_slot))
    return pick


def check_served(config: Dict, seed: int, seqs: List[List[int]],
                 control: bool = False) -> List["harness.Check"]:
    """The widest reference logit gap of the served tokens (with
    ``control``, of the float8 control's first choices instead) against
    the configuration's limit, and the number of tokens compared."""
    from reference import glm4

    chk = config["check"]
    n = sum(len(q) - 1 for q in seqs)
    if not n:
        return [harness.Check("served_tokens_checked", 0, chk["tokens"],
                              higher_fails=False)]
    got = glm4.gaps(config["model"], seed, seqs, control=control)
    widest = max(float(g.max()) for g in got if g.size)
    return [harness.Check("max_logit_gap", widest, chk["max_logit_gap"]),
            harness.Check("served_tokens_checked", n, chk["tokens"],
                          higher_fails=False)]

"""Serving launcher: batched prefill + decode with continuous batching,
hardened for faults *and* load.

``python -m repro.launch.serve --arch qwen3-0.6b --requests 8`` runs a small
request stream through the engine on CPU (smoke config); on a pod the same
engine serves the full config with the production mesh.
``--traffic poisson`` drives the same engine open-loop on a virtual clock
(seeded arrivals, analytic capacity) — the overload-control smoke.

Engine: fixed decode batch of slots; requests queue in, prefill fills a
slot's state, decode steps the whole batch every tick, finished slots are
recycled (continuous batching).  With ``--pcilt`` the decode runs the
paper's converted table path (``core.serving.convert_mamba_decode``) under a
:class:`repro.core.serving.HealthMonitor`: table integrity is spot-checked
one layer per tick, and a breached layer is demoted to its exact dense
fake-quant oracle — serving continues, degraded and logged, never wrong.

Resilience contract (``docs/resilience.md`` has the full matrix):

* **tick-level try/restore** — every committed tick checkpoints the full
  engine state (cache, tokens, slots, queue, pending arrivals, request
  fields) into a bounded ring; any step fault restores the latest
  checkpoint and replays, up to ``max_restarts`` (``Supervisor`` semantics,
  applied to serving);
* **never wrong** — a table-corruption breach detected at tick ``k`` may
  have poisoned commits back to the breached layer's ``last_verified``
  tick, so the engine rolls back *to that tick* and replays with the layer
  demoted: every token a request ends up with was produced by verified
  tables or the dense oracle;
* **deadlines** — a request exceeding ``deadline_s`` is evicted, its slot
  state zeroed, and requeued with exponential backoff for up to
  ``max_retries`` attempts before it is failed (bounded, never lost
  silently);
* **watchdog** — decode tick wall times feed a
  :class:`repro.runtime.StepWatchdog`; straggler ticks land in the stats;
* **accounting** — every request ends in exactly one outcome
  (``served`` / ``degraded`` / ``failed`` / ``rejected``), derived from
  request state at the end so checkpoint replays can never double-count.

Overload contract (``docs/serving.md`` has the full matrix):

* **bounded admission** — ``queue_limit`` caps the queue; a request
  arriving at a full queue is shed *at admission* with the typed
  ``rejected`` outcome (never a timeout discovered minutes later), and the
  estimated-service-time test additionally rejects requests whose deadline
  is already unmeetable given the backlog (doomed work is refused, not
  half-served);
* **EDF scheduling** — free slots take the eligible queued request with
  the earliest deadline (no-deadline requests sort last, FIFO tie-break),
  minimizing deadline misses under load;
* **queue-side deadline eviction** — a request that exceeds its deadline
  *while still queued* is evicted there (counted in
  ``queue_evictions``) instead of burning prefill ticks on a doomed
  attempt;
* **backpressure telemetry** — every tick appends a structured record
  (queue depth, slot occupancy, eviction counters, tick seconds) to
  ``stats["telemetry"]``; ``stats`` also carries the shed rate and the
  resident table bytes.

All time flows through an injectable ``clock`` (``Engine(clock=...)``,
default :class:`repro.runtime.WallClock`); a
:class:`repro.runtime.VirtualClock` plus ``step_cost_s`` makes every
deadline/backoff/arrival path deterministic — the CI traffic smoke runs
thousands of virtual seconds in milliseconds.

``--chaos`` drives the engine through every injected fault class
(scheduled tick fault, NaN-poisoned state, corrupted projection stack,
flipped head ``seg_idx`` pointers, garbled autotune cache) and exits
non-zero if any request is lost or the served tokens diverge from a
fault-free reference run — the CI smoke for the resilience layer.
``--chaos --traffic ...`` composes the two: faults injected mid-burst must
uphold both contracts at once.

``--chaos-drift`` exercises the calibration-drift sentinel: PCILT decode
runs *monitored* (the fused kernels emit in-kernel saturation counters), a
mid-serve parameter drift pushes one layer's activations out of the
calibrated range without corrupting a single table byte, and the contract
requires detect (typed ``drift`` demotion) -> rollback -> online
recalibration (tables rebuilt at the observed range, checksums
re-recorded, ``rehoist(verify=True)``) -> repromote, with undrifted tokens
identical to a fault-free run.  ``--no-sentinel`` is the zero-overhead
opt-out (executors compile without counter outputs).
"""

from __future__ import annotations

import argparse
import logging
import math
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import build_model
from repro.nn.module import materialize, shape_structs
from repro.launch.steps import make_decode_step, make_prefill_step, make_ctx
from repro.runtime import StepWatchdog, WallClock, span

log = logging.getLogger("repro.serve")

#: every request ends in exactly one of these
OUTCOMES = ("served", "degraded", "failed", "rejected")


class _Degraded(Exception):
    """Health breach: roll back to ``target_tick`` and replay demoted."""

    def __init__(self, target_tick: int, events):
        super().__init__(f"health breach; replay from tick {target_tick}")
        self.target_tick = target_tick
        self.events = events


class Request:
    def __init__(self, rid: int, prompt: np.ndarray, max_new: int,
                 deadline_s: Optional[float] = None, max_retries: int = 2):
        self.rid = rid
        self.prompt = np.asarray(prompt)
        self.max_new = max_new
        self.deadline_s = deadline_s
        self.max_retries = max_retries
        self.out: List[int] = []
        self.done = False
        #: queued | active | served | degraded | failed | rejected
        self.outcome = "queued"
        self.retries = 0
        #: True when any committed token was produced under demotion
        self.degraded = False
        self.t_arrive = 0.0  # when the request hit the engine (clock domain)
        self.t_enqueue = 0.0  # start of the current queued attempt
        self.t_admit = 0.0  # when the current attempt's prefill began
        self.t_done = 0.0  # when a terminal outcome was assigned
        self.not_before = 0.0  # backoff gate for requeued requests


class Engine:
    """Slot-based continuous batching with checkpointed fault recovery and
    bounded-admission overload control."""

    def __init__(self, cfg, max_len: int = 256, slots: int = 4, mesh=None, *,
                 pcilt: bool = False, pcilt_bundle: Optional[Dict] = None,
                 oracle_every: int = 4, max_restarts: int = 8,
                 ckpt_keep: Optional[int] = None, chaos: Optional[Dict] = None,
                 clock=None, queue_limit: Optional[int] = None,
                 step_cost_s: Optional[float] = None, sentinel: bool = True):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.max_len = max_len
        self.slots = slots
        self.mesh = mesh
        self.max_restarts = max_restarts
        #: injectable time source (`.time()` / `.sleep(s)`); the default is
        #: the wall clock — tests and the traffic bench pass a VirtualClock
        self.clock = clock if clock is not None else WallClock()
        #: bounded admission queue: None = unbounded (the closed-loop
        #: `run()` semantics), an int caps the queue and sheds beyond it
        self.queue_limit = queue_limit
        #: simulated per-step service time: each engine step advances the
        #: clock by this much (VirtualClock benches/CI); None = real time
        self.step_cost_s = step_cost_s
        self.params = materialize(self.model.param_specs(), jax.random.PRNGKey(0))
        cspecs = self.model.cache_specs(slots, max_len)
        self.cache = materialize(cspecs, jax.random.PRNGKey(1))
        self.cache = dict(self.cache, pos=jnp.asarray(0, jnp.int32))
        self.decode = jax.jit(make_decode_step(cfg, mesh))
        self.active: List[Optional[Request]] = [None] * slots
        self.tokens = np.zeros((slots, 1), np.int32)
        #: chaos schedule {step_count: [fn(engine)]} keyed on the monotone
        #: ``self.steps`` counter (prefill + decode steps; never rewound by a
        #: restore); entries pop one-shot, so a checkpoint replay of a
        #: faulted step runs clean
        self.chaos = dict(chaos or {})
        self.ckpts: deque = deque(
            maxlen=ckpt_keep or (int(cfg.n_layers) + 4))
        self.queue: List[Request] = []
        self._requests: List[Request] = []
        self._pending: List[Tuple[float, Request]] = []
        self.tick = 0
        self.steps = 0  # monotone prefill+decode step count (chaos clock)
        self.prefill_ticks = 0
        self.restarts = 0
        self.rollbacks = 0
        self.queue_evictions = 0
        self.slot_evictions = 0
        self.telemetry: List[Dict] = []
        self._tick_ema: Optional[float] = None
        #: True while ``_prefill_into_slot`` steps a prompt (span phase)
        self._in_refill = False
        #: ``prefill_ticks`` at the last telemetry record
        self._refills_recorded = 0

        self.pdecode = None
        self.monitor = None
        #: calibration-drift sentinel: decode steps run monitored
        #: (``with_stats=True`` — in-kernel saturation counters) and feed the
        #: monitor's per-layer drift EWMAs.  ``sentinel=False`` is the
        #: zero-overhead opt-out: the unmonitored executor compiles without
        #: counter outputs, bit-identical to pre-sentinel serving.
        self.sentinel = bool(sentinel) and pcilt
        self._last_sat = None
        if pcilt:
            from repro.core.serving import (HealthMonitor, PCILTMambaDecode,
                                            convert_mamba_decode)

            if cfg.pcilt is None:
                raise ValueError(
                    "Engine(pcilt=True) requires cfg.pcilt (a configs.base."
                    "PCILTConfig) — set cfg = dataclasses.replace(cfg, "
                    "pcilt=PCILTConfig(...)) before constructing")
            # Under a mesh only the tables' segment axis is sharded (each
            # projection psums its partial fetch sums); activations stay
            # replicated, so calibration and every other op compute what
            # one device computes.
            ctx = make_ctx(None, None, decode=True)
            if pcilt_bundle is not None:
                self.pdecode = PCILTMambaDecode(self.model, pcilt_bundle, ctx)
            else:
                calib = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                           cfg.vocab)
                self.pdecode = convert_mamba_decode(
                    self.model, self.params, calib, ctx, head="shared",
                    mesh=mesh)
            self.monitor = HealthMonitor(self.pdecode, self.params,
                                         oracle_every=oracle_every)

    # -- stepping ------------------------------------------------------------

    def _raw_step(self):
        with span("step.dispatch"):
            toks = jnp.asarray(self.tokens)
            if self.pdecode is None:
                return self.decode(self.params, self.cache, toks)
            lmask, hmask = self.monitor.ok_masks()
            if self.sentinel:
                logits, new_cache, self._last_sat = self.pdecode.step(
                    self.params, self.cache, toks, lmask, hmask,
                    with_stats=True)
            else:
                logits, new_cache = self.pdecode.step(
                    self.params, self.cache, toks, lmask, hmask)
            if self.cfg.padded_vocab > self.cfg.vocab:  # never sample padding
                neg = jnp.full((self.cfg.padded_vocab - self.cfg.vocab,),
                               -1e30, logits.dtype)
                logits = logits.at[..., self.cfg.vocab:].set(neg)
            return logits, new_cache

    def _step(self):
        phase = "prefill" if self._in_refill else "decode"
        with span("step", phase=phase, step=self.steps):
            # chaos clock: fire every due injection exactly once, before the
            # forward — a raise here surfaces as a step fault (restore +
            # replay)
            for k in sorted(k for k in self.chaos if k <= self.steps):
                for act in self.chaos.pop(k):
                    act(self)
            self.steps += 1
            if self.step_cost_s is not None:
                self.clock.sleep(self.step_cost_s)  # simulated service time
            logits, new_cache = self._raw_step()
            # finite gate BEFORE committing: NaN/Inf outputs (poisoned state,
            # numerical blowup) trigger restore-and-replay, never a sampled
            # token.  The recurrent state must be gated too, not just the
            # logits: the PCILT path quantizes activations to integer table
            # indices, which *launders* NaN into a valid (wrong) lookup —
            # poisoned ssd state yields finite logits while the corruption
            # persists in the cache.
            with span("step.gate"):
                checks = [jnp.all(jnp.isfinite(logits))]
                checks += [jnp.all(jnp.isfinite(l))
                           for l in jax.tree.leaves(new_cache)
                           if jnp.issubdtype(l.dtype, jnp.floating)]
                if not bool(jnp.all(jnp.stack(checks))):
                    raise RuntimeError(
                        "non-finite decode outputs or state (NaN/Inf)")
            self.cache = new_cache
            with span("step.sample"):
                return np.asarray(jnp.argmax(logits, axis=-1))

    def _prefill_into_slot(self, slot: int, req: Request):
        """Feed the prompt through decode steps (teacher-forced prefill).

        Production pods run the fused ``prefill_step`` over the whole prompt;
        the slot engine replays tokens through the decode path so a single
        compiled step serves both phases (classic small-deployment trade).

        Concurrently active slots keep *generating* during these ticks —
        their cache advances either way, so their sampled tokens must be
        committed, not dropped (dropping them skipped every token a slot
        sampled while a neighbor prefilled).  The step that consumes the
        final prompt token emits the request's first generated token."""
        with span("refill", rid=req.rid, slot=slot,
                  prompt_len=len(req.prompt)):
            req.outcome = "active"
            req.t_admit = self.clock.time()
            # an idle slot still steps with the batch (its outputs dropped),
            # so its recurrent state is garbage by now — start from a clean
            # slate or the request's tokens depend on what the slot did while
            # unowned
            self._reset_slot(slot)
            last = 0
            self._in_refill = True
            try:
                for t in req.prompt:
                    self.tokens[slot, 0] = int(t)
                    out = self._step()
                    self.prefill_ticks += 1
                    self._commit_tokens(out, skip=slot)
                    last = int(out[slot])
            finally:
                self._in_refill = False
            self.active[slot] = req
            req.out.append(last)
            self.tokens[slot, 0] = last
            self._finish_if_done(slot)

    def _commit_tokens(self, nxt, skip: Optional[int] = None):
        # tainted = some layer was online-recalibrated: tokens are correct
        # under the *new* tables but no longer bit-comparable to the original
        # conversion, so they carry the degraded marking too
        degraded_now = self.monitor is not None and (
            self.monitor.degraded or self.monitor.tainted)
        with span("commit") as sp:
            committed = 0
            for s, req in enumerate(self.active):
                if req is None or s == skip:
                    continue
                tok = int(nxt[s])
                req.out.append(tok)
                self.tokens[s, 0] = tok
                committed += 1
                if degraded_now:
                    req.degraded = True
                self._finish_if_done(s)
            sp.set_metadata(tokens=committed)

    def _finish_if_done(self, s: int):
        req = self.active[s]
        if req is not None and len(req.out) >= req.max_new:
            req.done = True
            req.outcome = "degraded" if req.degraded else "served"
            req.t_done = self.clock.time()
            self.active[s] = None
            self._reset_slot(s)

    def _reset_slot(self, s: int):
        """Zero one slot's recurrent/cache state so a recycled (or evicted)
        slot can never leak a previous request's context into the next."""
        def z(a):
            if hasattr(a, "ndim") and a.ndim >= 2 and a.shape[1] == self.slots:
                return a.at[:, s].set(0)
            return a

        self.cache = dict(self.cache,
                          layers=jax.tree.map(z, self.cache["layers"]))

    # -- checkpoint ring -----------------------------------------------------

    def _checkpoint(self):
        """Snapshot the full engine state (jax arrays are immutable — holding
        the refs *is* the snapshot; host-side state is copied)."""
        with span("checkpoint"):
            self.ckpts.append({
                "tick": self.tick,
                "cache": self.cache,
                "tokens": self.tokens.copy(),
                "active": list(self.active),
                "queue": list(self.queue),
                "pending": list(self._pending),
                "queue_evictions": self.queue_evictions,
                "slot_evictions": self.slot_evictions,
                "reqs": {r.rid: (list(r.out), r.done, r.outcome, r.retries,
                                 r.degraded, r.t_admit, r.not_before,
                                 r.t_arrive, r.t_enqueue, r.t_done)
                         for r in self._requests},
            })

    def _restore(self, target_tick: int):
        """Restore the newest checkpoint at or before ``target_tick``
        (falling back to the oldest retained — the ring bounds how far back
        a restore can reach, and the monitor's per-tick verification bounds
        how far back one ever *needs* to reach)."""
        snaps = [c for c in self.ckpts if c["tick"] <= target_tick]
        snap = snaps[-1] if snaps else self.ckpts[0]
        # drop now-stale snapshots of ticks the replay will redo
        keep = [c for c in self.ckpts if c["tick"] <= snap["tick"]
                and c is not snap] + [snap]
        self.ckpts = deque(keep, maxlen=self.ckpts.maxlen)
        self.cache = snap["cache"]
        self.tokens = snap["tokens"].copy()
        self.active = list(snap["active"])
        self.queue = list(snap["queue"])
        self._pending = list(snap["pending"])
        self.queue_evictions = snap["queue_evictions"]
        self.slot_evictions = snap["slot_evictions"]
        for r in self._requests:
            (out, done, outcome, retries, degraded, t_admit, nb,
             t_arrive, t_enqueue, t_done) = snap["reqs"][r.rid]
            r.out, r.done, r.outcome = list(out), done, outcome
            r.retries, r.degraded, r.t_admit, r.not_before = \
                retries, degraded, t_admit, nb
            r.t_arrive, r.t_enqueue, r.t_done = t_arrive, t_enqueue, t_done
        self.tick = snap["tick"]
        # telemetry for replayed ticks will be re-recorded
        self.telemetry = [e for e in self.telemetry if e["tick"] < self.tick]
        if self.monitor is not None:
            # a verification recorded at a now-rewound tick no longer vouches
            # for any committed token — clamp so a later breach rolls back
            # far enough
            np.minimum(self.monitor.last_verified, self.tick,
                       out=self.monitor.last_verified)
            self.monitor.head_last_verified = min(
                self.monitor.head_last_verified, self.tick)
        log.warning("restored engine state at tick %d", self.tick)

    # -- admission / scheduling ----------------------------------------------

    def _est_ticks(self, req: Request) -> int:
        """Engine steps one attempt of ``req`` costs end to end (prefill
        replays the prompt through the decode path, then one step per
        generated token)."""
        return len(req.prompt) + req.max_new

    def _est_turnaround_s(self, req: Request) -> Optional[float]:
        """Crude service-time estimate for an arriving request: the backlog
        ahead of it (active remainders + queued attempts, spread over the
        slots) plus its own attempt, priced at the observed per-tick EMA.
        ``None`` until a tick has been measured (never reject blind)."""
        if self._tick_ema is None:
            return None
        backlog = sum(self._est_ticks(r) for r in self.queue)
        backlog += sum(max(0, r.max_new - len(r.out))
                       for r in self.active if r is not None)
        return (backlog / self.slots + self._est_ticks(req)) * self._tick_ema

    def _submit(self, req: Request, now: float) -> bool:
        """Admission control: enqueue or shed with the typed ``rejected``
        outcome.  Two tests, both cheap and both *at the door*:

        * **queue depth** — a full bounded queue sheds immediately;
        * **estimated service time** — a deadline the backlog already makes
          unmeetable is refused rather than admitted, prefillled, and
          evicted later (doomed work is the most expensive kind under
          overload).
        """
        req.t_arrive = req.t_enqueue = now
        if self.queue_limit is not None and len(self.queue) >= self.queue_limit:
            req.done = True
            req.outcome = "rejected"
            req.t_done = now
            log.warning("req %d rejected: queue full (%d >= %d)",
                        req.rid, len(self.queue), self.queue_limit)
            return False
        if req.deadline_s is not None:
            est = self._est_turnaround_s(req)
            if est is not None and est > req.deadline_s:
                req.done = True
                req.outcome = "rejected"
                req.t_done = now
                log.warning("req %d rejected: estimated turnaround %.3fs > "
                            "deadline %.3fs", req.rid, est, req.deadline_s)
                return False
        req.outcome = "queued"
        self.queue.append(req)
        return True

    def _admit_arrivals(self, now: float):
        due = [p for p in self._pending if p[0] <= now]
        if due:
            self._pending = [p for p in self._pending if p[0] > now]
            for _, req in due:
                self._submit(req, now)

    def _edf_pick(self, now: float) -> Optional[int]:
        """Earliest-deadline-first: the eligible (not backing off) queued
        request with the soonest absolute deadline for its current attempt;
        no-deadline requests sort last, FIFO breaks ties."""
        best = None
        best_key = None
        for i, r in enumerate(self.queue):
            if r.not_before > now:
                continue
            d = (r.t_enqueue + r.deadline_s if r.deadline_s is not None
                 else math.inf)
            key = (d, i)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    # -- deadlines -----------------------------------------------------------

    def _enforce_deadlines(self):
        with span("deadlines"):
            now = self.clock.time()
            for s, req in enumerate(self.active):
                if req is None or req.deadline_s is None:
                    continue
                if now - req.t_admit <= req.deadline_s:
                    continue
                self.active[s] = None
                self._reset_slot(s)
                self.slot_evictions += 1
                req.out = []
                req.degraded = False
                req.retries += 1
                if req.retries > req.max_retries:
                    req.done = True
                    req.outcome = "failed"
                    req.t_done = now
                    log.error("req %d failed: deadline %.3fs exceeded %d "
                              "times", req.rid, req.deadline_s, req.retries)
                else:
                    req.not_before = now + 0.05 * (2 ** (req.retries - 1))
                    req.outcome = "queued"
                    # the fresh attempt's deadline window opens when the
                    # backoff expires — clocking it from the requeue instant
                    # would let a backoff longer than the deadline evict the
                    # request forever
                    req.t_enqueue = req.not_before
                    self.queue.append(req)
                    log.warning("req %d missed deadline; requeued (retry "
                                "%d/%d, backoff %.3fs)", req.rid, req.retries,
                                req.max_retries, req.not_before - now)
            # queue-side enforcement: a request past its attempt deadline
            # while *still queued* is evicted here — before it burns prefill
            # ticks on an attempt that cannot meet its deadline anyway
            still: List[Request] = []
            for req in self.queue:
                if req.deadline_s is None or \
                        now - req.t_enqueue <= req.deadline_s:
                    still.append(req)
                    continue
                self.queue_evictions += 1
                req.retries += 1
                if req.retries > req.max_retries:
                    req.done = True
                    req.outcome = "failed"
                    req.t_done = now
                    log.error("req %d failed: deadline %.3fs expired in queue "
                              "(%d attempts)", req.rid, req.deadline_s,
                              req.retries)
                else:
                    req.not_before = now + 0.05 * (2 ** (req.retries - 1))
                    req.t_enqueue = req.not_before  # window opens post-backoff
                    still.append(req)
                    log.warning("req %d deadline expired while queued; "
                                "attempt window reset (retry %d/%d)", req.rid,
                                req.retries, req.max_retries)
            self.queue = still

    # -- main loop -----------------------------------------------------------

    def run(self, requests: List[Request], greedy: bool = True):
        """Closed-loop serving: every request is offered at once (the
        pre-traffic semantics — what the chaos smoke and the resilience
        tests drive)."""
        now = self.clock.time()
        return self._serve([(now, r) for r in requests])

    def run_traffic(self, requests: List[Request],
                    arrivals: Sequence[float]):
        """Open-loop serving: ``requests[i]`` becomes visible at absolute
        clock time ``arrivals[i]`` (see ``runtime.traffic``).  The engine
        never sees a request before its arrival, and the arrival process
        never waits for the engine — offered load is fixed, which is what
        makes shed rate and tail latency honest under overload."""
        if len(requests) != len(arrivals):
            raise ValueError(
                f"{len(requests)} requests but {len(arrivals)} arrival "
                f"times — the traffic trace must cover every request")
        pending = sorted(zip((float(t) for t in arrivals), requests),
                         key=lambda p: p[0])
        return self._serve(pending)

    def _serve_tick(self, watchdog: StepWatchdog):
        """One iteration of the serving loop: admit and refill free slots,
        then one decode step, its health pass, commit, deadlines, telemetry
        and checkpoint (a tick with no active slot waits instead)."""
        t_tick = self.clock.time()
        now = t_tick
        with span("admit") as sp:
            self._admit_arrivals(now)
            admitted = 0
            for s in range(self.slots):
                if self.active[s] is not None or not self.queue:
                    continue
                i = self._edf_pick(now)
                if i is None:
                    break  # every queued request is backing off
                self._prefill_into_slot(s, self.queue.pop(i))
                admitted += 1
            sp.set_metadata(admitted=admitted)
        if not any(r is not None for r in self.active):
            if self.queue:
                self.clock.sleep(0.005)  # wait out shortest backoff
                self._enforce_deadlines()  # backoff may outlive one
            elif self._pending:
                nxt = min(t for t, _ in self._pending)
                self.clock.sleep(max(nxt - now, 1e-9))
            return
        t_step = self.clock.time()
        nxt = self._step()
        t_monitor = self.clock.time()
        if self.monitor is not None:
            breaches = self.monitor.on_tick(
                self.tick, sat=self._last_sat, rows=self.slots)
            if breaches:
                # commits since the breached layer was last verified may be
                # corrupt — rewind there and replay demoted.  Drift is
                # different: committed tokens were produced inside the
                # calibrated range (the counters fired on *this* tick's
                # activations), so it indicts only the current,
                # not-yet-committed tick.
                lv = [int(self.monitor.last_verified[e["layer"]])
                      for e in breaches
                      if e["layer"] is not None and e["kind"] != "drift"]
                lv += [int(self.monitor.head_last_verified)
                       for e in breaches if e["kind"] == "head"]
                lv += [self.tick for e in breaches if e["kind"] == "drift"]
                raise _Degraded(max(min(lv), 0), breaches)
        t_commit = self.clock.time()
        self._commit_tokens(nxt)
        self._enforce_deadlines()
        dt = self.clock.time() - t_tick
        watchdog.observe(self.tick, dt)
        self._tick_ema = (dt if self._tick_ema is None
                          else 0.9 * self._tick_ema + 0.1 * dt)
        occupied = sum(r is not None for r in self.active)
        entry = {
            "tick": self.tick,
            "t": self.clock.time(),
            "queue_depth": len(self.queue),
            "pending": len(self._pending),
            "active_slots": occupied,
            "occupancy": occupied / self.slots,
            "queue_evictions": self.queue_evictions,
            "slot_evictions": self.slot_evictions,
            "tick_s": dt,
            "refill_steps": self.prefill_ticks - self._refills_recorded,
            "step_s": t_monitor - t_step,
            "monitor_s": t_commit - t_monitor,
        }
        self._refills_recorded = self.prefill_ticks
        if self.sentinel and self.monitor is not None:
            entry["saturation"] = self.monitor.saturation_summary()
        self.telemetry.append(entry)
        self.tick += 1
        self._checkpoint()

    def _serve(self, pending: List[Tuple[float, Request]]):
        self._requests = [r for _, r in pending]
        self._pending = list(pending)
        self.queue = []
        for r in self._requests:
            r.outcome = "queued"
        t0 = self.clock.time()
        self.tick = 0
        self.prefill_ticks = 0
        self._refills_recorded = 0
        self.queue_evictions = 0
        self.slot_evictions = 0
        self.telemetry = []
        self._tick_ema = None
        self.ckpts.clear()
        self._checkpoint()
        watchdog = StepWatchdog()
        while (self._pending or self.queue
               or any(r is not None for r in self.active)):
            with span("tick", tick=self.tick, queue=len(self.queue),
                      active=sum(r is not None for r in self.active)):
                try:
                    self._serve_tick(watchdog)
                except _Degraded as d:
                    self.rollbacks += 1
                    log.warning("rolling back to tick <= %d after %d "
                                "breach(es)", d.target_tick, len(d.events))
                    with span("recover", kind="rollback",
                              target_tick=d.target_tick):
                        self._restore(d.target_tick)
                    if self.monitor is not None and \
                            self.monitor.drift_pending:
                        # online recalibration between ticks: rebuild the
                        # drifted layer's tables at the observed range and
                        # repromote (or record the typed sticky event), then
                        # replay
                        with span("recover", kind="recalibrate",
                                  target_tick=self.tick):
                            self.monitor.recalibrate_pending(self.tick)
                except Exception as e:  # noqa: BLE001 — any tick fault
                    self.restarts += 1
                    log.error("decode tick %d failed (%s); restart %d/%d",
                              self.tick, e, self.restarts, self.max_restarts)
                    if self.restarts > self.max_restarts:
                        raise
                    with span("recover", kind="restart",
                              target_tick=self.tick):
                        self._restore(self.tick)
        dt = self.clock.time() - t0
        # outcome accounting from final request state — replays through the
        # checkpoint ring can never double-count
        outcomes = {r.rid: r.outcome for r in self._requests}
        offered = len(self._requests)
        rejected = sum(o == "rejected" for o in outcomes.values())
        stats = {
            "decode_ticks": self.tick,
            "prefill_ticks": self.prefill_ticks,
            "wall_s": dt,
            "offered": offered,
            "served": sum(o == "served" for o in outcomes.values()),
            "degraded": sum(o == "degraded" for o in outcomes.values()),
            "failed": sum(o == "failed" for o in outcomes.values()),
            "rejected": rejected,
            "shed_rate": rejected / offered if offered else 0.0,
            "retried": sum(r.retries > 0 for r in self._requests),
            "restarts": self.restarts,
            "rollbacks": self.rollbacks,
            "queue_evictions": self.queue_evictions,
            "slot_evictions": self.slot_evictions,
            "straggler_ticks": list(watchdog.flagged),
            "outcomes": outcomes,
            "telemetry": list(self.telemetry),
            "table_bytes": (self.pdecode.table_bytes()
                            if self.pdecode is not None else 0),
        }
        if self.monitor is not None:
            stats["health_events"] = list(self.monitor.events)
            stats.update(self.monitor.counters())
            if self.sentinel:
                stats["saturation"] = self.monitor.saturation_summary()
                stats["recalibrations"] = int(
                    self.monitor.recalibrations.sum())
        return stats


def token_latencies(requests: Sequence[Request]) -> List[float]:
    """Per-token latency (seconds/token, arrival to completion) of every
    *completed* request — the tail the overload contract bounds."""
    out = []
    for r in requests:
        if r.outcome in ("served", "degraded") and r.out:
            out.append((r.t_done - r.t_arrive) / len(r.out))
    return out


def verify_accounting(requests: Sequence[Request], stats: Dict) -> None:
    """The overload-accounting invariant: every request ends in exactly one
    typed outcome and the outcome counts partition the offered set — no
    admitted request is ever silently dropped.  Raises ``SystemExit`` on
    violation (the CI traffic smoke's non-zero exit)."""
    bad = [r.rid for r in requests if r.outcome not in OUTCOMES]
    if bad:
        raise SystemExit(
            f"accounting violated: requests {bad} ended without a terminal "
            f"outcome (allowed: {OUTCOMES})")
    total = sum(stats[k] for k in OUTCOMES)
    if total != stats["offered"] or stats["offered"] != len(requests):
        raise SystemExit(
            f"accounting violated: served+degraded+failed+rejected = {total} "
            f"!= offered = {stats['offered']} (requests: {len(requests)})")
    undone = [r.rid for r in requests if not r.done]
    if undone:
        raise SystemExit(
            f"accounting violated: requests {undone} have a terminal outcome "
            f"but done=False")


def _chaos_plan(eng: Engine, injector):
    """The fault schedule the ``--chaos`` smoke drives: one action per fault
    class, each exercising its detection + response end to end."""
    from repro.kernels import autotune as atn

    def garble_autotune(e):
        cache = atn.get_cache()
        # make sure there are bytes to garble, then corrupt them in place;
        # the reload must warn + quarantine, never crash or silently reset
        cache.record("chaos_probe|B=1,dtype=float32|backend=cpu",
                     atn.TileConfig(Bb=8, Gb=1, Ob=128), None, 0)
        injector.garble_file(cache.path, "garbage")
        atn.reset_cache(cache.path)

    def poison_state(e):
        layers = e.cache["layers"]
        e.cache = dict(e.cache, layers=dict(
            layers, ssd=injector.poison(layers["ssd"], "nan", n=4)))

    def corrupt_proj(e):
        tabs = e.pdecode.pcilt["proj"]["tables"]
        tabs["wx"] = injector.corrupt_table(tabs["wx"], n_flips=2)

    def flip_head(e):
        head = e.pdecode.pcilt["head"]
        head["seg_idx"] = injector.flip_seg_idx(
            head["seg_idx"], n_pool=head["pool"].shape[0])

    # keyed on the monotone step counter (prefill + decode steps) so every
    # entry fires even when requests finish during neighbors' prefill ticks
    return {
        4: [garble_autotune],
        7: [lambda e: injector.maybe_fail(7)],
        11: [poison_state],
        15: [corrupt_proj],
        19: [flip_head],
    }


#: the drift smoke's injection site: one layer's mixer norm gain, amplified
#: hard enough that the very first monitored tick classifies "saturated"
DRIFT_LAYER = 1
DRIFT_GAMMA = 64.0
DRIFT_STEP = 10


def _chaos_drift_plan(eng: Engine, injector):
    """The ``--chaos-drift`` schedule: amplify one layer's mixer norm gain
    so its ``wo`` activations walk out of the calibrated range.  No table
    byte changes — checksums pass, the dense oracle agrees — only the
    in-kernel saturation counters can catch it."""

    def drift_norm(e):
        blocks = dict(e.params["blocks"])
        mixer = dict(blocks["mixer"])
        norm = dict(mixer["norm"])
        norm["scale"] = injector.drift_scale(norm["scale"], DRIFT_GAMMA,
                                             rows=[DRIFT_LAYER])
        mixer["norm"] = norm
        blocks["mixer"] = mixer
        # params are deliberately outside the checkpoint ring: a rollback
        # must NOT undo the drift, the workload really moved
        e.params = dict(e.params, blocks=blocks)

    return {DRIFT_STEP: [drift_norm]}


def _make_requests(cfg, n: int, max_new: int, deadline: Optional[float],
                   seed: int) -> List[Request]:
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(2, cfg.vocab, size=rng.integers(4, 12)),
                    max_new, deadline_s=deadline) for i in range(n)]


def serve_config(arch: str, *, full: bool = False, pcilt: bool = False):
    """The config ``main`` serves: the published shape with ``full``, the
    smoke shape otherwise.  Under ``pcilt`` the decode runs f32, with the
    config's own table format (``cfg.pcilt``) or, where it has none,
    INT4 g2."""
    import dataclasses as dc

    from repro.configs.base import PCILTConfig

    cfg = get_config(arch) if full else get_smoke_config(arch)
    if cfg.n_img_tokens or cfg.encoder_layers:
        raise SystemExit("serve demo targets text decoder archs")
    if pcilt:
        if cfg.ssm is None:
            raise SystemExit("--pcilt serves the converted Mamba decode "
                             "path; pick an [ssm] arch (e.g. mamba2-130m)")
        cfg = dc.replace(cfg, pcilt=cfg.pcilt or PCILTConfig(act_bits=4,
                                                             group=2),
                         dtype=jnp.float32)
    return cfg


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen3-0.6b")
    p.add_argument("--full", action="store_true")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--pcilt", action="store_true",
                   help="serve the converted PCILT decode path (Mamba archs) "
                        "under the health monitor")
    p.add_argument("--chaos", action="store_true",
                   help="drive the fault-injection schedule and verify the "
                        "resilience contract (implies a reference run)")
    p.add_argument("--chaos-drift", action="store_true",
                   help="inject calibration drift (no corrupted bytes) and "
                        "verify the sentinel contract: detect -> demote -> "
                        "recalibrate -> repromote (requires --pcilt)")
    p.add_argument("--no-sentinel", action="store_true",
                   help="serve unmonitored (no in-kernel saturation "
                        "counters) — the zero-overhead opt-out")
    p.add_argument("--deadline", type=float, default=None,
                   help="per-request deadline in seconds")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--traffic", choices=("poisson", "burst", "ramp"),
                   default=None,
                   help="open-loop arrival profile on a virtual clock (the "
                        "overload-control smoke); verifies the outcome-"
                        "accounting invariant and exits non-zero on a break")
    p.add_argument("--load", type=float, default=1.0,
                   help="offered load as a multiple of analytic capacity "
                        "(--traffic only; 2.0 = overload)")
    p.add_argument("--rate", type=float, default=None,
                   help="explicit arrival rate in requests/s (overrides "
                        "--load)")
    p.add_argument("--queue-limit", type=int, default=None,
                   help="bounded admission queue depth (default: 2*slots "
                        "under --traffic, unbounded otherwise)")
    p.add_argument("--step-cost", type=float, default=1e-3,
                   help="simulated seconds per engine step on the virtual "
                        "clock (--traffic only)")
    args = p.parse_args(argv)

    logging.basicConfig(level=logging.WARNING)
    if args.chaos_drift and args.chaos:
        raise SystemExit("--chaos-drift and --chaos are separate smokes — "
                         "run them as two invocations")
    if args.chaos_drift and not args.pcilt:
        raise SystemExit("--chaos-drift exercises the PCILT drift sentinel; "
                         "add --pcilt")
    if args.chaos_drift and args.no_sentinel:
        raise SystemExit("--chaos-drift needs the sentinel; drop "
                         "--no-sentinel")
    cfg = serve_config(args.arch, full=args.full, pcilt=args.pcilt)
    if args.pcilt:
        import os
        import tempfile

        if args.chaos and "REPRO_PCILT_TUNE_CACHE" not in os.environ:
            # the chaos plan garbles the autotune cache file — never the
            # user's real one
            from repro.kernels import autotune as atn

            atn.reset_cache(os.path.join(tempfile.mkdtemp(), "tiles.json"))

    reqs = _make_requests(cfg, args.requests, args.max_new, args.deadline,
                          args.seed)

    engine_kw = {}
    arrivals = None
    if args.traffic:
        from repro.runtime import VirtualClock, make_arrivals

        engine_kw = dict(clock=VirtualClock(), step_cost_s=args.step_cost,
                         queue_limit=args.queue_limit
                         if args.queue_limit is not None else 2 * args.slots)
        # analytic capacity on the virtual clock: prefill ticks serialize
        # (one slot replays its prompt at a time) while decode ticks are
        # shared by every active slot, so one request costs about
        # (mean prompt + max_new/slots) steps of step_cost seconds each
        steps_per_req = 7.5 + args.max_new / args.slots  # prompts are 4..11
        capacity = 1.0 / (steps_per_req * args.step_cost)
        rate = args.rate if args.rate is not None else args.load * capacity
        arrivals = make_arrivals(args.traffic, args.requests, rate,
                                 seed=args.seed)
        print(f"traffic: {args.traffic} arrivals at {rate:.1f} req/s "
              f"({args.load:.2f}x capacity {capacity:.1f} req/s), "
              f"queue_limit={engine_kw['queue_limit']}")
    elif args.queue_limit is not None:
        engine_kw = dict(queue_limit=args.queue_limit)

    injector = None
    eng = Engine(cfg, max_len=256, slots=args.slots, pcilt=args.pcilt,
                 sentinel=not args.no_sentinel, **engine_kw)
    if args.chaos:
        from repro.runtime.faults import FaultInjector

        injector = FaultInjector(fail_at=(7,), seed=args.seed)
        if eng.pdecode is not None:
            eng.chaos = _chaos_plan(eng, injector)
        else:
            eng.chaos = {4: [lambda e: injector.maybe_fail(7)]}
    elif args.chaos_drift:
        from repro.runtime.faults import FaultInjector

        injector = FaultInjector(seed=args.seed)
        eng.chaos = _chaos_drift_plan(eng, injector)

    if arrivals is not None:
        stats = eng.run_traffic(reqs, arrivals)
    else:
        stats = eng.run(reqs)
    for r in reqs:
        print(f"req {r.rid}: prompt {len(r.prompt)} toks -> {r.out[:8]}... "
              f"[{r.outcome}]")
    n_completed = sum(r.outcome in ("served", "degraded") for r in reqs)
    print(f"served {n_completed} requests in {stats['wall_s']:.2f}s "
          f"({stats['decode_ticks']} decode ticks)")
    if stats["degraded"] or stats["restarts"] or stats["rollbacks"]:
        print(f"resilience: degraded={stats['degraded']} "
              f"retried={stats['retried']} failed={stats['failed']} "
              f"restarts={stats['restarts']} rollbacks={stats['rollbacks']}")

    if arrivals is not None:
        verify_accounting(reqs, stats)
        lats = token_latencies(reqs)
        p50 = float(np.percentile(lats, 50)) if lats else float("nan")
        p99 = float(np.percentile(lats, 99)) if lats else float("nan")
        print(f"overload: rejected={stats['rejected']} "
              f"(shed {100 * stats['shed_rate']:.1f}%) "
              f"queue_evictions={stats['queue_evictions']} "
              f"slot_evictions={stats['slot_evictions']} "
              f"p50/p99 token latency {p50:.4f}/{p99:.4f}s")
        print("accounting invariant verified: "
              f"{stats['served']}+{stats['degraded']}+{stats['failed']}"
              f"+{stats['rejected']} == {stats['offered']} offered")

    if args.chaos:
        if arrivals is not None:
            _verify_chaos_traffic_contract(cfg, args, eng, reqs, stats,
                                           injector, arrivals, engine_kw)
        else:
            _verify_chaos_contract(cfg, args, eng, reqs, stats, injector)
    elif args.chaos_drift:
        _verify_chaos_drift_contract(cfg, args, eng, reqs, stats, injector)


def _verify_chaos_contract(cfg, args, eng, reqs, stats, injector):
    """The CI gate: no request lost, fault-free-identical tokens, and the
    demoted path equal to the dense fake-quant oracle.  Exits non-zero on
    any violation."""
    lost = [r.rid for r in reqs if r.outcome not in ("served", "degraded")]
    if lost:
        raise SystemExit(f"chaos contract violated: requests lost: {lost}")
    if not injector.events:
        raise SystemExit("chaos smoke injected no faults — schedule never "
                         "fired (engine finished too fast?)")
    if eng.chaos:
        raise SystemExit(f"chaos smoke left faults unfired at step keys "
                         f"{sorted(eng.chaos)} (engine ran only "
                         f"{eng.steps} steps)")

    # fault-free reference run: same params (PRNGKey(0)), same request stream.
    # Undegraded requests must be token-identical; degraded requests ran
    # (partly) through the dense-oracle path, which is allclose-but-not-
    # bitwise to PCILT — their correctness is covered by the oracle-
    # equivalence check below, not token identity.
    ref_eng = Engine(cfg, max_len=256, slots=args.slots, pcilt=args.pcilt)
    ref = _make_requests(cfg, args.requests, args.max_new, args.deadline,
                         args.seed)
    ref_eng.run(ref)
    mismatched = [r.rid for r, q in zip(reqs, ref)
                  if r.outcome == "served" and r.out != q.out]
    if mismatched:
        raise SystemExit(
            f"chaos contract violated: undegraded tokens diverge from the "
            f"fault-free run for requests {mismatched}")
    n_exact = sum(r.outcome == "served" for r in reqs)

    if eng.pdecode is not None:
        # demoted decode == dense fake-quant oracle (one explicit step)
        pc_fq = dict(eng.pdecode.pcilt)
        proj = pc_fq.get("proj")
        B = args.slots
        cspecs = eng.model.cache_specs(B, 256)
        cache = materialize(cspecs, jax.random.PRNGKey(5))
        cache = dict(cache, pos=jnp.asarray(1, jnp.int32))
        tok = np.full((B, 1), 3, np.int32)
        la = jnp.zeros((cfg.n_layers,), bool)
        got, _ = eng.pdecode.step(eng.params, cache, jnp.asarray(tok),
                                  layer_ok=la, head_ok=jnp.asarray(False))
        if proj is not None:
            pc_fq["proj"] = dict(proj, path="dense_fq")
        ref_step = jax.jit(lambda p, c, t: eng.model.decode_step(
            p, c, t, make_ctx(None, None, decode=True), pcilt=pc_fq,
            head_ok=jnp.asarray(False)))
        want, _ = ref_step(eng.params, cache, jnp.asarray(tok))
        if not np.allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                           atol=1e-4):
            raise SystemExit("chaos contract violated: demoted decode "
                             "diverges from the dense fake-quant oracle")
    print(f"chaos contract verified: {len(reqs)} requests completed "
          f"({n_exact} token-identical to fault-free run, "
          f"{len(injector.events)} faults injected, "
          f"{stats['restarts']} restarts, {stats['rollbacks']} rollbacks, "
          f"{stats['degraded']} degraded)")


def _verify_chaos_drift_contract(cfg, args, eng, reqs, stats, injector):
    """The drift-sentinel CI gate: injected calibration drift (no corrupted
    bytes — checksums pass, the oracle agrees) must be caught by the
    saturation counters, the drifting layer demoted, its tables
    recalibrated online at the observed range and repromoted, with no
    request lost; requests that finished undegraded must be token-identical
    to a fault-free reference run, and the hot-swapped tables bit-equal to
    a fresh conversion-arithmetic build at the recorded new scale.  Exits
    non-zero on any violation."""
    from repro.core.pcilt import (build_grouped_tables,
                                  build_paired_stacked_tables)

    lost = [r.rid for r in reqs if r.outcome not in ("served", "degraded")]
    if lost:
        raise SystemExit(f"drift contract violated: requests lost: {lost}")
    drifts = [e for e in injector.events if e["kind"] == "calibration_drift"]
    if not drifts:
        raise SystemExit("drift smoke never injected — schedule never fired "
                         f"(engine ran only {eng.steps} steps)")
    events = stats["health_events"]
    demotions = [e for e in events if e["kind"] == "drift"]
    recals = [e for e in events if e["kind"] == "recalibrate"]
    if not demotions:
        raise SystemExit("drift contract violated: sentinel never fired "
                         f"(saturation: {stats.get('saturation')})")
    if any(e["layer"] != DRIFT_LAYER for e in demotions):
        raise SystemExit(f"drift contract violated: demotions fired off the "
                         f"drifted layer {DRIFT_LAYER}: {demotions}")
    if not recals:
        raise SystemExit("drift contract violated: no online recalibration "
                         f"(events: {[e['kind'] for e in events]})")
    mon = eng.monitor
    bad = [l for l in range(mon.n_layers) if not mon.layer_ok[l]]
    if bad:
        raise SystemExit(f"drift contract violated: layers {bad} not "
                         "repromoted after recalibration")

    # hot-swapped tables == fresh conversion-arithmetic build at the
    # recorded post-drift scale, bitwise
    proj = eng.pdecode.pcilt["proj"]
    spec, group = proj["spec"], proj["group"]
    paired = bool(proj.get("paired"))
    for ev in recals:
        l = ev["layer"]
        for name, new_scale in ev["scales"].items():
            if float(np.asarray(proj["scales"][name][l])) != new_scale:
                continue  # a later recalibration superseded this one
            wf = jnp.asarray(
                eng.params["blocks"]["mixer"][name]["kernel"][l],
                jnp.float32)
            t = np.asarray(proj["tables"][name])
            if paired:
                ref = build_paired_stacked_tables(
                    wf[None], spec, jnp.full((1,), new_scale, jnp.float32),
                    group)[:, 0]
                got = t[:, l]
            else:
                pad = (-wf.shape[0]) % group
                if pad:
                    wf = jnp.concatenate(
                        [wf, jnp.zeros((pad, wf.shape[1]), wf.dtype)], 0)
                ref = build_grouped_tables(wf, spec, new_scale, group)
                got = t[l]
            if not np.array_equal(got, np.asarray(ref).astype(got.dtype)):
                raise SystemExit(
                    f"drift contract violated: recalibrated table "
                    f"{name}[{l}] != fresh build at scale {new_scale}")

    # undrifted tokens: a fault-free reference run of the same stream —
    # requests that finished undegraded (before the drift / the
    # recalibration taint) must be token-identical
    ref_eng = Engine(cfg, max_len=256, slots=args.slots, pcilt=args.pcilt)
    ref = _make_requests(cfg, args.requests, args.max_new, args.deadline,
                         args.seed)
    ref_eng.run(ref)
    mismatched = [r.rid for r, q in zip(reqs, ref)
                  if r.outcome == "served" and r.out != q.out]
    if mismatched:
        raise SystemExit(
            f"drift contract violated: undrifted tokens diverge from the "
            f"fault-free run for requests {mismatched}")
    print(f"drift contract verified: {len(reqs)} requests completed, "
          f"sentinel fired {len(demotions)}x on layer {DRIFT_LAYER}, "
          f"{len(recals)} recalibration(s), {stats['rollbacks']} "
          f"rollback(s), {stats['degraded']} degraded; recalibrated tables "
          f"bit-equal to fresh build at the new scale")


def _verify_chaos_traffic_contract(cfg, args, eng, reqs, stats, injector,
                                   arrivals, engine_kw):
    """Chaos under traffic: the overload contract and the resilience
    contract must hold *at once* — every outcome typed and accounted, no
    admitted request silently dropped, and every request served undegraded
    in both the chaos run and a fault-free reference run of the same
    arrival trace must be token-identical."""
    from repro.runtime import VirtualClock

    verify_accounting(reqs, stats)  # raises SystemExit on violation
    if not injector.events:
        raise SystemExit("chaos-under-traffic smoke injected no faults — "
                         "schedule never fired")
    ref_kw = dict(engine_kw, clock=VirtualClock())
    ref_eng = Engine(cfg, max_len=256, slots=args.slots, pcilt=args.pcilt,
                     **ref_kw)
    ref = _make_requests(cfg, args.requests, args.max_new, args.deadline,
                         args.seed)
    ref_stats = ref_eng.run_traffic(ref, arrivals)
    verify_accounting(ref, ref_stats)
    mismatched = [r.rid for r, q in zip(reqs, ref)
                  if r.outcome == "served" and q.outcome == "served"
                  and r.out != q.out]
    if mismatched:
        raise SystemExit(
            f"chaos-under-traffic contract violated: undegraded tokens "
            f"diverge from the fault-free run for requests {mismatched}")
    print(f"chaos-under-traffic contract verified: {stats['offered']} "
          f"offered -> {stats['served']} served / {stats['degraded']} "
          f"degraded / {stats['failed']} failed / {stats['rejected']} "
          f"rejected; {len(injector.events)} faults injected, "
          f"{stats['restarts']} restarts, {stats['rollbacks']} rollbacks")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

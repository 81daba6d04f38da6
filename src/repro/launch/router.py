"""Cluster router: N slot-engine replicas behind one admission queue.

One ``ContinuousScheduler`` (DESIGN.md §8) saturates a single slot batch;
serving the paper's stack to real traffic needs MANY such batches.  The
``SlotState`` runtime is functional — the engine holds only compiled
executables and parameters, all mutable serving state lives in the pytree
— so N replicas are simply N independent ``SlotState``s driven through
ONE engine's cached executables.  No per-replica compile, no parameter
copies, and per-request images stay bit-identical to the one-shot engine
no matter which replica serves them.

What the router adds over the single-replica scheduler (DESIGN.md §13):

* **Occupancy routing** — each admissible request (FIFO) enters the
  least-occupied replica with a free slot, keeping step batches evenly
  full so no replica idles while another queues.
* **SLO-aware admission: degrade, don't queue** — with a
  ``RouterSLO(deadline_steps=...)`` and a sampler bank, a request whose
  queue wait has eaten its deadline budget is admitted at a LOWER tier
  from the bank (largest step budget that still meets the deadline, else
  the bank's cheapest tier best-effort) instead of waiting for its
  original tier.  Deadlines are counted in ROUNDS (one round = one
  ``slot_step`` across the cluster), so degradation decisions — and the
  committed bench result that degradation beats queueing on p95 SLO
  attainment — are deterministic on any machine.
* **Decode off the hot loop** — retirement decodes and progressive
  preview decodes are DISPATCHED between steps (JAX async) and fetched
  only after the next admission pass, so pixel movement never blocks
  admission or stepping.
* **Streaming** — ``stream()`` yields per-request progress events
  (``admitted`` / ``preview`` / ``finished``); previews are in-flight
  latents decoded every ``preview_every`` rounds (time-to-first-pixel).

Ledger contract: every replica scatters INTEGER counters into the same
``LedgerAccum`` bucket layout, and
``pipeline.merge_ledger_accums``/``energy_report_cluster`` sum them
before reporting — the energy headline is bit-identical across replica
counts, routing decisions, and admission orders, and (degradation aside)
to the same requests served one-shot.  Tests: tests/test_router.py.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterator, Optional

import numpy as np

from repro.launch.scheduler import _latency_metrics, poll_arrivals


@dataclasses.dataclass(frozen=True)
class RouterSLO:
    """Round-denominated latency SLO for cluster admission.

    ``deadline_steps``: enqueue->image budget in router rounds (a round
    advances every occupied replica by one denoising iteration, so the
    budget reads as "denoising-step times").  ``degrade=True`` is the
    router's contract — under overload, serve a cheaper tier now rather
    than the requested tier late; ``degrade=False`` is the queueing
    baseline (positive control in tests/benches: it misses the SLO the
    degrading router meets).
    """
    deadline_steps: Optional[int] = None
    degrade: bool = True

    def met(self, req) -> Optional[bool]:
        """Did ``req`` finish within its round budget? (None: no SLO.)"""
        if self.deadline_steps is None or req.finish_round is None:
            return None
        return (req.finish_round - req.arrival_round) <= self.deadline_steps


class ClusterRouter:
    """Route requests across ``replicas`` slot-state replicas.

    ``engine`` is shared: replica ``i`` is an independent ``SlotState``
    stepped through the same cached executables (the functional slot API
    makes this safe — see ``DiffusionEngine.init_slots``).  ``engines``
    optionally supplies one engine per replica instead (e.g. each built
    over its own device subset); they must share the pipeline config so
    executables, images and ledger buckets agree.

    ``bank`` defaults from ``engine.policies.bank`` (the ``ServePolicies``
    bundle), like the single-replica scheduler.  ``preview_every=K`` (>0)
    dispatches a progressive preview decode of every in-flight row each K
    rounds and streams it as a ``preview`` event.
    """

    def __init__(self, engine, replicas: int, slots_per_replica: int,
                 bank=None, slo: Optional[RouterSLO] = None,
                 preview_every: int = 0, engines=None):
        from repro.diffusion import solvers

        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if engines is not None:
            engines = list(engines)
            if len(engines) != replicas:
                raise ValueError(
                    f"engines= carries {len(engines)} engines for "
                    f"{replicas} replicas")
            for e in engines:
                if e.cfg != engine.cfg:
                    raise ValueError(
                        "per-replica engines must share the pipeline "
                        "config — differing configs fork executables, "
                        "images and ledger buckets")
        self.engine = engine
        self.engines = engines or [engine] * replicas
        self.replicas = replicas
        self.slots_per_replica = slots_per_replica
        if bank is None:
            bank = engine.policies.bank
        self.bank = solvers.as_bank(bank) if bank is not None else None
        self.slo = slo or RouterSLO()
        if (self.slo.deadline_steps is not None and self.slo.degrade
                and self.bank is None):
            raise ValueError(
                "RouterSLO degradation needs a sampler bank — the lower "
                "tiers a request can degrade to must be compiled into the "
                "step executable (pass bank= or build the engine with "
                "ServePolicies(bank=...))")
        self.preview_every = preview_every

    # -- lifecycle -------------------------------------------------------
    def warmup(self) -> float:
        """Compile step/encode/decode executables off the serving clock.

        One warmup covers every replica: shared-engine replicas reuse the
        same cache entries, per-replica engines each warm their own.
        """
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        for eng in dict.fromkeys(self.engines):  # unique, order-kept
            cfg = eng.cfg
            state = eng.init_slots(self.slots_per_replica, bank=self.bank)
            toks = jnp.zeros((1, cfg.text.max_len), jnp.int32)
            un = toks if state.uncond_context is not None else None
            state = eng.admit(state, 0, toks, jax.random.PRNGKey(0),
                              uncond_tokens=un)
            state = eng.slot_step(state)
            k = 1
            while k <= self.slots_per_replica:
                jax.block_until_ready(
                    eng.decode_slots(state, list(range(k))))
                k *= 2
        return time.perf_counter() - t0

    # -- SLO admission ---------------------------------------------------
    def _admission_tier(self, req, round_idx: int) -> int:
        """Bank index to admit ``req`` at, degrading if its wait demands.

        Deterministic round arithmetic: with ``waited`` rounds already
        spent queueing, the request meets its deadline only if
        ``waited + num_steps <= deadline_steps``.  When the requested
        tier cannot, pick the LARGEST-budget strictly-lower tier that
        can (cheapest acceptable quality loss); when none can, fall back
        to the bank's cheapest tier (best effort).  Never upgrades.
        """
        pidx = req.policy_index
        slo = self.slo
        if (slo.deadline_steps is None or not slo.degrade
                or self.bank is None):
            return pidx
        waited = round_idx - req.arrival_round
        steps = self.bank[pidx].num_steps
        if waited + steps <= slo.deadline_steps:
            return pidx
        fitting = [i for i, p in enumerate(self.bank)
                   if p.num_steps < steps
                   and waited + p.num_steps <= slo.deadline_steps]
        if fitting:
            return max(fitting, key=lambda i: (self.bank[i].num_steps, -i))
        cheapest = min(range(len(self.bank)),
                       key=lambda i: (self.bank[i].num_steps, i))
        return cheapest if self.bank[cheapest].num_steps < steps else pidx

    # -- serving ---------------------------------------------------------
    def stream(self, requests: list) -> Iterator[dict]:
        """Serve ``requests``, yielding progress events as they happen.

        Events are dicts with ``event`` in ``{"admitted", "preview",
        "finished"}`` plus ``rid`` / ``replica`` / ``slot`` / ``round`` /
        ``t_s``; ``preview`` events carry the decoded in-flight ``image``
        and the row's current ``step``; ``finished`` events carry the
        final ``image`` (also stored on the request).  The generator
        returns once every request has finished — the router never drops
        a request.
        """
        import jax

        if self.bank is None:
            for r in requests:
                if r.policy_index != 0:
                    raise ValueError(
                        f"request {r.rid} carries policy_index="
                        f"{r.policy_index} but the router has no bank")
        pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        ready: list = []
        owners = [dict() for _ in range(self.replicas)]
        states = [eng.init_slots(self.slots_per_replica, bank=self.bank)
                  for eng in self.engines]
        decode_jobs: list = []    # (req, round, images_row) dispatched
        preview_jobs: list = []   # (req, slot_step_idx, images_row)
        completed = 0
        round_idx = 0
        stepped_rows = 0
        step_calls = 0
        step_wall = 0.0
        self._t0 = t0 = time.perf_counter()
        while completed < len(requests) or decode_jobs or preview_jobs:
            now = time.perf_counter() - t0
            poll_arrivals(pending, ready, now)
            for r in ready:
                if r.arrival_round is None:
                    r.arrival_round = round_idx
            # FIFO admission, least-occupied replica first; degrade
            # decision happens HERE, with the request's realized wait
            while ready:
                free = [(len(owners[i]), i) for i in range(self.replicas)
                        if len(owners[i]) < self.slots_per_replica]
                if not free:
                    break
                req = ready.pop(0)
                _, ri = min(free)
                slot = next(s for s in range(self.slots_per_replica)
                            if s not in owners[ri])
                pidx = self._admission_tier(req, round_idx)
                if pidx != req.policy_index:
                    req.degraded_from = req.tier
                    req.policy_index = pidx
                    req.tier = self.bank[pidx].label()
                states[ri] = self.engines[ri].admit(
                    states[ri], slot, req.tokens, None,
                    uncond_tokens=req.uncond_tokens, latents=req.latents,
                    policy_index=req.policy_index)
                owners[ri][slot] = req
                req.replica = ri
                req.admitted_s = time.perf_counter() - t0
                yield {"event": "admitted", "rid": req.rid, "replica": ri,
                       "slot": slot, "round": round_idx,
                       "tier": req.tier, "degraded_from": req.degraded_from,
                       "t_s": req.admitted_s}
            # fetch decodes dispatched LAST round — they computed while
            # we admitted, so pixel movement never blocked admission
            for req, fin_round, row in decode_jobs:
                req.image = np.asarray(jax.device_get(row))[0]
                req.finished_s = time.perf_counter() - t0
                req.finish_round = fin_round
                completed += 1
                yield {"event": "finished", "rid": req.rid,
                       "replica": req.replica, "round": fin_round,
                       "tier": req.tier, "image": req.image,
                       "t_s": req.finished_s}
            decode_jobs = []
            for req, at_step, row in preview_jobs:
                img = np.asarray(jax.device_get(row))[0]
                req.previews += 1
                pv_t = time.perf_counter() - t0
                if req.first_preview_s is None:
                    req.first_preview_s = pv_t
                yield {"event": "preview", "rid": req.rid,
                       "replica": req.replica, "round": round_idx,
                       "step": at_step, "image": img, "t_s": pv_t}
            preview_jobs = []
            if not any(owners):
                if completed < len(requests) and pending:
                    time.sleep(max(pending[0].arrival_s
                                   - (time.perf_counter() - t0), 0.0))
                continue
            # one router round: step every occupied replica
            for ri in range(self.replicas):
                if not owners[ri]:
                    continue
                states[ri] = self.engines[ri].slot_step(states[ri])
                step_calls += 1
                step_wall += self.engines[ri].last_wall_s
                stepped_rows += len(owners[ri])
            round_idx += 1
            # dispatch retirement decodes (async) and free the slots NOW
            # — the freed rows are admissible next pass, the pixels are
            # fetched after it
            for ri in range(self.replicas):
                if not owners[ri]:
                    continue
                eng = self.engines[ri]
                done = [s for s in eng.finished_slots(states[ri])
                        if s in owners[ri]]
                if done:
                    imgs = eng.decode_slots(states[ri], done)
                    for j, slot in enumerate(done):
                        decode_jobs.append((owners[ri].pop(slot),
                                            round_idx, imgs[j:j + 1]))
                    states[ri] = eng.retire(states[ri], done)
            # progressive previews of rows still in flight
            if self.preview_every and round_idx % self.preview_every == 0:
                for ri in range(self.replicas):
                    slots = sorted(owners[ri])
                    if not slots:
                        continue
                    eng = self.engines[ri]
                    pv = eng.decode_preview(states[ri], slots)
                    step_of = jax.device_get(states[ri].step_idx)
                    for j, slot in enumerate(slots):
                        preview_jobs.append((owners[ri][slot],
                                             int(step_of[slot]),
                                             pv[j:j + 1]))
        self._states = states
        self._rounds = round_idx
        self._step_calls = step_calls
        self._step_wall = step_wall
        self._stepped_rows = stepped_rows

    def run(self, requests: list, ledger: bool = False) -> dict:
        """Drain :meth:`stream` and return serving metrics.

        ``ledger=True`` adds the merged-replica energy report
        (``pipeline.energy_report_cluster``) — bit-identical across
        replica counts.  ``metrics["states"]`` carries the per-replica
        ``SlotState``s (callers pop it before serializing).
        """
        events = {"admitted": 0, "preview": 0, "finished": 0}
        for ev in self.stream(requests):
            events[ev["event"]] += 1
        makespan = time.perf_counter() - self._t0
        states = self._states
        metrics = {
            "mode": "cluster_router",
            "denoiser_family": self.engine.denoiser.family,
            "replicas": self.replicas,
            "slots_per_replica": self.slots_per_replica,
            "rounds": self._rounds,
            "engine_steps": self._step_calls,
            "step_wall_s": self._step_wall,
            "mean_occupancy": self._stepped_rows / max(
                self._step_calls * self.slots_per_replica, 1),
            "events": events,
            "dropped": len(requests) - events["finished"],
            "policies": self.engine.policies.describe(),
            **_latency_metrics(requests, makespan, bank=self.bank,
                               default_steps=self.engine.cfg.ddim
                               .num_inference_steps),
        }
        if self.slo.deadline_steps is not None:
            met = [self.slo.met(r) for r in requests]
            metrics["slo"] = {
                "deadline_steps": self.slo.deadline_steps,
                "degrade": self.slo.degrade,
                "met": int(sum(bool(m) for m in met)),
                "attainment": sum(bool(m) for m in met)
                / max(len(met), 1),
            }
        if self.preview_every:
            firsts = [r.first_preview_s for r in requests
                      if r.first_preview_s is not None]
            metrics["preview"] = {
                "every": self.preview_every,
                "decodes": events["preview"],
                "first_preview_s": (_summary_or_none(firsts)),
            }
        if ledger:
            from repro.diffusion.pipeline import energy_report_cluster

            rep = energy_report_cluster(self.engine.cfg,
                                        [st.accum for st in states],
                                        bank=self.bank)
            # banked summaries carry per-policy breakdown lists; the
            # unbanked summary is all scalars
            metrics["energy"] = (rep.summary() if self.bank is not None
                                 else {k: float(v)
                                       for k, v in rep.summary().items()})
        metrics["states"] = states
        return metrics


def _summary_or_none(vals):
    from repro.launch.scheduler import _lat_summary

    return _lat_summary(vals) if vals else None


def _main(argv=None) -> int:
    """Router smoke entrypoint (the CI router-smoke step).

    ``--check-identity`` serves the same trace at 1 replica and at
    ``--replicas``, then asserts the merged energy headline is
    bit-identical and no request was dropped — the DESIGN.md §13
    invariant, executable anywhere.
    """
    import argparse
    import json

    import jax

    from repro.diffusion.engine import DiffusionEngine
    from repro.launch.cli import (add_policy_args, config_from_args,
                                  policies_from_args)
    from repro.launch.platform import use_compile_cache
    from repro.launch.scheduler import (apply_trace, bursty_trace,
                                        make_requests)

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    add_policy_args(ap)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2,
                    help="slots per replica")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--burst", type=int, default=4)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--slo-steps", type=int, default=0,
                    help="deadline in router rounds (0: no SLO)")
    ap.add_argument("--no-degrade", action="store_true",
                    help="queue instead of degrading under overload")
    ap.add_argument("--preview-every", type=int, default=0)
    ap.add_argument("--check-identity", action="store_true",
                    help="assert ledger bit-identity 1 vs N replicas")
    args = ap.parse_args(argv)
    use_compile_cache()

    policies = policies_from_args(args)
    cfg = config_from_args(args, policies=policies, steps=args.steps)
    eng = DiffusionEngine(cfg, key=jax.random.PRNGKey(0),
                          policies=policies)
    slo = RouterSLO(deadline_steps=args.slo_steps or None,
                    degrade=not args.no_degrade)

    def serve(replicas):
        router = ClusterRouter(eng, replicas, args.slots,
                               slo=slo if replicas == args.replicas
                               else RouterSLO(),
                               preview_every=args.preview_every)
        reqs = make_requests(cfg, args.requests, seed=7,
                             bank=router.bank)
        apply_trace(reqs, bursty_trace(args.requests, args.burst, 0.05))
        router.warmup()
        m = router.run(reqs, ledger=True)
        m.pop("states")
        return m, reqs

    m, reqs = serve(args.replicas)
    out = {k: v for k, v in m.items()}
    if args.check_identity:
        m1, reqs1 = serve(1)
        out["ledger_bit_identical_across_replicas"] = (
            m["energy"] == m1["energy"])
        out["images_bit_identical_across_replicas"] = all(
            np.array_equal(a.image, b.image)
            for a, b in zip(reqs, reqs1))
        assert out["ledger_bit_identical_across_replicas"], (
            m["energy"], m1["energy"])
        assert out["images_bit_identical_across_replicas"]
        assert m["dropped"] == 0 and m1["dropped"] == 0, "dropped requests"
    print(json.dumps(out, indent=2, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())

"""Text-to-image serving front-end over the jitted DiffusionEngine.

  PYTHONPATH=src python -m repro.launch.serve_diffusion --smoke \
      --requests 8 --micro-batch 4 --steps 5 [--guidance 7.5] \
      [--model unet|dit] [--kernels fused] [--tips adaptive] [--mesh 4] \
      [--ledger] [--continuous --slots 4 --arrival-rate 2.0 --burst 2] \
      [--solver dpm2m,steps=12] [--tiers draft balanced quality] \
      [--replicas 2 --slo-steps 12 --preview-every 2]

The policy flags (``--kernels``/``--tips``/--reuse/``--solver``/
``--tiers``) are the shared ``launch.cli`` wiring: they parse into ONE
frozen ``core.policies.ServePolicies`` bundle consumed by this CLI,
``examples/generate_image.py`` and the cluster router alike.

Cluster mode (``--replicas N``, DESIGN.md §13): N slot-state replicas
behind occupancy-routed FIFO admission with decode off the hot step
loop (``launch.router.ClusterRouter``).  ``--slo-steps D`` sets a
round-denominated deadline — under overload requests degrade to a lower
``--tiers`` bank entry instead of queueing (``--no-degrade`` for the
queueing baseline); ``--preview-every K`` decodes in-flight latents
every K rounds for streaming previews.  The merged ledger keeps the
``--ledger`` headline bit-identical across replica counts.

``--model`` selects the denoiser family behind the contract (DESIGN.md
§11): the BK-SDM UNet (default) or the DiT-S/2 transformer.  Every
serving mode, kernel policy, quality tier and the banked energy ledger
work unchanged for both families; reports carry the active family under
``denoiser_family``.

Phase-aware sampling (DESIGN.md §10): ``--solver`` swaps the solver /
step budget for every request (``SamplerPolicy`` spec: tier name, solver
name, or ``dpm2m,steps=10,phases=detail_guard``); ``--tiers`` serves a
MIXED quality-tier trace through the continuous scheduler — each request
round-robins a bank entry, every tier coexists in the one jitted
``slot_step`` via per-row coefficient gathers, and the ``--ledger``
report becomes the per-policy banked breakdown with each tier normalized
by its own step budget.

Micro-batching: incoming prompts are queued and packed into fixed-size
micro-batches (padding the tail with repeats), each served by ONE compiled
engine call — the whole encode -> scanned-denoise -> decode path is a single
XLA computation, with cond+uncond CFG fused into one batched UNet call per
step.  The engine caches one executable per micro-batch signature, so after
the first call every shape is compile-free.

Continuous batching (``--continuous``, DESIGN.md §8): instead of draining
fixed micro-batches, a persistent ``--slots``-row batch stays in flight and
every denoising step advances all occupied slots — each at its OWN
iteration index.  Finished rows are decoded and swapped for queued prompts
between steps, so a request arriving mid-generation starts one UNet
iteration later instead of one full generation later.  ``--arrival-rate``
(requests/s, with ``--burst`` arrivals at a time; 0 = all at once) drives a
deterministic bursty trace, and the report adds enqueue->image latency
percentiles (p50/p95), queueing delay, occupancy and goodput.  The
``--ledger`` headline comes from the integer per-iteration accumulator and
is bit-identical to the same requests served one-shot, at any slot count
or occupancy (tests/test_continuous.py pins this).

Mesh mode (``--mesh N``): data-parallel sharded execution over N devices
(DESIGN.md §6).  With ``JAX_PLATFORMS=cpu`` the N devices are simulated
with the dry-run's ``XLA_FLAGS`` trick (set before jax initializes);
otherwise the first N real devices are used.  The scheduler rounds the
micro-batch up to a multiple of the dp degree, shards prompt tokens and
latents along the ``data`` axis (params replicated), and masks padded tail
rows out of every reported metric: ``stats_rows`` restricts the PSSA/TIPS
accounting to the valid rows at the source, so the energy ledger never
sees a padded duplicate.

Reports aggregate imgs/s (valid images only), per-iteration wall time, and
(with ``--ledger``) the full-geometry energy headline driven by the stats
of EVERY micro-batch — the per-iteration SAS/TIPS terms are summed across
engine calls before dividing (``pipeline.energy_report_multi``), with the
stats pytrees staying on device (batch-sharded under a mesh) until that
single host read.

``--kernels`` selects the per-op kernel routing (``KernelPolicy``):
``reference`` (materializing pure-JAX), ``fused`` (blocked Pallas
attention, self AND cross — neither the SAS nor the cross-attention
probability tensor materializes; stats bit-identical), ``autotuned``
(``fused`` with block sizes from the committed autotune table —
``kernels.autotune``), or per-op overrides like
``self_attention=fused,ffn=dbsc,ffn_quant=int8``.  Interpret mode is
auto-selected per backend, so the same flag works on CPU and TPU.

``--tips`` selects the precision runtime (``PrecisionPolicy``): ``fixed``
(the silicon's predefined CAS threshold), ``adaptive`` (per-sample
quantile spotting realizing a target INT6 ratio), or field overrides like
``adaptive,target=0.5,mid=true``.  The ``--ledger`` report names the
active policy and its per-iteration realized low-precision ratios.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time


def make_config(args):
    """Config for a CLI namespace — delegates to the shared wiring.

    Kept as the module's historical entry point (benches build bare
    namespaces for it); the flag semantics now live once in
    ``repro.launch.cli`` so this CLI, the example, and the cluster
    router cannot drift.
    """
    from repro.launch.cli import config_from_args

    return config_from_args(args)


def synthetic_requests(cfg, n: int, seed: int = 7):
    """n prompt token rows (no tokenizer offline; semantics don't matter)."""
    import jax
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (n, cfg.text.max_len), 0, cfg.text.vocab_size)


def micro_batches(requests, batch: int):
    """Pack request rows into fixed-size batches, padding the tail.

    Returns (batched_tokens, valid_count) pairs; padded rows repeat the
    first request so every call hits the same compiled signature.  Padded
    rows are masked out downstream: ``valid`` drives both the imgs/s
    accounting and the ``stats_rows`` ledger restriction.
    """
    import jax.numpy as jnp
    n = requests.shape[0]
    out = []
    for i in range(0, n, batch):
        chunk = requests[i:i + batch]
        valid = chunk.shape[0]
        if valid < batch:
            pad = jnp.broadcast_to(chunk[:1],
                                   (batch - valid,) + chunk.shape[1:])
            chunk = jnp.concatenate([chunk, pad], axis=0)
        out.append((chunk, valid))
    return out


def serve(cfg, requests, micro_batch: int, key=None, ledger: bool = False,
          mesh=None, sampler_policy=None) -> dict:
    """Drain the request queue through the engine; return serving metrics.

    ``mesh``: optional ``jax.sharding.Mesh`` for data-parallel execution;
    the effective micro-batch is rounded up to a multiple of its dp size.

    ``sampler_policy``: a ``solvers.SamplerPolicy`` applied to EVERY
    request (micro-batches share one scan executable, so one policy per
    run; mixed tiers need ``serve_continuous`` with a bank).  The energy
    ledger then normalizes by the policy's own step budget.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import tips
    from repro.diffusion.engine import DiffusionEngine
    from repro.diffusion.pipeline import (aggregated_reuse_ratios_per_iter,
                                          aggregated_tips_ratios_per_iter,
                                          energy_report_multi)
    from repro.launch.mesh import dp_size_of

    key = key if key is not None else jax.random.PRNGKey(0)
    eng = DiffusionEngine(cfg, key=key, mesh=mesh)
    dp = dp_size_of(mesh) if mesh is not None else 1
    # micro-batches must tile evenly over the data axis
    micro_batch = -(-micro_batch // dp) * dp
    use_cfg = cfg.ddim.guidance_scale != 1.0
    uncond = (jnp.zeros((micro_batch, cfg.text.max_len), jnp.int32)
              if use_cfg else None)

    # warm exactly the signatures the loop will run: the full-batch one
    # (skipped when every request fits in one padded tail) and the tail's
    # stats_rows one — compiles land in compile_s, not the serving wall
    n_requests = int(requests.shape[0])
    tail = n_requests % micro_batch
    compile_s = 0.0
    if n_requests >= micro_batch:
        compile_s += eng.warmup(micro_batch, use_cfg,
                                sampler_policy=sampler_policy)
    if tail:
        compile_s += eng.warmup(micro_batch, use_cfg, stats_rows=tail,
                                sampler_policy=sampler_policy)
    batches = micro_batches(requests, micro_batch)

    images = 0
    padded = 0
    wall = 0.0
    stats_per_batch = []        # (stacked UNetStats, valid rows) per call
    for i, (toks, valid) in enumerate(batches):
        # a padded tail batch compiles its own stats_rows signature once
        rows = valid if valid < micro_batch else None
        out = eng.generate(toks, jax.random.fold_in(key, i),
                           uncond_tokens=uncond, stats_rows=rows,
                           sampler_policy=sampler_policy)
        wall += eng.last_wall_s
        images += valid
        padded += micro_batch - valid
        stats_per_batch.append(out.stats)

    steps = (cfg.ddim.num_inference_steps if sampler_policy is None
             else sampler_policy.num_steps)
    metrics = {
        "requests": int(requests.shape[0]),
        "denoiser_family": eng.denoiser.family,
        "kernel_policy": cfg.unet.effective_kernel_policy().describe(),
        "precision_policy": cfg.unet.effective_precision().describe(),
        "micro_batch": micro_batch,
        "mesh": None if mesh is None else {
            "dp": dp,
            "shape": {k: int(v) for k, v in mesh.shape.items()},
            "devices": int(mesh.devices.size),
        },
        "engine_calls": len(batches),
        "padded_rows": padded,
        "steps_per_image": steps,
        "guidance_fused_cfg": use_cfg,
        "compile_s": compile_s,
        "serve_wall_s": wall,
        "imgs_per_s": images / max(wall, 1e-9),
        "iter_wall_ms": 1e3 * wall / max(len(batches) * steps, 1),
    }
    if sampler_policy is not None:
        metrics["sampler_policy"] = sampler_policy.describe()
    if ledger and stats_per_batch:
        # ONE host read per call of the scalar ledger leaves; per-row
        # leaves never leave the mesh (stats stay batch-sharded)
        fetched = [s.ledger_fetch() for s in stats_per_batch]
        rep = energy_report_multi(cfg, fetched,
                                  sampler_policy=sampler_policy)
        metrics["energy"] = {k: float(v) for k, v in rep.summary().items()}
        if steps == cfg.ddim.num_inference_steps:
            # the per-iteration ratio extras index the CONFIG schedule;
            # a policy with its own budget reports through the energy
            # summary above (its TIPS window already step-scaled there)
            ratios = aggregated_tips_ratios_per_iter(cfg, fetched)
            # realized (not target) INT6 row fraction, per DDIM iteration
            # — the number the active PrecisionPolicy actually delivered
            metrics["tips_low_ratio_per_iter"] = [float(r) for r in ratios]
            metrics["tips_workload_low_fraction"] = float(
                tips.workload_low_precision_fraction(jnp.asarray(ratios),
                                                     ddim=cfg.ddim))
            # realized per-iteration temporal-reuse ratio (zeros when off)
            metrics["reuse_ratio_per_iter"] = [
                float(r) for r in
                aggregated_reuse_ratios_per_iter(cfg, stats_per_batch)]
    return metrics


def serve_continuous(cfg, num_requests: int, num_slots: int,
                     arrival_rate: float = 0.0, burst: int = 1,
                     key=None, ledger: bool = False, seed: int = 7,
                     edit: bool = False, bank=None) -> dict:
    """Serve a synthetic request trace through the continuous scheduler.

    ``arrival_rate`` is requests/second, arriving ``burst`` at a time
    (0 = the whole queue is available at t=0).  Compilation happens off
    the clock (``warmup``), so the latency percentiles measure serving,
    not tracing.  ``edit`` switches the trace to the img2img/editing
    request class (``scheduler.make_edit_requests``): every request is
    the same base latent with a localized edit window — the workload
    ``--reuse temporal`` serves with most patch rows cached.

    ``bank`` (tuple of ``solvers.SamplerPolicy``): mixed quality-tier
    serving — requests cycle through the bank's tiers round-robin, all
    inside one step executable, and the ``--ledger`` report becomes the
    per-policy banked breakdown (``pipeline.energy_report_banked``).
    """
    import jax

    from repro.diffusion.engine import DiffusionEngine
    from repro.launch.scheduler import (ContinuousScheduler, apply_trace,
                                        bursty_trace, make_edit_requests,
                                        make_requests)

    key = key if key is not None else jax.random.PRNGKey(0)
    eng = DiffusionEngine(cfg, key=key)
    if edit:
        requests = make_edit_requests(cfg, num_requests, seed=seed)
    else:
        requests = make_requests(cfg, num_requests, seed=seed, bank=bank)
    if arrival_rate > 0:
        gap = burst / arrival_rate
        apply_trace(requests, bursty_trace(num_requests, burst, gap))
    sched = ContinuousScheduler(eng, num_slots, bank=bank)
    compile_s = sched.warmup()
    metrics = sched.run(requests, ledger=ledger)
    metrics.pop("state")
    metrics.update(
        compile_s=compile_s,
        kernel_policy=cfg.unet.effective_kernel_policy().describe(),
        precision_policy=cfg.unet.effective_precision().describe(),
        reuse_policy=cfg.unet.reuse_policy.describe(),
        steps_per_image=(cfg.ddim.num_inference_steps if bank is None
                         else [p.num_steps for p in bank]),
        workload="edit" if edit else "t2i",
        arrival={"rate_per_s": arrival_rate, "burst": burst},
    )
    return metrics


def serve_cluster(cfg, num_requests: int, replicas: int, num_slots: int,
                  arrival_rate: float = 0.0, burst: int = 1, key=None,
                  ledger: bool = False, seed: int = 7, bank=None,
                  slo_steps: int = 0, degrade: bool = True,
                  preview_every: int = 0) -> dict:
    """Serve a synthetic trace through the multi-replica cluster router.

    ``replicas`` independent slot states share one engine's executables
    (``launch.router.ClusterRouter``); ``slo_steps`` (>0) turns on
    round-denominated SLO admission — under overload a request degrades
    to a lower bank tier instead of queueing (``degrade=False`` is the
    queueing baseline).  ``preview_every`` streams progressive preview
    decodes of in-flight rows.  The ``--ledger`` headline merges every
    replica's integer accumulator (``pipeline.energy_report_cluster``)
    and is bit-identical at any replica count.
    """
    import jax

    from repro.diffusion.engine import DiffusionEngine
    from repro.launch.router import ClusterRouter, RouterSLO
    from repro.launch.scheduler import (apply_trace, bursty_trace,
                                        make_requests)

    key = key if key is not None else jax.random.PRNGKey(0)
    eng = DiffusionEngine(cfg, key=key)
    router = ClusterRouter(eng, replicas, num_slots, bank=bank,
                           slo=RouterSLO(deadline_steps=slo_steps or None,
                                         degrade=degrade),
                           preview_every=preview_every)
    requests = make_requests(cfg, num_requests, seed=seed,
                             bank=router.bank)
    if arrival_rate > 0:
        gap = burst / arrival_rate
        apply_trace(requests, bursty_trace(num_requests, burst, gap))
    compile_s = router.warmup()
    metrics = router.run(requests, ledger=ledger)
    metrics.pop("states")
    metrics.update(
        compile_s=compile_s,
        kernel_policy=cfg.unet.effective_kernel_policy().describe(),
        precision_policy=cfg.unet.effective_precision().describe(),
        reuse_policy=cfg.unet.reuse_policy.describe(),
        steps_per_image=(cfg.ddim.num_inference_steps
                         if router.bank is None
                         else [p.num_steps for p in router.bank]),
        workload="t2i",
        arrival={"rate_per_s": arrival_rate, "burst": burst},
    )
    return metrics


def main():
    from repro.launch.cli import add_policy_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced geometry (CPU-friendly)")
    add_policy_args(ap)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--micro-batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=5,
                    help="DDIM iterations (paper: 25)")
    ap.add_argument("--guidance", type=float, default=1.0)
    ap.add_argument("--ledger", action="store_true",
                    help="print the full-geometry energy headline")
    ap.add_argument("--mesh", type=int, default=0,
                    help="data-parallel degree: shard micro-batches over N "
                         "devices (simulated host devices when "
                         "JAX_PLATFORMS=cpu, the first N real devices "
                         "otherwise); 0 = single-device")
    ap.add_argument("--edit", action="store_true",
                    help="serve the img2img/editing request class (shared "
                         "base latent + localized per-request edits) — "
                         "pair with --continuous and --reuse temporal")
    ap.add_argument("--continuous", action="store_true",
                    help="slot-based continuous batching instead of fixed "
                         "micro-batches (DESIGN.md §8)")
    ap.add_argument("--slots", type=int, default=4,
                    help="in-flight slot count for --continuous")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="request arrivals per second for --continuous "
                         "(0 = whole queue available at t=0)")
    ap.add_argument("--burst", type=int, default=1,
                    help="arrivals per burst for --arrival-rate")
    ap.add_argument("--replicas", type=int, default=0,
                    help="cluster-router mode (DESIGN.md §13): run N "
                         "slot-engine replicas behind occupancy routing "
                         "(0 = single scheduler); uses --slots per replica")
    ap.add_argument("--slo-steps", type=int, default=0,
                    help="router SLO: enqueue->image deadline in router "
                         "rounds; under overload requests degrade to a "
                         "lower --tiers entry instead of queueing "
                         "(0 = no SLO)")
    ap.add_argument("--no-degrade", action="store_true",
                    help="queue instead of degrading when the SLO cannot "
                         "be met (the positive-control baseline)")
    ap.add_argument("--preview-every", type=int, default=0,
                    help="router streaming: decode progressive previews "
                         "of in-flight rows every K rounds (0 = off)")
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")
    if args.micro_batch < 1:
        ap.error("--micro-batch must be >= 1")
    if args.requests < 1:
        ap.error("--requests must be >= 1")
    if args.mesh < 0:
        ap.error("--mesh must be >= 0")
    if args.slots < 1:
        ap.error("--slots must be >= 1")
    if args.burst < 1:
        ap.error("--burst must be >= 1")
    if args.arrival_rate < 0:
        ap.error("--arrival-rate must be >= 0")
    if args.continuous and args.mesh > 1:
        ap.error("--continuous is single-device (see DESIGN.md §8); "
                 "drop --mesh")
    if args.edit and not args.continuous:
        ap.error("--edit rides the slot scheduler's admit(latents=) path; "
                 "add --continuous")
    if args.tiers and not (args.continuous or args.replicas):
        ap.error("--tiers is mixed-tier serving over the slot engine; "
                 "add --continuous or --replicas (micro-batches share one "
                 "scan executable — use --solver for a single policy)")
    if args.replicas < 0:
        ap.error("--replicas must be >= 0")
    if args.replicas:
        if args.mesh > 1:
            ap.error("--replicas runs the single-device slot runtime per "
                     "replica (DESIGN.md §13); drop --mesh")
        if args.edit:
            ap.error("--replicas serves t2i traces; --edit rides the "
                     "single-replica --continuous path")
        if args.continuous:
            ap.error("--replicas IS continuous batching across N slot "
                     "states; drop --continuous")
    if args.slo_steps and not args.replicas:
        ap.error("--slo-steps is cluster-router admission; add --replicas")
    if args.slo_steps and not args.no_degrade and not args.tiers:
        ap.error("SLO degradation picks lower tiers from a bank; add "
                 "--tiers (or --no-degrade for the queueing baseline)")
    if args.preview_every and not args.replicas:
        ap.error("--preview-every is cluster-router streaming; add "
                 "--replicas")
    if args.tiers and args.solver:
        ap.error("--tiers and --solver are exclusive: a bank already "
                 "names every policy in flight")
    if args.tiers and args.edit:
        ap.error("--edit traces share one base latent workload; tiered "
                 "admission is t2i-only for now")

    from repro.launch.platform import cpu_forced, use_compile_cache

    if args.mesh > 1 and cpu_forced():
        # must run before the first jax backend init; an accelerator host
        # exposes its real devices
        from repro.launch.mesh import simulate_host_devices
        simulate_host_devices(args.mesh)
    use_compile_cache()

    from repro.launch.cli import config_from_args, policies_from_args
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(args.mesh) if args.mesh > 1 else None
    # ONE parse of the policy surface feeds the config, the engine's
    # bundle, and the scheduler/router bank — the CLIs cannot drift from
    # each other or from the programmatic ServePolicies API
    policies = policies_from_args(args)
    cfg = config_from_args(args, policies=policies)
    sampler_policy = policies.sampler
    bank = policies.bank
    sampling = ("tiers " + "+".join(p.label() for p in bank) if bank
                else sampler_policy.key() if sampler_policy
                else f"ddim@{args.steps}")
    batching = (f"router replicas={args.replicas} slots={args.slots}"
                if args.replicas
                else f"continuous slots={args.slots}" if args.continuous
                else f"micro-batch {args.micro_batch}")
    print(f"engine: model {args.model}, latent {cfg.unet.latent_size}^2, "
          f"sampling {sampling}, "
          f"guidance {args.guidance} "
          f"({'fused-CFG' if args.guidance != 1.0 else 'no CFG'}), "
          f"{batching}, kernels {args.kernels}, "
          f"tips {args.tips}, reuse {args.reuse}, "
          f"workload {'edit' if args.edit else 't2i'}, "
          f"mesh {'dp=' + str(args.mesh) if mesh is not None else 'none'}")
    if args.replicas:
        if bank is None and sampler_policy is not None:
            bank = (sampler_policy,)      # single-tier bank
        metrics = serve_cluster(cfg, args.requests, args.replicas,
                                args.slots,
                                arrival_rate=args.arrival_rate,
                                burst=args.burst, ledger=args.ledger,
                                bank=bank, slo_steps=args.slo_steps,
                                degrade=not args.no_degrade,
                                preview_every=args.preview_every)
    elif args.continuous:
        if bank is None and sampler_policy is not None:
            bank = (sampler_policy,)      # single-tier bank
        metrics = serve_continuous(cfg, args.requests, args.slots,
                                   arrival_rate=args.arrival_rate,
                                   burst=args.burst, ledger=args.ledger,
                                   edit=args.edit, bank=bank)
    else:
        reqs = synthetic_requests(cfg, args.requests)
        metrics = serve(cfg, reqs, args.micro_batch, ledger=args.ledger,
                        mesh=mesh, sampler_policy=sampler_policy)
    print(json.dumps(metrics, indent=2))


if __name__ == "__main__":
    main()

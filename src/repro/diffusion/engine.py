"""Fully-jitted batched diffusion engine (encode -> scan -> decode).

The seed pipeline dispatched 25 Python-level UNet steps (x2 under CFG).
``DiffusionEngine`` compiles the *whole* text-to-image path — text encoding,
the scanned DDIM loop with fused-CFG batched UNet calls, and the VAE decode
— into ONE ``jax.jit`` per (batch, geometry) signature:

  * one XLA computation per generation call: no per-step dispatch overhead,
    cross-step fusion, and the latent buffer is donated (updated in place);
  * classifier-free guidance costs one batched UNet call per step instead
    of two (cond + uncond concatenated along batch, split after);
  * the PSSA/TIPS statistics trajectory comes back as a stacked
    ``UNetStats`` pytree — ``(num_steps, ...)`` leaves — feeding the
    full-geometry energy ledger without leaving the device until read.

Compiled executables are cached per input signature, so a serving front-end
(``repro.launch.serve_diffusion``) pays compilation once per micro-batch
shape and then streams generations through it.

Data-parallel mesh mode (DESIGN.md §6): pass ``mesh`` (a
``jax.sharding.Mesh`` with a ``data`` axis, e.g. from
``repro.launch.mesh.make_elastic_mesh`` / ``make_smoke_mesh`` /
``make_data_mesh``) and the engine replicates the UNet/text/VAE parameters
across the mesh while sharding prompt tokens and latents along the data
axes.  Calls run under ``jax.set_mesh(mesh)``, so the Pallas kernels —
which the compiler cannot partition — run per data shard
(``kernels.runtime.data_parallel``).  The executable cache is keyed on
the mesh signature, so an elastic relaunch onto a different mesh
(``place_on_mesh``) retraces instead of reusing stale executables.  The
stacked stats pytree comes back with its
per-row leaves still batch-sharded; only the scalar ledger counters are
pulled to host, once, when the energy report reads them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.policies import ServePolicies, legacy_warning
from repro.core.reuse import ReuseCache, reuse_cache_zeros
from repro.diffusion import solvers as solvers_mod
from repro.diffusion.denoiser import make_denoiser
from repro.diffusion.sampler import (denoise_step, sample_scan,
                                     sample_scan_reuse)
from repro.diffusion.stats import LedgerAccum, attn_layer_order
from repro.diffusion.text_encoder import encode_text, init_text_encoder_params
from repro.diffusion.vae import decode, init_vae_params
from repro.launch.mesh import dp_axes_of, dp_size_of, mesh_signature


@dataclasses.dataclass
class EngineOutput:
    """One engine call: images plus the stacked stats trajectory."""
    images: jax.Array            # (B, 8S, 8S, 3) in [-1, 1]
    latents: jax.Array           # (B, S, S, 4) final denoised latents
    stats: object                # UNetStats, leaves (num_steps, ...)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SlotState:
    """Persistent in-flight batch for continuous serving (DESIGN.md §8).

    One row per slot.  ``step_idx`` is the next DDIM iteration each slot
    will execute; ``active`` marks occupied slots (inactive rows still run
    through the fixed-shape UNet step, their results discarded and their
    stats masked).  ``accum`` holds the per-iteration integer ledger
    buckets each executed step scatters into.  ``uncond_context`` is
    ``None`` (static, via the treedef) when the engine's config disables
    CFG.  The whole state is donated to the jitted ``slot_step``
    executable, so a serving loop updates it in place.
    """
    latents: jax.Array                     # (S, s, s, C)
    context: jax.Array                     # (S, Tk, d) encoded cond text
    uncond_context: Optional[jax.Array]    # (S, Tk, d) or None
    step_idx: jax.Array                    # (S,) int32
    active: jax.Array                      # (S,) bool
    accum: LedgerAccum
    # per-slot previous-step activations for temporal patch reuse; None
    # (static, via the treedef) when cfg.unet.reuse_policy is disabled
    reuse_cache: Optional[ReuseCache] = None
    # sampler bank (static tuple of SamplerPolicy, in the treedef): when
    # set, ``policy_id`` selects each row's (solver, steps) pair and
    # ``solver_hist`` (S, H, s, s, C) carries multistep solver history;
    # the ledger buckets become per-(policy, step) — see init_slots.
    # ``bank=None`` keeps the legacy single-schedule state byte-identical.
    policy_id: Optional[jax.Array] = None  # (S,) int32 or None
    solver_hist: Optional[jax.Array] = None
    bank: Optional[tuple] = None

    def tree_flatten(self):
        return ((self.latents, self.context, self.uncond_context,
                 self.step_idx, self.active, self.accum,
                 self.reuse_cache, self.policy_id, self.solver_hist),
                self.bank)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, bank=aux)

    @property
    def num_slots(self) -> int:
        return int(self.step_idx.shape[0])


def _check_cfg_inputs(guidance_scale: float, uncond_tokens) -> bool:
    """CFG contract: ``uncond_tokens`` iff ``guidance_scale != 1.0``.

    The seed engine silently disabled CFG when ``guidance_scale != 1.0``
    but no unconditional prompt was supplied — a guidance-7.5 run would
    quietly produce unguided images.  Both mismatch directions now raise.
    """
    wants_cfg = guidance_scale != 1.0
    has_uncond = uncond_tokens is not None
    if wants_cfg and not has_uncond:
        raise ValueError(
            f"guidance_scale={guidance_scale} requires classifier-free "
            "guidance but uncond_tokens is None — pass the unconditional "
            "prompt tokens (or set ddim.guidance_scale=1.0)")
    if has_uncond and not wants_cfg:
        raise ValueError(
            "uncond_tokens were passed but ddim.guidance_scale == 1.0 "
            "disables classifier-free guidance — drop uncond_tokens or "
            "set a guidance_scale != 1.0")
    return wants_cfg


class DiffusionEngine:
    """Holds params; jits the whole generate path once per signature.

    ``cfg`` is a ``repro.diffusion.pipeline.PipelineConfig``.  Use
    ``generate(prompt_tokens, key, uncond_tokens=...)``; pass
    ``uncond_tokens`` iff ``cfg.ddim.guidance_scale != 1.0`` (a mismatch
    raises ``ValueError``).

    ``policies`` (a ``repro.core.policies.ServePolicies``) is THE policy
    surface (DESIGN.md §13): one frozen bundle of kernel routing,
    TIPS/DBSC precision, temporal patch reuse, and the sampling defaults
    (``sampler`` for ``generate``, ``bank`` for ``init_slots``).  The
    bundle — re-derived through the config's ``effective_*`` accessors —
    is the single policy component of every executable-cache key, so any
    spelling (``policies=``, the deprecated per-policy kwargs below, or
    the legacy ``UNetConfig`` fold-in knobs) that resolves to the same
    effective policies shares executables.

    ``kernel_policy`` / ``precision_policy`` / ``reuse_policy`` are
    deprecated aliases that fold into the bundle (DeprecationWarning);
    ``mesh`` switches on data-parallel sharded execution (see module
    docstring); ``None`` keeps the seed single-device behaviour untouched.
    """

    def __init__(self, cfg, key=None, kernel_policy=None, mesh=None,
                 precision_policy=None, reuse_policy=None, policies=None):
        if (kernel_policy is not None or precision_policy is not None
                or reuse_policy is not None):
            if policies is not None:
                raise ValueError(
                    "pass either policies=ServePolicies(...) or the "
                    "legacy per-policy kwargs, not both")
            legacy_warning(
                "DiffusionEngine(kernel_policy=/precision_policy=/"
                "reuse_policy=) are deprecated aliases — pass "
                "policies=ServePolicies(kernels=..., precision=..., "
                "reuse=...); cache keys and ledgers are identical")
            policies = ServePolicies.from_config(cfg.unet)
            if kernel_policy is not None:
                policies = dataclasses.replace(policies,
                                               kernels=kernel_policy)
            if precision_policy is not None:
                policies = dataclasses.replace(policies,
                                               precision=precision_policy)
            if reuse_policy is not None:
                policies = dataclasses.replace(policies,
                                               reuse=reuse_policy)
        self._default_sampler = policies.sampler if policies else None
        self._default_bank = policies.bank if policies else None
        if policies is not None:
            cfg = policies.apply(cfg)
        if cfg.unet.reuse_policy.enabled and cfg.unet.reuse_policy.capacity < 1.0:
            # a fresh engine run starts from an INVALID cache: every patch
            # of every row is active on step 0, so a sub-1.0 static gather
            # capacity would silently reuse zeros.  capacity < 1 belongs to
            # the edit path (sampler.sample_scan_reuse with recorded
            # base_caches), where the reference is valid from step 0.
            raise ValueError(
                f"reuse_policy.capacity={cfg.unet.reuse_policy.capacity} < "
                f"1.0 on the engine's temporal path — the cache starts "
                f"invalid, so capacity must be 1.0 (use the edit-mode "
                f"sampler with recorded base caches for shrunken gathers)")
        self.cfg = cfg
        key = key if key is not None else jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(key, 3)
        assert cfg.text.d_model == cfg.unet.context_dim, \
            (cfg.text.d_model, cfg.unet.context_dim)
        # the denoiser contract resolves cfg.unet (ANY registered family
        # config — UNet or DiT) to its forward/init; everything below this
        # line is model-agnostic.  The attribute keeps its historical name:
        # it is the denoiser's parameter pytree, whichever family owns it.
        self.denoiser = make_denoiser(cfg.unet)
        self.text_params = init_text_encoder_params(k1, cfg.text)
        self.unet_params = self.denoiser.init_params(k2)
        self.vae_params = init_vae_params(k3, cfg.vae)
        # jitted executables keyed by (batch, use_cfg, stats_rows, mesh
        # signature); geometry is fixed per engine so the signature is the
        # leading dims plus the placement.
        self._compiled: dict = {}
        # slot-mode executables: step per (slots, use_cfg, policies), plus
        # the encode/decode stages cached separately (admission and
        # retirement run them outside the per-step computation)
        self._slot_compiled: dict = {}
        self._encode_fn = None
        self._decode_fn = None
        self._admit_fn = None
        self.last_wall_s: Optional[float] = None
        self.mesh = None
        self.dp_size = 1
        self._data_sharding = None
        if mesh is not None:
            self.place_on_mesh(mesh)

    # ------------------------------------------------------------------
    # Mesh placement
    # ------------------------------------------------------------------
    def place_on_mesh(self, mesh) -> "DiffusionEngine":
        """Place params on ``mesh``: replicated weights, data-sharded batch.

        Callable again after an elastic resize — executables compiled for
        the previous mesh stay cached under the old signature and new
        signatures retrace against the new placement.
        """
        replicated = NamedSharding(mesh, P())
        self.mesh = mesh
        self.dp_size = dp_size_of(mesh)
        self._data_sharding = NamedSharding(mesh, P(dp_axes_of(mesh)))
        self.text_params = jax.device_put(self.text_params, replicated)
        self.unet_params = jax.device_put(self.unet_params, replicated)
        self.vae_params = jax.device_put(self.vae_params, replicated)
        return self

    def _shard_batch(self, x):
        """Commit a batch-leading array to the data axes (no-op unsharded)."""
        if x is None or self._data_sharding is None:
            return x
        return jax.device_put(x, self._data_sharding)

    # ------------------------------------------------------------------
    def _weights(self) -> tuple:
        """(text, denoiser, VAE) params: an argument of every executable.

        Weights captured by a jitted closure would be embedded in the
        program as constants — at full width 2.5 GB copied into every
        executable, which exhausts host memory while compiling.
        """
        return self.text_params, self.unet_params, self.vae_params

    def _run(self, weights, prompt_tokens, uncond_tokens, latents,
             stats_rows=None, sampler_policy=None, sampler_bank=None,
             policy_id=None):
        """Traced end-to-end path; ``uncond_tokens`` may be None (static)."""
        cfg = self.cfg
        text_params, unet_params, vae_params = weights
        context = encode_text(text_params, prompt_tokens, cfg.text)
        uncond = (encode_text(text_params, uncond_tokens, cfg.text)
                  if uncond_tokens is not None else None)

        def unet_apply(lat, tvec, ctx, active, **kw):
            return self.denoiser.apply(unet_params, lat, tvec, ctx,
                                       tips_active=active, **kw)

        if cfg.unet.reuse_policy.enabled:
            cache = reuse_cache_zeros(cfg.unet, latents.shape[0],
                                      use_cfg=uncond_tokens is not None)
            latents, stats = sample_scan_reuse(
                unet_apply, latents, context, uncond, cfg.ddim,
                reuse_cache=cache, stats_rows=stats_rows,
                sampler_policy=sampler_policy,
                sampler_bank=sampler_bank, policy_id=policy_id)
        else:
            latents, stats = sample_scan(unet_apply, latents, context,
                                         uncond, cfg.ddim,
                                         stats_rows=stats_rows,
                                         sampler_policy=sampler_policy,
                                         sampler_bank=sampler_bank,
                                         policy_id=policy_id)
        images = decode(vae_params, latents, cfg.vae)
        return images, latents, stats

    def set_precision(self, policy) -> "DiffusionEngine":
        """Switch the TIPS/DBSC precision runtime on a live engine.

        The policy participates in the executable-cache key, so the next
        ``generate`` retraces against the new policy; executables compiled
        for the previous policy stay cached under their own key.
        """
        self.cfg = dataclasses.replace(
            self.cfg, unet=dataclasses.replace(self.cfg.unet,
                                               precision=policy))
        # the frozen handle closes over its config — rebuild it so the
        # retrace actually traces the new policy (params are unaffected:
        # precision never changes parameter shapes)
        self.denoiser = make_denoiser(self.cfg.unet)
        return self

    @property
    def policies(self) -> ServePolicies:
        """The engine's effective ``ServePolicies`` bundle.

        Re-derived from the live config through the ``effective_*``
        accessors (so legacy fold-in knobs and ``set_precision`` swaps
        are reflected), with the engine-level sampling defaults riding
        along.  This is what routers/schedulers read instead of the four
        per-axis kwargs.
        """
        return ServePolicies.from_config(self.cfg.unet,
                                         sampler=self._default_sampler,
                                         bank=self._default_bank)

    def _policy_key(self, sampler_policy=None,
                    sampler_bank=None) -> ServePolicies:
        """The single policy component of an executable-cache key.

        One frozen ``ServePolicies`` value per distinct effective policy
        set — legacy spellings normalize through ``effective_*`` to the
        same bundle, so they share executables with the modern API.
        """
        return ServePolicies.from_config(self.cfg.unet,
                                         sampler=sampler_policy,
                                         bank=sampler_bank)

    def _cache_key(self, batch: int, use_cfg: bool,
                   stats_rows: Optional[int] = None,
                   sampler_policy=None, sampler_bank=None) -> tuple:
        # positions 0-3 are load-bearing (tests introspect them); the
        # ServePolicies bundle is THE policy tail — a change on any
        # policy axis retraces
        return (batch, use_cfg, stats_rows, mesh_signature(self.mesh),
                self._policy_key(sampler_policy, sampler_bank))

    def _get_compiled(self, batch: int, use_cfg: bool,
                      stats_rows: Optional[int] = None,
                      sampler_policy=None, sampler_bank=None):
        key = self._cache_key(batch, use_cfg, stats_rows, sampler_policy,
                              sampler_bank)
        fn = self._compiled.get(key)
        if fn is None:
            # under a bank the policy index is a RUNTIME operand (a (B,)
            # int32 array) so the one-shot program keeps the same dynamic
            # coefficient gathers the slot executable has — a trace-time
            # constant would let XLA fold the gathers and shift FMA
            # contraction, breaking the bit-exact oracle contract
            if use_cfg and sampler_bank is not None:
                fn = jax.jit(
                    lambda w, p, u, l, pid: self._run(w, p, u, l, stats_rows,
                                                      sampler_policy,
                                                      sampler_bank, pid),
                    donate_argnums=(3,))
            elif use_cfg:
                fn = jax.jit(
                    lambda w, p, u, l: self._run(w, p, u, l, stats_rows,
                                                 sampler_policy),
                    donate_argnums=(3,))
            elif sampler_bank is not None:
                fn = jax.jit(
                    lambda w, p, l, pid: self._run(w, p, None, l, stats_rows,
                                                   sampler_policy,
                                                   sampler_bank, pid),
                    donate_argnums=(2,))
            else:
                fn = jax.jit(
                    lambda w, p, l: self._run(w, p, None, l, stats_rows,
                                              sampler_policy),
                    donate_argnums=(2,))
            self._compiled[key] = fn
        return fn

    # ------------------------------------------------------------------
    def init_latents(self, batch: int, key) -> jax.Array:
        s = self.cfg.unet.latent_size
        return jax.random.normal(key, (batch, s, s,
                                       self.cfg.unet.in_channels))

    def generate(self, prompt_tokens, key, uncond_tokens=None,
                 latents=None, stats_rows=None,
                 sampler_policy=None, sampler_bank=None) -> EngineOutput:
        """(B, text_len) int32 tokens -> EngineOutput.

        The initial ``latents`` buffer (drawn from ``key`` unless given) is
        donated to the compiled call.  Wall time of the call (device sync
        included) lands in ``self.last_wall_s``.

        ``stats_rows`` (static) restricts the PSSA/TIPS accounting to the
        first N rows — serving sets it to the valid row count of a padded
        tail micro-batch.  Under a mesh, ``batch`` must be a multiple of
        the data-parallel degree (the serving front-end pads to it).

        ``sampler_policy`` (a ``solvers.SamplerPolicy``) swaps the solver
        and per-request step budget for this call; it joins the
        executable-cache key, so each distinct policy compiles once.  The
        stats trajectory then carries ``policy.num_steps`` leading steps.

        ``sampler_bank`` (static tuple of policies containing
        ``sampler_policy``) traces this call under the full bank's
        structure with every row pinned to the policy's index — the
        bit-exact one-shot oracle for mixed-tier slot serving
        (DESIGN.md §10).  It joins the cache key too.
        """
        cfg = self.cfg
        if (sampler_policy is None and sampler_bank is None
                and self._default_sampler is not None):
            # engine-level sampling defaults from the ServePolicies
            # bundle (a bank without a sampler only feeds init_slots —
            # one-shot generate needs a concrete policy)
            sampler_policy = self._default_sampler
            sampler_bank = self._default_bank
        if sampler_bank is not None:
            sampler_bank = solvers_mod.as_bank(sampler_bank)
            if sampler_policy not in sampler_bank:
                raise ValueError(
                    f"sampler_policy {sampler_policy and sampler_policy.key()}"
                    f" is not an entry of sampler_bank "
                    f"{[p.key() for p in sampler_bank]}")
        use_cfg = _check_cfg_inputs(cfg.ddim.guidance_scale, uncond_tokens)
        batch = prompt_tokens.shape[0]
        if self.mesh is not None and batch % self.dp_size:
            raise ValueError(
                f"batch {batch} must be a multiple of the data-parallel "
                f"degree {self.dp_size} under mesh "
                f"{dict(self.mesh.shape)} — pad the micro-batch")
        if latents is None:
            latents = self.init_latents(batch, key)
        prompt_tokens = self._shard_batch(prompt_tokens)
        uncond_tokens = self._shard_batch(uncond_tokens)
        latents = self._shard_batch(latents)
        fn = self._get_compiled(batch, use_cfg, stats_rows, sampler_policy,
                                sampler_bank)
        args = ((prompt_tokens, uncond_tokens, latents) if use_cfg
                else (prompt_tokens, latents))
        if sampler_bank is not None:
            args += (jnp.full((batch,), sampler_bank.index(sampler_policy),
                              jnp.int32),)
        t0 = time.perf_counter()
        with (jax.set_mesh(self.mesh) if self.mesh is not None
              else contextlib.nullcontext()):
            images, latents, stats = fn(self._weights(), *args)
        jax.block_until_ready(images)
        self.last_wall_s = time.perf_counter() - t0
        return EngineOutput(images=images, latents=latents, stats=stats)

    # ------------------------------------------------------------------
    def warmup(self, batch: int, use_cfg: Optional[bool] = None,
               stats_rows: Optional[int] = None,
               sampler_policy=None, sampler_bank=None) -> float:
        """Compile (and discard) one call for the given signature.

        ``use_cfg`` defaults to what the config demands
        (``guidance_scale != 1.0``); forcing it AGAINST the config raises
        the same ``ValueError`` as ``generate`` — a warmed-up signature
        the engine would refuse to serve is a bug, not a cache entry.
        Returns the wall seconds the warmup call took (compile + run).
        """
        cfg = self.cfg
        if use_cfg is None:
            use_cfg = cfg.ddim.guidance_scale != 1.0
        toks = jnp.zeros((batch, cfg.text.max_len), jnp.int32)
        un = jnp.zeros((batch, cfg.text.max_len), jnp.int32) if use_cfg \
            else None
        t0 = time.perf_counter()
        self.generate(toks, jax.random.PRNGKey(0), uncond_tokens=un,
                      stats_rows=stats_rows, sampler_policy=sampler_policy,
                      sampler_bank=sampler_bank)
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    # Slot-state mode: continuous batching (DESIGN.md §8)
    # ------------------------------------------------------------------
    def init_slots(self, num_slots: int, bank=None) -> SlotState:
        """Fresh all-inactive slot state for ``num_slots`` in-flight rows.

        The slot count is the step executable's batch signature — pick it
        once per serving run (every ``slot_step`` reuses the same compiled
        program regardless of occupancy).  Single-device only: slot
        admission rewrites individual batch rows between steps, which
        would thrash a data-sharded placement.

        ``bank`` (tuple of ``solvers.SamplerPolicy``) turns on the
        phase-aware sampling runtime: requests admitted with different
        ``policy_index`` values coexist in the SAME jitted ``slot_step``
        (per-row coefficient gathers), with multistep solver history in
        ``solver_hist`` and the ledger widened to per-(policy, step)
        buckets — bucket ``p * N + i`` (N = bank max budget) holds policy
        ``p``'s step-``i`` counters, so per-policy energy normalization
        stays exact (``pipeline.energy_report_banked``).  ``bank=None``
        is the legacy single-schedule state, untouched.

        Replica safety (DESIGN.md §13): the slot API is functional —
        state in, state out, with donation consuming only the PASSED
        state's buffers — so one engine may drive N independent
        ``SlotState``s ("replicas") through the SAME cached executables.
        Each replica's ``accum`` is its own integer ledger; summing them
        (``pipeline.merge_ledger_accums``) reproduces the one-shot
        headline bit-for-bit at any replica count or admission order.
        The cluster router (``repro.launch.router``) is built on exactly
        this: call ``init_slots`` once per replica.
        """
        if self.mesh is not None:
            raise ValueError(
                "slot-state mode is single-device: per-slot admission "
                "rewrites batch rows between steps (use micro-batch "
                "serving for mesh execution)")
        if num_slots < 1:
            raise ValueError(f"num_slots={num_slots} must be >= 1")
        if bank is None:
            bank = self._default_bank
        cfg = self.cfg
        s, c = cfg.unet.latent_size, cfg.unet.in_channels
        ctx_shape = (num_slots, cfg.text.max_len, cfg.text.d_model)
        use_cfg = cfg.ddim.guidance_scale != 1.0
        if bank is not None:
            bank = solvers_mod.as_bank(bank)
        num_buckets = (cfg.ddim.num_inference_steps if bank is None
                       else len(bank) * solvers_mod.bank_max_steps(bank))
        return SlotState(
            latents=jnp.zeros((num_slots, s, s, c)),
            # cond and uncond context must be DISTINCT buffers: the state
            # is donated to the admit/step executables, and XLA rejects
            # donating one buffer twice
            context=jnp.zeros(ctx_shape),
            uncond_context=jnp.zeros(ctx_shape) if use_cfg else None,
            step_idx=jnp.zeros((num_slots,), jnp.int32),
            active=jnp.zeros((num_slots,), bool),
            accum=LedgerAccum.zeros(num_buckets,
                                    len(attn_layer_order(cfg.unet))),
            # all-invalid: a slot's first step after admission computes
            # every patch dense (nothing is ever read from the zeros)
            reuse_cache=(reuse_cache_zeros(cfg.unet, num_slots, use_cfg)
                         if cfg.unet.reuse_policy.enabled else None),
            policy_id=(jnp.zeros((num_slots,), jnp.int32)
                       if bank is not None else None),
            solver_hist=(solvers_mod.init_history(bank, num_slots,
                                                  (s, s, c))
                         if bank is not None else None),
            bank=bank)

    def _encode_compiled(self):
        if self._encode_fn is None:
            self._encode_fn = jax.jit(
                lambda tp, toks: encode_text(tp, toks, self.cfg.text))
        return functools.partial(self._encode_fn, self.text_params)

    def admit(self, state: SlotState, slot: int, prompt_tokens, key,
              uncond_tokens=None, latents=None,
              policy_index: int = 0) -> SlotState:
        """Occupy one slot with a new request (between steps).

        ``prompt_tokens`` is (1, text_len); the initial latent row is
        drawn from ``key`` (or passed explicitly — the oracle tests hand
        the same per-request draw to the one-shot engine).  Text encoding
        runs through its own cached executable; the step executable never
        retraces on admission.  The same CFG contract as ``generate``
        applies, plus the slot state itself must have been built for the
        same CFG mode.

        ``policy_index`` selects the request's ``SamplerPolicy`` from the
        state's bank (banked states only); admission zeroes the row's
        solver history, so a multistep solver restarts its warmup exactly
        as a fresh one-shot run would.
        """
        use_cfg = _check_cfg_inputs(self.cfg.ddim.guidance_scale,
                                    uncond_tokens)
        if use_cfg != (state.uncond_context is not None):
            raise ValueError(
                "slot state CFG mode does not match the admit call — "
                "rebuild the state with init_slots() for this config")
        if state.bank is None:
            if policy_index != 0:
                raise ValueError(
                    f"policy_index={policy_index} on a bank-less slot "
                    f"state — build the state with init_slots(bank=...)")
        elif not 0 <= policy_index < len(state.bank):
            raise ValueError(
                f"policy_index={policy_index} outside the state's bank "
                f"of {len(state.bank)} policies")
        enc = self._encode_compiled()
        ctx = enc(prompt_tokens)
        if latents is None:
            latents = self.init_latents(1, key)
        if self._admit_fn is None:
            # one fused dispatch per admission (slot index and policy
            # traced, so any slot/policy reuses the same executable);
            # state donated
            def _adm(state, slot, ctx_row, lat_row, un_row, pid):
                new = dataclasses.replace(
                    state,
                    latents=state.latents.at[slot].set(lat_row),
                    context=state.context.at[slot].set(ctx_row),
                    step_idx=state.step_idx.at[slot].set(0),
                    active=state.active.at[slot].set(True))
                if un_row is not None:
                    new = dataclasses.replace(
                        new, uncond_context=state.uncond_context
                        .at[slot].set(un_row))
                if state.reuse_cache is not None:
                    # cache invalidation on admit: the row's first step
                    # must not reuse the previous occupant's activations
                    new = dataclasses.replace(
                        new,
                        reuse_cache=new.reuse_cache.invalidate_row(slot))
                if state.policy_id is not None:
                    # zeroed history: multistep warmup weights multiply
                    # exact zeros, never the previous occupant's outputs
                    new = dataclasses.replace(
                        new,
                        policy_id=state.policy_id.at[slot].set(pid),
                        solver_hist=state.solver_hist.at[slot].set(0.0))
                return new
            self._admit_fn = jax.jit(_adm, donate_argnums=(0,))
        un_row = enc(uncond_tokens)[0] if use_cfg else None
        return self._admit_fn(state, jnp.int32(slot), ctx[0], latents[0],
                              un_row, jnp.int32(policy_index))

    def _slot_step_traced(self, unet_params, state: SlotState) -> SlotState:
        cfg = self.cfg

        def unet_apply(lat, tvec, ctx, act, **kw):
            return self.denoiser.apply(unet_params, lat, tvec, ctx,
                                       tips_active=act, **kw)

        if state.bank is not None:
            lat, stats, new_cache, new_hist = denoise_step(
                unet_apply, state.latents, state.context,
                state.uncond_context, state.step_idx, cfg.ddim,
                active=state.active, row_stats=True,
                reuse_cache=state.reuse_cache, bank=state.bank,
                policy_id=state.policy_id, solver_hist=state.solver_hist)
            # per-(policy, step) bucket p*N + i; rows whose counter sits
            # at/past their budget (possible only if a finished slot was
            # not retired before the next step) map out of range and the
            # scatter's mode="drop" discards them — a short-budget row
            # can never bleed into the next policy's step-0 bucket
            n_max = solvers_mod.bank_max_steps(state.bank)
            budgets = jnp.asarray([p.num_steps for p in state.bank],
                                  jnp.int32)[state.policy_id]
            bucket = jnp.where(state.step_idx < budgets,
                               state.policy_id * n_max + state.step_idx,
                               len(state.bank) * n_max)
            accum = state.accum.scatter(bucket, state.active, stats)
            return dataclasses.replace(
                state, latents=lat, accum=accum, reuse_cache=new_cache,
                solver_hist=new_hist,
                step_idx=state.step_idx + state.active.astype(jnp.int32))

        out = denoise_step(unet_apply, state.latents, state.context,
                           state.uncond_context, state.step_idx,
                           cfg.ddim, active=state.active,
                           row_stats=True, reuse_cache=state.reuse_cache)
        if state.reuse_cache is not None:
            lat, stats, new_cache = out
        else:
            (lat, stats), new_cache = out, None
        # stats masking invariant: inactive rows are zeroed BEFORE the
        # scatter, and each active row lands in ITS iteration's bucket —
        # integer adds, so any occupancy pattern reproduces the one-shot
        # folded counters exactly (reuse counters included)
        accum = state.accum.scatter(state.step_idx, state.active, stats)
        return dataclasses.replace(
            state, latents=lat, accum=accum, reuse_cache=new_cache,
            step_idx=state.step_idx + state.active.astype(jnp.int32))

    def slot_step(self, state: SlotState) -> SlotState:
        """Advance every active slot by ONE denoising iteration (jitted).

        One executable per (slot count, CFG mode, policies, sampler bank)
        — compiled on first use, donated state, reused for the whole
        serving run.  Wall seconds land in ``self.last_wall_s``.
        """
        key = (state.num_slots, state.uncond_context is not None,
               self._policy_key(None, state.bank))
        fn = self._slot_compiled.get(key)
        if fn is None:
            fn = jax.jit(self._slot_step_traced, donate_argnums=(1,))
            self._slot_compiled[key] = fn
        t0 = time.perf_counter()
        state = fn(self.unet_params, state)
        jax.block_until_ready(state.latents)
        self.last_wall_s = time.perf_counter() - t0
        return state

    def finished_slots(self, state: SlotState) -> list:
        """Active slots whose step counter has run off THEIR schedule.

        Banked states compare each row against its own policy's step
        budget — short-budget (draft-tier) rows retire early while
        quality-tier neighbours keep stepping.
        """
        if state.bank is not None:
            idx, act, pid = jax.device_get(
                (state.step_idx, state.active, state.policy_id))
            budgets = [p.num_steps for p in state.bank]
            return [i for i in range(len(idx))
                    if act[i] and idx[i] >= budgets[pid[i]]]
        n = self.cfg.ddim.num_inference_steps
        idx, act = jax.device_get((state.step_idx, state.active))
        return [i for i in range(len(idx)) if act[i] and idx[i] >= n]

    def decode_slots(self, state: SlotState, slots=None) -> jax.Array:
        """VAE-decode slot latents through a cached executable.

        ``slots=None`` decodes the whole buffer in one batch-S call;
        passing the finished slot list decodes ONLY those rows, one
        batch-1 call each — a retirement event typically frees one or two
        slots, so this is the serving path (decoding the full buffer
        would spend a multiple of the per-step wall on unfinished rows).
        Both shapes hit one cached executable each, and a decoded row is
        bit-identical whichever path produced it (and bit-identical to
        the decode fused inside ``generate`` — tests pin this), so the
        choice is pure wall time.
        """
        if self._decode_fn is None:
            self._decode_fn = jax.jit(
                lambda vp, lat: decode(vp, lat, self.cfg.vae))
        if slots is None:
            return self._decode_fn(self.vae_params, state.latents)
        # power-of-two chunking bounds the executable count to log2(S)+1
        # while keeping retirement decodes near the per-row optimum; a
        # scheduler warms those sizes off the clock (see
        # ContinuousScheduler.warmup)
        slots = list(slots)
        if not slots:
            raise ValueError(
                "decode_slots: empty slot list — guard on "
                "finished_slots() (or pass slots=None for the whole "
                "buffer)")
        out, i = [], 0
        while i < len(slots):
            c = 1 << ((len(slots) - i).bit_length() - 1)
            sel = jnp.asarray(slots[i:i + c], jnp.int32)
            out.append(self._decode_fn(self.vae_params, state.latents[sel]))
            i += c
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)

    def decode_preview(self, state: SlotState, slots) -> jax.Array:
        """Progressive preview decode of IN-FLIGHT slot latents.

        Decodes the named rows at whatever denoising iteration each has
        reached — the time-to-first-pixel path: a router calls this every
        K steps so a client sees the image sharpen while its slot is
        still denoising.  Runs through the SAME cached power-of-two
        chunked decode executables as retirement decode (``decode_slots``
        — a preview of a row that just finished is bit-identical to its
        final image), and the call is dispatched asynchronously like any
        jax computation: the router materializes the pixels off the hot
        ``slot_step`` loop.
        """
        return self.decode_slots(state, list(slots))

    def retire(self, state: SlotState, slots) -> SlotState:
        """Free finished slots (after decoding); rows become admissible."""
        idx = jnp.asarray(list(slots), jnp.int32)
        return dataclasses.replace(state,
                                   active=state.active.at[idx].set(False))

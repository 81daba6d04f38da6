"""Temporal patch reuse (SIGE-style incremental denoising) — DESIGN.md §9.

The contract under test:

  * threshold 0 (or a fully-changed input) forces every patch active, and
    the gather -> compute -> scatter path is then BIT-IDENTICAL to the
    dense UNet — eps, images, AND the integer ledger counters — across
    reference|kernel delta routing, the scanned sampler, fused-CFG, and
    the slot engine;
  * the patch-delta kernel matches its reference bit-for-bit (max/abs
    commute exactly with blocking);
  * a corrupted cache row at a full-reuse threshold CHANGES the output
    (positive control: the parity tests can detect a stale-cache leak);
  * cache lifecycle: a fresh cache is all-invalid (first step dense), an
    admitted slot's row is invalidated (no reuse across occupants);
  * realized-reuse counters are integers, masked like every other ledger
    bucket, and identical across slot counts;
  * ``ReusePolicy`` guards: capacity bounds, engine temporal-path
    capacity==1.0, parse round-trips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.reuse import (LayerReuseCache, ReuseCache, ReusePolicy,
                              reuse_cache_zeros)
from repro.diffusion.engine import DiffusionEngine
from repro.diffusion.pipeline import (PipelineConfig,
                                      aggregated_reuse_ratios_per_iter,
                                      reuse_ratios_from_accum)
from repro.diffusion.sampler import (DDIMConfig, sample_scan,
                                     sample_scan_reuse)
from repro.diffusion.unet import UNetConfig, init_unet_params, unet_forward
from repro.kernels.dispatch import KernelPolicy
from repro.kernels.patch_reuse import ops as reuse_ops
from repro.kernels.patch_reuse.ref import patch_delta_ref

# Relative L2 bound between the compiled dense and threshold-0 engine
# images (smoke geometry, 3 guided steps, CPU): the sound reading is
# 1.19e-4, a threshold that really reuses patches (1.0) reads 4.6e-3.
THR0_COMPILED_REL_L2 = 1e-3


@pytest.fixture(scope="module")
def ucfg():
    return UNetConfig().smoke()


@pytest.fixture(scope="module")
def params(ucfg):
    return init_unet_params(jax.random.PRNGKey(0), ucfg)


@pytest.fixture(scope="module")
def inputs(ucfg):
    lat = jax.random.normal(jax.random.PRNGKey(1),
                            (2, ucfg.latent_size, ucfg.latent_size,
                             ucfg.in_channels))
    ctx = jax.random.normal(jax.random.PRNGKey(2),
                            (2, ucfg.text_len, ucfg.context_dim))
    un = jax.random.normal(jax.random.PRNGKey(3),
                           (2, ucfg.text_len, ucfg.context_dim))
    t = jnp.array([500, 500])
    return lat, ctx, un, t


def with_reuse(ucfg, **kw):
    return dataclasses.replace(
        ucfg, reuse_policy=ReusePolicy.temporal(**kw))


# ---------------------------------------------------------------------------
# ReusePolicy surface
# ---------------------------------------------------------------------------
class TestPolicy:
    def test_presets_and_parse(self):
        assert not ReusePolicy.off().enabled
        assert ReusePolicy.parse("temporal").enabled
        assert ReusePolicy.parse("edit").capacity < 1.0
        p = ReusePolicy.parse("temporal,threshold=0.1")
        assert p.threshold == 0.1 and p.capacity == 1.0
        assert isinstance(hash(p), int)          # hashable (jit cache key)

    def test_validation(self):
        with pytest.raises(ValueError):
            ReusePolicy(threshold=-1.0)
        with pytest.raises(ValueError):
            ReusePolicy(capacity=0.0)
        with pytest.raises(ValueError):
            ReusePolicy(capacity=1.5)

    def test_cap_patches(self):
        p = ReusePolicy(enabled=True, capacity=0.0625)
        assert p.cap_patches(32) == 2
        assert p.cap_patches(4) == 1             # floor at one patch
        assert ReusePolicy(enabled=True).cap_patches(7) == 7

    def test_engine_rejects_sub_one_capacity(self):
        cfg = PipelineConfig.smoke()
        with pytest.raises(ValueError, match="capacity"):
            DiffusionEngine(cfg, reuse_policy=ReusePolicy.edit())

    def test_window_patch_mask(self):
        from repro.core.reuse import window_patch_mask
        # full-frame window: every patch active at every resolution
        assert all(window_patch_mask((0, 0, 8, 8), 8, 4, 8))
        assert all(window_patch_mask((0, 0, 8, 8), 4, 4, 8))
        # a 2x2 window in an 8x8 latent at resolution 8, patch=4 tokens
        # (half-row patches): rows 2-3 touch patches 4..7 -> exactly the
        # two left-half patches of those rows are active
        mask = window_patch_mask((2, 0, 2, 2), 8, 4, 8)
        assert len(mask) == 16
        assert [i for i, a in enumerate(mask) if a] == [4, 6]
        # downscaled resolution rounds the window OUTWARD (conservative:
        # boundary tokens always covered, never missed)
        # (2,2,3,3) in 8px spans rows [1, 2.5) at res 4 -> rows 1-2 of
        # the 4 row-patches active, first and last rows untouched
        lo = window_patch_mask((2, 2, 3, 3), 4, 4, 8)
        assert lo == (False, True, True, False)
        # a priori mask is a static tuple of python bools (trace-time
        # constant — what lets the edit engine skip the delta kernel)
        assert all(isinstance(a, bool) for a in mask)


# ---------------------------------------------------------------------------
# Kernel parity
# ---------------------------------------------------------------------------
class TestPatchDeltaKernel:
    @pytest.mark.parametrize("tokens,patch", [(64, 16), (80, 16), (24, 8)])
    def test_kernel_matches_reference(self, tokens, patch):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, tokens, 12))
        r = jax.random.normal(jax.random.PRNGKey(1), (2, tokens, 12))
        ref = patch_delta_ref(x, r, patch)
        for pol in (KernelPolicy(reuse="kernel"),
                    KernelPolicy(reuse="kernel", reuse_block_patches=3)):
            from repro.kernels import dispatch
            d, changed = dispatch.patch_delta(pol, x, r, patch=patch,
                                              threshold=0.5)
            assert jnp.array_equal(d, ref)       # max/abs commute exactly
            assert jnp.array_equal(changed, ref >= 0.5)

    def test_threshold_zero_all_active(self):
        from repro.kernels import dispatch
        x = jnp.zeros((1, 32, 4))
        _, changed = dispatch.patch_delta(KernelPolicy(), x, x,
                                          patch=16, threshold=0.0)
        assert bool(jnp.all(changed))            # delta 0 >= 0

    def test_plan_all_active_is_identity(self):
        active = jnp.ones((3, 8), bool)
        order, gate = reuse_ops.reuse_plan(active, 8)
        assert jnp.array_equal(order,
                               jnp.broadcast_to(jnp.arange(8), (3, 8)))
        assert bool(jnp.all(gate))

    def test_scatter_gated_rows_keep_base(self):
        base = jnp.arange(12, dtype=jnp.float32).reshape(1, 6, 2)
        rows = jnp.array([[0, 3]])
        vals = jnp.full((1, 2, 2), -1.0)
        gate = jnp.array([[True, False]])
        out = reuse_ops.scatter_rows(base, rows, vals, gate)
        assert jnp.array_equal(out[0, 0], jnp.array([-1.0, -1.0]))
        assert jnp.array_equal(out[0, 3], base[0, 3])   # gated off


# ---------------------------------------------------------------------------
# UNet-level exactness (the tentpole contract)
# ---------------------------------------------------------------------------
class TestUNetParity:
    @pytest.mark.parametrize("kernels", ["reference", "fused"])
    def test_thr0_bit_identical_and_counters(self, ucfg, params, inputs,
                                             kernels):
        lat, ctx, _, t = inputs
        kp = KernelPolicy.parse(kernels)
        base = dataclasses.replace(ucfg, kernel_policy=kp)
        eps_d, st_d = unet_forward(params, lat, t, ctx, base)
        rcfg = with_reuse(base, threshold=0.0)
        cache = reuse_cache_zeros(rcfg, 2, use_cfg=False)
        eps_r, st_r, cache2 = unet_forward(params, lat, t, ctx, rcfg,
                                           reuse_cache=cache)
        assert jnp.array_equal(eps_d, eps_r)
        # second step against a VALID cache, same threshold: still dense
        eps_r2, st_r2, _ = unet_forward(params, lat, t, ctx, rcfg,
                                        reuse_cache=cache2)
        assert jnp.array_equal(eps_d, eps_r2)
        # ledger counters bit-identical to the dense run
        for a, b in zip(st_d.pssa, st_r.pssa):
            assert jnp.array_equal(a.nnz, b.nnz)
            assert jnp.array_equal(a.bitmap_ones_xor, b.bitmap_ones_xor)
        # realized-reuse counters: everything computed
        for c in st_r2.reuse:
            assert c.computed.dtype == jnp.int32
            assert jnp.array_equal(c.computed, c.total)

    def test_fully_changed_input_is_dense(self, ucfg, params, inputs):
        """A large threshold with a COMPLETELY different input: every
        patch trips the delta, so the output is exactly dense."""
        lat, ctx, _, t = inputs
        rcfg = with_reuse(ucfg, threshold=0.05)
        cache = reuse_cache_zeros(rcfg, 2, use_cfg=False)
        _, _, cache2 = unet_forward(params, lat, t, ctx, rcfg,
                                    reuse_cache=cache)
        lat2 = lat + 100.0                       # every patch changes
        eps_d, _ = unet_forward(params, lat2, t, ctx, ucfg)
        eps_r, st_r, _ = unet_forward(params, lat2, t, ctx, rcfg,
                                      reuse_cache=cache2)
        assert jnp.array_equal(eps_d, eps_r)
        for c in st_r.reuse:
            assert jnp.array_equal(c.computed, c.total)

    def test_full_reuse_replays_cache(self, ucfg, params, inputs):
        lat, ctx, _, t = inputs
        eps_d, _ = unet_forward(params, lat, t, ctx, ucfg)
        rcfg = with_reuse(ucfg, threshold=1e9)
        cache = reuse_cache_zeros(rcfg, 2, use_cfg=False)
        _, _, cache2 = unet_forward(params, lat, t, ctx, rcfg,
                                    reuse_cache=cache)
        eps_f, st_f, _ = unet_forward(params, lat, t, ctx, rcfg,
                                      reuse_cache=cache2)
        assert jnp.array_equal(eps_f, eps_d)     # same input -> same eps
        assert sum(int(jnp.sum(c.computed)) for c in st_f.reuse) == 0

    def test_stale_cache_leak_detected(self, ucfg, params, inputs):
        """POSITIVE CONTROL: corrupt one cached activation row at a
        full-reuse threshold — the output must move.  Proves the parity
        assertions above would catch a scatter that read stale rows."""
        lat, ctx, _, t = inputs
        rcfg = with_reuse(ucfg, threshold=1e9)
        cache = reuse_cache_zeros(rcfg, 2, use_cfg=False)
        eps_clean, _, cache2 = unet_forward(params, lat, t, ctx, rcfg,
                                            reuse_cache=cache)
        bad_layers = list(cache2.layers)
        l0 = bad_layers[0]
        bad_layers[0] = LayerReuseCache(
            ref=l0.ref, sa=l0.sa.at[0].add(10.0), ca=l0.ca, ffn=l0.ffn)
        bad = ReuseCache(valid=cache2.valid, layers=tuple(bad_layers))
        eps_bad, _, _ = unet_forward(params, lat, t, ctx, rcfg,
                                     reuse_cache=bad)
        assert not jnp.array_equal(eps_clean, eps_bad)

    def test_invalid_row_forces_dense(self, ucfg, params, inputs):
        """Row invalidation overrides even a full-reuse threshold."""
        lat, ctx, _, t = inputs
        rcfg = with_reuse(ucfg, threshold=1e9)
        cache = reuse_cache_zeros(rcfg, 2, use_cfg=False)
        _, _, cache2 = unet_forward(params, lat, t, ctx, rcfg,
                                    reuse_cache=cache)
        inv = cache2.invalidate_row(1)
        _, st, _ = unet_forward(params, lat, t, ctx, rcfg,
                                reuse_cache=inv)
        for c in st.reuse:
            assert int(c.computed[0]) == 0               # row 0 reuses
            assert int(c.computed[1]) == int(c.total[1])  # row 1 dense

    def test_cfg_dup_parity(self, ucfg, params, inputs):
        lat, ctx, un, t = inputs
        ctx_f = jnp.concatenate([ctx, un], axis=0)
        eps_d, _ = unet_forward(params, lat, t, ctx_f, ucfg,
                                stats_rows=2, cfg_dup=True)
        rcfg = with_reuse(ucfg, threshold=0.0)
        cache = reuse_cache_zeros(rcfg, 2, use_cfg=True)
        eps_r, _, cache2 = unet_forward(params, lat, t, ctx_f, rcfg,
                                        stats_rows=2, cfg_dup=True,
                                        reuse_cache=cache)
        assert jnp.array_equal(eps_d, eps_r)
        eps_r2, _, _ = unet_forward(params, lat, t, ctx_f, rcfg,
                                    stats_rows=2, cfg_dup=True,
                                    reuse_cache=cache2)
        assert jnp.array_equal(eps_d, eps_r2)


# ---------------------------------------------------------------------------
# Sampler: temporal scan carry + img2img edit mode
# ---------------------------------------------------------------------------
class TestSampler:
    @pytest.fixture(scope="class")
    def scfg(self):
        return DDIMConfig(num_inference_steps=3, guidance_scale=7.5,
                          tips_active_iters=2)

    def apply(self, params, ucfg):
        def unet_apply(l, t, c, a, **kw):
            return unet_forward(params, l, t, c, ucfg, tips_active=a,
                                **kw)
        return unet_apply

    def test_scan_thr0_parity_and_record(self, ucfg, params, inputs, scfg):
        lat, ctx, un, _ = inputs
        lat_d, _ = sample_scan(self.apply(params, ucfg), lat, ctx, un,
                               scfg)
        rcfg = with_reuse(ucfg, threshold=0.0)
        cache = reuse_cache_zeros(rcfg, 2, use_cfg=True)
        lat_r, stats, caches = sample_scan_reuse(
            self.apply(params, rcfg), lat, ctx, un, scfg,
            reuse_cache=cache, record_caches=True)
        assert jnp.array_equal(lat_d, lat_r)
        # recorded stack: leading axis = iterations
        assert jax.tree_util.tree_leaves(caches)[0].shape[0] == 3

    def test_edit_mode_exact_and_bounded(self, ucfg, params, inputs, scfg):
        lat, ctx, un, _ = inputs
        rcfg = with_reuse(ucfg, threshold=0.0)
        cache = reuse_cache_zeros(rcfg, 2, use_cfg=True)
        lat_b, _, caches = sample_scan_reuse(
            self.apply(params, rcfg), lat, ctx, un, scfg,
            reuse_cache=cache, record_caches=True)
        # edit run on the SAME input at sub-1.0 capacity: full reuse,
        # replays the base trajectory exactly
        ecfg = dataclasses.replace(
            ucfg, reuse_policy=ReusePolicy.edit(threshold=0.05,
                                                capacity=0.25))
        lat_e, st = sample_scan_reuse(self.apply(params, ecfg), lat, ctx,
                                      un, scfg, base_caches=caches)
        assert jnp.array_equal(lat_e, lat_b)
        assert sum(int(jnp.sum(c.computed)) for c in st.reuse) == 0
        # perturbed input diverges, and computed stays under the static cap
        lat2 = lat.at[:, :4, :4, :].add(3.0)
        lat_e2, st2 = sample_scan_reuse(self.apply(params, ecfg), lat2,
                                        ctx, un, scfg, base_caches=caches)
        assert not jnp.array_equal(lat_e2, lat_b)
        for c in st2.reuse:
            assert bool(jnp.all(c.computed <= c.total))

    def test_exactly_one_cache_source(self, ucfg, params, inputs, scfg):
        lat, ctx, un, _ = inputs
        with pytest.raises(ValueError, match="exactly one"):
            sample_scan_reuse(self.apply(params, ucfg), lat, ctx, un,
                              scfg)


# ---------------------------------------------------------------------------
# Engine + slots: lifecycle, masking, ratio helpers
# ---------------------------------------------------------------------------
class TestEngine:
    @pytest.fixture(scope="class")
    def cfg(self):
        cfg = PipelineConfig.smoke()
        return dataclasses.replace(cfg, ddim=dataclasses.replace(
            cfg.ddim, num_inference_steps=3, guidance_scale=7.5,
            tips_active_iters=2))

    @pytest.fixture(scope="class")
    def toks(self, cfg):
        return jax.random.randint(jax.random.PRNGKey(9),
                                  (2, cfg.text.max_len), 0,
                                  cfg.text.vocab_size)

    def test_one_shot_thr0_bit_identical(self, cfg, toks):
        """Threshold-0 reuse recomputes every patch: the dense result.

        Op by op (``jax.disable_jit``) every operation of the gathered
        path meets the same inputs as its dense twin: bit for bit.  The
        engine's compiled programs take the weights as runtime operands,
        and there the compiler fuses the gathered path's layer norms apart
        from the dense path's, so the images differ by rounding: relative
        L2 1.19e-4 on the CPU, against 4.6e-3 once threshold 1.0 really
        reuses patches.  ``THR0_COMPILED_REL_L2`` sits between the two.
        """
        un = jnp.zeros_like(toks)
        eng_d = DiffusionEngine(cfg, key=jax.random.PRNGKey(0))
        eng_r = DiffusionEngine(cfg, key=jax.random.PRNGKey(0),
                                reuse_policy=ReusePolicy.temporal(
                                    threshold=0.0))

        def generate(eng):
            return eng.generate(toks, None, uncond_tokens=un,
                                latents=eng.init_latents(
                                    2, jax.random.PRNGKey(7)))

        out_d, out_r = generate(eng_d), generate(eng_r)
        rel = float(jnp.linalg.norm(out_r.images - out_d.images)
                    / jnp.linalg.norm(out_d.images))
        assert rel <= THR0_COMPILED_REL_L2, rel
        with jax.disable_jit():
            eager_d, eager_r = generate(eng_d), generate(eng_r)
        assert jnp.array_equal(eager_d.images, eager_r.images)
        # dense trajectories report zero reuse, and so does threshold 0
        for out in (out_d, out_r, eager_d, eager_r):
            assert aggregated_reuse_ratios_per_iter(cfg, [out.stats]) \
                == [0.0, 0.0, 0.0]

    def test_slot_parity_and_counters_across_slot_counts(self, cfg, toks):
        un = jnp.zeros_like(toks)
        eng = DiffusionEngine(cfg, key=jax.random.PRNGKey(0),
                              reuse_policy=ReusePolicy.temporal(
                                  threshold=1.0))
        lat0 = eng.init_latents(2, jax.random.PRNGKey(7))

        def run(num_slots):
            st = eng.init_slots(num_slots)
            for i in range(2):
                st = eng.admit(st, i, toks[i:i + 1], None,
                               uncond_tokens=un[i:i + 1],
                               latents=lat0[i:i + 1])
            for _ in range(cfg.ddim.num_inference_steps):
                st = eng.slot_step(st)
            return st

        st2, st4 = run(2), run(4)
        assert jnp.array_equal(st2.latents, st4.latents[:2])
        # reuse buckets are integer counters: slot count cannot move them
        assert jnp.array_equal(st2.accum.reuse_computed,
                               st4.accum.reuse_computed)
        assert jnp.array_equal(st2.accum.reuse_total,
                               st4.accum.reuse_total)
        r = reuse_ratios_from_accum(cfg, st2.accum)
        assert r[0] == 0.0                       # first step: invalid cache
        assert all(0.0 <= x <= 1.0 for x in r)

    def test_admit_invalidates_previous_occupant(self, cfg, toks):
        un = jnp.zeros_like(toks)
        eng = DiffusionEngine(cfg, key=jax.random.PRNGKey(0),
                              reuse_policy=ReusePolicy.temporal(
                                  threshold=1e9))
        st = eng.init_slots(1)
        st = eng.admit(st, 0, toks[:1], jax.random.PRNGKey(1),
                       uncond_tokens=un[:1])
        st = eng.slot_step(st)
        assert bool(st.reuse_cache.valid[0])     # cache valid after a step
        st = eng.retire(st, [0])
        st = eng.admit(st, 0, toks[1:], jax.random.PRNGKey(2),
                       uncond_tokens=un[1:])
        assert not bool(st.reuse_cache.valid[0])  # invalidated on admit
        # the new occupant's first step is dense despite threshold=1e9
        comp0 = int(jnp.sum(st.accum.reuse_computed[0]))
        tot0 = int(jnp.sum(st.accum.reuse_total[0]))
        st = eng.slot_step(st)
        d_comp = int(jnp.sum(st.accum.reuse_computed[0])) - comp0
        d_tot = int(jnp.sum(st.accum.reuse_total[0])) - tot0
        assert d_tot > 0 and d_comp == d_tot

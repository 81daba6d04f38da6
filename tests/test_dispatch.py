"""Kernel-dispatch layer tests: fused-path parity + the stats contract.

The contract under test (DESIGN.md §5):

  * ``KernelPolicy`` routes each hot-path op (self-attention, FFN, bitmap)
    to its reference or Pallas implementation; interpret auto-selects from
    the backend so the same policy is TPU-real and CPU-testable;
  * the fused self-attention path — blocked Pallas kernel, kernel-side
    PSSA counters — produces outputs within fp tolerance of the
    materializing reference and ``PSSAStats`` that are BIT-IDENTICAL
    (equal integer counters through the shared byte arithmetic), under
    plain calls, ``vmap``, and inside the scanned sampler;
  * no (B, H, T, T) score matrix is materialized anywhere on the fused
    path (asserted on the jaxpr);
  * the ops' pad-and-slice block handling is exact for non-block-multiple
    geometries (no degenerate block fallback).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pssa
from repro.core.attention import (self_attention_pssa,
                                  self_attention_pssa_fused)
from repro.diffusion.engine import DiffusionEngine
from repro.diffusion.pipeline import PipelineConfig, energy_report
from repro.diffusion.sampler import sample_scan
from repro.diffusion.stats import UNetStats
from repro.diffusion.unet import init_unet_params, unet_forward
from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy
from repro.kernels.patch_bitmap.ops import patch_bitmap
from repro.kernels.pssa_attention.ops import pssa_attention
from repro.kernels.runtime import default_interpret, resolve_interpret

THRESH = 1.0 / 1024.0


def _qkv(b=2, h=4, t=64, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, t, d)) for k in ks)


def _assert_stats_bit_equal(a: pssa.PSSAStats, b: pssa.PSSAStats):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"PSSAStats.{name}")


# ----------------------------------------------------------------------------
# KernelPolicy
# ----------------------------------------------------------------------------
def test_policy_presets_and_parse():
    assert KernelPolicy.reference() == KernelPolicy()
    fused = KernelPolicy.fused()
    assert fused.self_attention == "fused" and fused.bitmap == "kernel"
    assert KernelPolicy.parse("fused") == fused
    pol = KernelPolicy.parse("self_attention=fused,ffn=dbsc,interpret=true")
    assert (pol.self_attention, pol.ffn, pol.interpret) == \
        ("fused", "dbsc", True)
    assert KernelPolicy.parse("interpret=auto").interpret is None
    with pytest.raises(ValueError):
        KernelPolicy.parse("self_attention=nope")
    with pytest.raises(ValueError):
        KernelPolicy.parse("warp_drive=fused")
    with pytest.raises(ValueError):
        KernelPolicy.parse("interpret=yes")
    with pytest.raises(ValueError):
        KernelPolicy(ffn="nope")


def test_interpret_auto_selects_from_backend(monkeypatch):
    # Pallas has a real lowering on TPU (Mosaic) AND GPU (triton-pallas):
    # interpret must resolve False on both and True only where nothing
    # compiles (CPU — this container).  The earlier mapping treated TPU
    # as the only compiling backend, which forced interpret mode — and
    # ``KernelPolicy.auto()``'s reference routing — on GPU.
    from repro.kernels import runtime

    assert default_interpret()        # this container is CPU-only
    assert resolve_interpret(None) == default_interpret()
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert KernelPolicy().resolve_interpret() == default_interpret()
    desc = KernelPolicy.fused().describe()
    assert desc["interpret"] == "auto"
    assert desc["interpret_resolved"] == default_interpret()

    # the full backend -> interpret mapping, including the two names
    # jax has used for the CUDA platform and ROCm
    for backend, expect in [("cpu", True), ("tpu", False), ("gpu", False),
                            ("cuda", False), ("rocm", False)]:
        monkeypatch.setattr(runtime.jax, "default_backend",
                            lambda b=backend: b)
        assert runtime.default_interpret() is expect, backend
        assert runtime.resolve_interpret(None) is expect, backend
        # explicit values always win over the backend
        assert runtime.resolve_interpret(True) is True
        assert runtime.resolve_interpret(False) is False


def test_dispatch_table_covers_policy_choices():
    for op, impls in dispatch.DISPATCH_TABLE.items():
        assert set(impls) == set(dispatch._CHOICES[op])
    ops = {row["op"] for row in dispatch.support_matrix()}
    assert ops == set(dispatch.DISPATCH_TABLE)


# ----------------------------------------------------------------------------
# Fused self-attention parity (op level)
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("t,patch", [(64, 16), (256, 32)])
def test_fused_attention_matches_reference(t, patch):
    q, k, v = _qkv(t=t)
    ref = self_attention_pssa(q, k, v, patch=patch, threshold=THRESH)
    fused = self_attention_pssa_fused(q, k, v, patch=patch, threshold=THRESH)
    np.testing.assert_allclose(np.asarray(fused.out), np.asarray(ref.out),
                               rtol=2e-5, atol=2e-5)
    _assert_stats_bit_equal(fused.stats, ref.stats)


def test_fused_attention_stats_rows_matches_cond_only_call():
    q, k, v = _qkv(b=4, t=64)
    fused = self_attention_pssa_fused(q, k, v, patch=16, threshold=THRESH,
                                      stats_rows=2)
    cond = self_attention_pssa_fused(q[:2], k[:2], v[:2], patch=16,
                                     threshold=THRESH)
    _assert_stats_bit_equal(fused.stats, cond.stats)


def test_fused_attention_under_vmap():
    """The Pallas op must batch (pallas_call has a batching rule): vmap
    over a leading axis == a Python loop over the same slices."""
    q, k, v = _qkv(b=3, h=2, t=64)
    fn = lambda a, b, c: self_attention_pssa_fused(
        a[None], b[None], c[None], patch=16, threshold=THRESH)
    mapped = jax.vmap(fn)(q, k, v)
    for i in range(q.shape[0]):
        one = fn(q[i], k[i], v[i])
        np.testing.assert_allclose(np.asarray(mapped.out[i]),
                                   np.asarray(one.out),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(mapped.stats.nnz[i]),
                                      np.asarray(one.stats.nnz))
        np.testing.assert_array_equal(
            np.asarray(mapped.stats.bitmap_ones_xor[i]),
            np.asarray(one.stats.bitmap_ones_xor))


def test_dispatch_downgrades_oracle_and_unpruned_to_reference():
    """reference_stats / prune_scores=False definitionally materialize; the
    fused policy must silently route them to the reference implementation
    rather than change semantics."""
    q, k, v = _qkv(t=64)
    pol = KernelPolicy.fused()
    ref = self_attention_pssa(q, k, v, patch=16, threshold=THRESH,
                              prune_scores=False)
    out = dispatch.self_attention(pol, q, k, v, patch=16, threshold=THRESH,
                                  prune_scores=False)
    np.testing.assert_array_equal(np.asarray(out.out), np.asarray(ref.out))
    oracle = dispatch.self_attention(pol, q, k, v, patch=16,
                                     threshold=THRESH, reference_stats=True)
    ref_o = self_attention_pssa(q, k, v, patch=16, threshold=THRESH,
                                reference_stats=True)
    _assert_stats_bit_equal(oracle.stats, ref_o.stats)


# ----------------------------------------------------------------------------
# Pad-and-slice block handling (no degenerate fallback)
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("t", [144, 320])
def test_pssa_attention_op_non_power_of_two_t(t):
    """Non-power-of-two T used to collapse the block fallback to 1-wide
    blocks; now the op pads to the block multiple and masks — exact."""
    q, k, v = _qkv(b=1, h=2, t=t, d=8, seed=3)
    out_k, nnz_k, xor_k = pssa_attention(q, k, v, THRESH, patch=16,
                                         use_kernel=True)
    out_r, nnz_r, xor_r = pssa_attention(q, k, v, THRESH, patch=16,
                                         use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(nnz_k), np.asarray(nnz_r))
    np.testing.assert_array_equal(np.asarray(xor_k), np.asarray(xor_r))


@pytest.mark.parametrize("rows", [100, 7])
def test_patch_bitmap_op_ragged_rows(rows):
    sas = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(0), (rows, 128)) * 4, -1)
    pk, ck = patch_bitmap(sas, 32, THRESH, use_kernel=True)
    pr, cr = patch_bitmap(sas, 32, THRESH, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))


# ----------------------------------------------------------------------------
# patch_bitmap popcounts drive the exact byte accounting
# ----------------------------------------------------------------------------
def test_patch_bitmap_counts_match_exact_byte_counts():
    """Kernel popcounts summed == the integer counters behind
    ``compress_stats``; ``pssa.exact_byte_counts`` closes the loop."""
    lead, tq, tk, patch = 2, 64, 128, 32
    sas = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(1), (lead, tq, tk)) * 4, -1)
    pol = KernelPolicy.fused()
    _, counts = dispatch.patch_bitmap(pol, sas, patch, THRESH)
    ones_xor = int(jnp.sum(counts))
    nnz = int(jnp.sum(pssa.bitmap(pssa.prune(sas, THRESH))))
    exact = pssa.exact_byte_counts(nnz, ones_xor, lead=lead, tq=tq, tk=tk,
                                   patch=patch)
    st = pssa.compress_stats(sas, patch, THRESH)
    assert float(st.bytes_index_pssa) == exact["bytes_index_pssa"]
    assert float(st.bytes_values) == exact["bytes_values"]
    assert float(st.bytes_pssa_total) == (exact["bytes_values"]
                                          + exact["bytes_index_pssa"])


# ----------------------------------------------------------------------------
# Fused policy through the UNet / sampler / engine
# ----------------------------------------------------------------------------
@pytest.fixture(scope="module")
def smoke_pair():
    cfg = PipelineConfig.smoke()
    cfg_fused = dataclasses.replace(
        cfg, unet=dataclasses.replace(cfg.unet,
                                      kernel_policy=KernelPolicy.fused()))
    params = init_unet_params(jax.random.PRNGKey(42), cfg.unet)
    return cfg, cfg_fused, params


def _unet_io(cfg, batch=1):
    s = cfg.unet.latent_size
    lat = jax.random.normal(jax.random.PRNGKey(0), (batch, s, s, 4))
    ctx = jax.random.normal(jax.random.PRNGKey(1),
                            (batch, cfg.unet.text_len, cfg.unet.context_dim))
    return lat, ctx


def test_fused_unet_forward_parity(smoke_pair):
    cfg, cfg_fused, params = smoke_pair
    lat, ctx = _unet_io(cfg)
    tvec = jnp.array([500])
    eps_r, st_r = unet_forward(params, lat, tvec, ctx, cfg.unet)
    eps_f, st_f = unet_forward(params, lat, tvec, ctx, cfg_fused.unet)
    # the fused preset swaps BOTH attentions (self + cross); each adds
    # ulp-level blocked-vs-einsum drift that the conv/norm stack amplifies
    np.testing.assert_allclose(np.asarray(eps_f), np.asarray(eps_r),
                               rtol=1e-3, atol=1e-3)
    assert st_f.layers == st_r.layers
    for a, b in zip(st_f.pssa, st_r.pssa):
        _assert_stats_bit_equal(a, b)
    for a, b in zip(st_f.tips, st_r.tips):      # TIPS path is untouched
        np.testing.assert_array_equal(np.asarray(a.low_precision_ratio),
                                      np.asarray(b.low_precision_ratio))


def test_fused_sample_scan_parity(smoke_pair):
    cfg, cfg_fused, params = smoke_pair
    lat, ctx = _unet_io(cfg)

    def apply(ucfg):
        def unet_apply(l, t, c, act, stats_rows=None, cfg_dup=False):
            return unet_forward(params, l, t, c, ucfg, tips_active=act,
                                stats_rows=stats_rows, cfg_dup=cfg_dup)
        return unet_apply

    lat_r, st_r = sample_scan(apply(cfg.unet), lat, ctx, None, cfg.ddim)
    lat_f, st_f = sample_scan(apply(cfg_fused.unet), lat, ctx, None,
                              cfg.ddim)
    np.testing.assert_allclose(np.asarray(lat_f), np.asarray(lat_r),
                               rtol=2e-3, atol=2e-3)
    assert isinstance(st_f, UNetStats)
    assert st_f.num_steps == cfg.ddim.num_inference_steps
    for a, b in zip(st_f.pssa, st_r.pssa):      # stacked across all steps
        _assert_stats_bit_equal(a, b)


def test_engine_fused_policy_end_to_end(smoke_pair):
    cfg, _, _ = smoke_pair
    key = jax.random.PRNGKey(7)
    eng_r = DiffusionEngine(cfg, key=key)
    eng_f = DiffusionEngine(cfg, key=key, kernel_policy=KernelPolicy.fused())
    assert eng_f.cfg.unet.kernel_policy == KernelPolicy.fused()
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, cfg.text.max_len),
                              0, cfg.text.vocab_size)
    s = cfg.unet.latent_size
    lat0 = jax.random.normal(jax.random.PRNGKey(2), (1, s, s, 4))
    out_r = eng_r.generate(toks, None, latents=lat0.copy())
    out_f = eng_f.generate(toks, None, latents=lat0.copy())
    np.testing.assert_allclose(np.asarray(out_f.latents),
                               np.asarray(out_r.latents),
                               rtol=2e-3, atol=2e-3)
    # the stats contract: PSSA accounting is bit-identical across policies,
    # so the energy-ledger headline is drift-free
    for a, b in zip(out_f.stats.pssa, out_r.stats.pssa):
        _assert_stats_bit_equal(a, b)
    rep_r = energy_report(cfg, out_r.stats).summary()
    rep_f = energy_report(eng_f.cfg, out_f.stats).summary()
    assert rep_f == rep_r


def test_engine_fused_policy_under_cfg(smoke_pair):
    """Fused kernels compose with fused-CFG prefix dedup (cfg_dup +
    stats_rows): cond-half accounting stays bit-identical to reference."""
    cfg, _, _ = smoke_pair
    cfg = dataclasses.replace(cfg, ddim=dataclasses.replace(
        cfg.ddim, guidance_scale=7.5))
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, cfg.text.max_len),
                              0, cfg.text.vocab_size)
    un = jnp.zeros_like(toks)
    s = cfg.unet.latent_size
    lat0 = jax.random.normal(jax.random.PRNGKey(2), (1, s, s, 4))
    out_r = DiffusionEngine(cfg, key=key).generate(
        toks, None, uncond_tokens=un, latents=lat0.copy())
    out_f = DiffusionEngine(cfg, key=key,
                            kernel_policy=KernelPolicy.fused()).generate(
        toks, None, uncond_tokens=un, latents=lat0.copy())
    # guidance_scale amplifies per-step kernel-vs-reference fp drift ~7.5x
    np.testing.assert_allclose(np.asarray(out_f.latents),
                               np.asarray(out_r.latents),
                               rtol=2e-2, atol=2e-2)
    for a, b in zip(out_f.stats.pssa, out_r.stats.pssa):
        _assert_stats_bit_equal(a, b)


# ----------------------------------------------------------------------------
# The point of the refactor: the SAS never exists on the fused path
# ----------------------------------------------------------------------------
def _avals_in(jaxpr):
    """All output avals in a (closed) jaxpr, recursing into sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval
        for val in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    val, is_leaf=lambda x: hasattr(x, "eqns")
                    or hasattr(x, "jaxpr")):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _avals_in(sub)


def _materializes_sas(cfg_unet, params, t_big):
    lat = jax.random.normal(jax.random.PRNGKey(0),
                            (1, cfg_unet.latent_size,
                             cfg_unet.latent_size, 4))
    ctx = jax.random.normal(jax.random.PRNGKey(1),
                            (1, cfg_unet.text_len, cfg_unet.context_dim))
    jaxpr = jax.make_jaxpr(
        lambda p, l, c: unet_forward(p, l, jnp.array([500]), c, cfg_unet))(
        params, lat, ctx)
    return any(getattr(a, "shape", ())[-2:] == (t_big, t_big)
               for a in _avals_in(jaxpr))


def test_no_sas_materialized_on_fused_path():
    # ffn_mult=2 de-aliases the GEGLU hidden width from T (at smoke
    # defaults 2*4*32 == 256 == T, so a benign FFN activation would trip
    # the (T, T) probe); with it, only a score matrix can end in (T, T).
    # 32x32 latents put T = 1024 above the kernel's largest tile
    # (``default_blocks``): at T <= 512 one on-chip (bq, bk) tile of the
    # kernel body is itself (T, T).
    ucfg = dataclasses.replace(PipelineConfig.smoke().unet, ffn_mult=2,
                               latent_size=32)
    params = init_unet_params(jax.random.PRNGKey(42), ucfg)
    t_big = ucfg.latent_size ** 2          # largest self-attention T
    # positive control: the reference path DOES materialize the (.., T, T)
    # score matrix — if this fails the probe is broken, not the model
    assert _materializes_sas(ucfg, params, t_big)
    fused = dataclasses.replace(ucfg, kernel_policy=KernelPolicy.fused())
    assert not _materializes_sas(fused, params, t_big)

"""Autotune + int8-datapath tests: table contract, block invariance, parity.

The contracts under test (DESIGN.md §12):

  * the autotune table key is a strict round-trip of (backend, op,
    geometry) in the dispatch layer's canonical field order; unknown
    geometries fall back to the policy's default blocks silently, while
    a PRESENT table that is malformed or version-stale raises a loud
    ``AutotuneTableError`` (a quietly ignored table would masquerade as
    a tuning regression);
  * block sizes are a pure wall-clock lever: PSSA/TIPS integer counters,
    images and the energy headline are bit-identical across tuned block
    configurations, including ragged non-block-multiple geometry;
  * ``KernelPolicy.ffn_quant="int8"`` routes the DBSC integer matmuls
    through real int8 x int8 -> int32 ``lax.dot_general`` with
    accumulators bit-identical to the modeled path (same integers,
    PE-shaped execution), so images and the energy ledger do not move;
    vs the FLOAT reference FFN the int8 image is only bounded (different
    scale semantics: per-sample fake-quant + f32 accumulation).
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.attention  # noqa: F401  (resolves the ops<->core cycle)
from repro.kernels import autotune, dispatch
from repro.kernels.autotune import AutotuneTableError
from repro.kernels.bitslice_matmul.ops import bitslice_matmul
from repro.kernels.bitslice_matmul.ref import (bitslice_matmul_int8,
                                               bitslice_matmul_ref)
from repro.kernels.dispatch import KernelPolicy
from repro.kernels.pssa_attention import ops as pssa_ops
from repro.kernels.pssa_attention.ops import pssa_attention
from repro.kernels.patch_bitmap.ops import patch_bitmap
from repro.kernels.patch_reuse.ops import patch_delta


@pytest.fixture(autouse=True)
def _fresh_table_cache():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def _write_table(tmp_path, table):
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    return str(path)


# ----------------------------------------------------------------------------
# Key round-trip + table validation
# ----------------------------------------------------------------------------
GEOMS = {
    "self_attention": (1, 8, 4096, 40, 64),
    "cross_attention": (1, 8, 1024, 40, 77),
    "bitmap": (4096, 4096, 64),
    "reuse": (1, 4096, 320, 64),
}


@pytest.mark.parametrize("op", sorted(GEOMS))
def test_key_round_trip(op):
    geom = GEOMS[op]
    key = autotune.make_key("cpu", op, geom)
    assert autotune.parse_key(key) == ("cpu", op, geom)
    # the key is the dispatch-table convention: backend/op/f=v,...
    backend, opname, dims = key.split("/")
    assert (backend, opname) == ("cpu", op)
    assert all("=" in part for part in dims.split(","))


@pytest.mark.parametrize("bad", [
    "cpu/self_attention",                                   # no geometry
    "cpu/unknown_op/b=1,h=8,t=64,d=8,patch=16",             # unknown op
    "cpu/self_attention/b=1,h=8,t=64,d=8",                  # missing field
    "cpu/self_attention/t=64,b=1,h=8,d=8,patch=16",         # wrong order
    "cpu/self_attention/b=1,h=8,t=sixty,d=8,patch=16",      # non-int
])
def test_parse_key_rejects_malformed(bad):
    with pytest.raises(AutotuneTableError):
        autotune.parse_key(bad)


def test_missing_table_is_empty_and_lookup_falls_back(tmp_path):
    # a missing file is a valid empty table (fresh checkout, exotic
    # backend): lookup returns None and dispatch keeps policy defaults
    path = str(tmp_path / "nope.json")
    assert autotune.load_table(path)["entries"] == {}
    assert autotune.lookup("self_attention", (1, 1, 64, 8, 16),
                           path=path) is None
    # unknown geometry in a REAL table also falls back to None
    assert autotune.lookup("self_attention", (9, 9, 144, 9, 9)) is None


def test_stale_version_rejected_loudly(tmp_path):
    path = _write_table(tmp_path, {"version": autotune.AUTOTUNE_VERSION + 1,
                                   "entries": {}})
    with pytest.raises(AutotuneTableError, match="version"):
        autotune.load_table(path)


def test_malformed_json_rejected_loudly(tmp_path):
    path = tmp_path / "broken.json"
    path.write_text("{not json")
    with pytest.raises(AutotuneTableError, match="not valid JSON"):
        autotune.load_table(str(path))


@pytest.mark.parametrize("entries,match", [
    ({"cpu/self_attention/b=1,h=8,t=64,d=8,patch=16":
      {"bogus_knob": 128}}, "unknown knob"),
    ({"cpu/self_attention/b=1,h=8,t=64,d=8,patch=16":
      {"attn_block_q": "big"}}, "positive int"),
    ({"cpu/self_attention/b=1,h=8,t=64,d=8,patch=16":
      {"attn_block_q": 0}}, "positive int"),
    ({"cpu/self_attention/b=1,h=8,t=64,d=8,patch=16": {}}, "knob"),
    ({"cpu/self_attention/b=1,t=64": {"attn_block_q": 64}}, "fields"),
])
def test_bad_entries_rejected_loudly(tmp_path, entries, match):
    path = _write_table(tmp_path, {"version": autotune.AUTOTUNE_VERSION,
                                   "entries": entries})
    with pytest.raises(AutotuneTableError, match=match):
        autotune.load_table(path)


def _kernel_blocks(monkeypatch, policy, geom):
    """(bq, bk) the PSSA kernel runs with when dispatched under ``policy``."""
    seen = []
    real = pssa_ops.pssa_attention_kernel

    def spy(*args, **kw):
        seen.append((kw["bq"], kw["bk"]))
        return real(*args, **kw)

    monkeypatch.setattr(pssa_ops, "pssa_attention_kernel", spy)
    jax.clear_caches()                 # a cached trace would skip the spy
    b, h, t, d, patch = geom
    q = jnp.ones((b, h, t, d))
    dispatch.self_attention(policy, q, q, q, patch=patch,
                            threshold=1.0 / 1024.0)
    return seen[-1]


def test_lookup_hits_and_dispatch_blocks(tmp_path, monkeypatch):
    geom = (1, 2, 64, 8, 16)
    key = autotune.make_key(jax.default_backend(), "self_attention", geom)
    path = _write_table(tmp_path, {
        "version": autotune.AUTOTUNE_VERSION,
        "entries": {key: {"attn_block_q": 64, "attn_block_k": 32}}})
    monkeypatch.setattr(autotune, "DEFAULT_TABLE_PATH", path)

    assert autotune.lookup("self_attention", geom) == {
        "attn_block_q": 64, "attn_block_k": 32}
    # dispatch resolution: tuned policy takes the table's winner; the
    # untuned policy (and unknown geometries) leave the blocks to the
    # op's geometry rule, which is what reaches the kernel
    tuned = KernelPolicy.autotuned()
    assert dispatch._blocks(tuned, "self_attention", geom) == {
        "attn_block_q": 64, "attn_block_k": 32}
    assert _kernel_blocks(monkeypatch, tuned, geom) == (64, 32)
    assert _kernel_blocks(monkeypatch, KernelPolicy.fused(), geom) \
        == pssa_ops.default_blocks(64)
    unknown = (1, 2, 128, 8, 16)
    assert _kernel_blocks(monkeypatch, tuned, unknown) \
        == pssa_ops.default_blocks(128)


def test_committed_table_is_valid():
    # the repo ships a generated table: it must load (validation is
    # load-time) and its entries must parse back to known ops
    table = autotune.load_table()
    assert table["version"] == autotune.AUTOTUNE_VERSION
    assert table["entries"], "committed table should not be empty"
    for key in table["entries"]:
        backend, op, geom = autotune.parse_key(key)
        assert op in autotune._OPS


def test_tune_smoke_produces_valid_loadable_table(tmp_path):
    # end-to-end: sweep tiny geometries for two cheap families, save,
    # reload through the validating loader, and hit an entry
    geoms = {"bitmap": ((64, 64, 16),), "reuse": ((1, 64, 8, 8),)}
    table = autotune.tune(geoms, reps=1, verbose=False)
    assert len(table["entries"]) == 2
    path = autotune.save_table(table, str(tmp_path / "t.json"))
    loaded = autotune.load_table(path)
    won = autotune.lookup("bitmap", (64, 64, 16), path=path)
    assert won and set(won) == {"bitmap_block_rows"}
    assert loaded["generated_on"]["backend"] == jax.default_backend()


# ----------------------------------------------------------------------------
# Block invariance: counters/outputs identical across tuned block sizes
# ----------------------------------------------------------------------------
def _qkv(b=1, h=2, t=96, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, t, d)) for k in ks)


def test_pssa_counters_bit_identical_across_blocks():
    # t=96 is the ragged knife edge: not a multiple of 64-block configs,
    # so the pad-and-slice path is exercised on both q and k axes
    q, k, v = _qkv(t=96)
    thr = 1.0 / 1024.0
    outs = [pssa_attention(q, k, v, threshold=thr, patch=16,
                           bq=bq, bk=bk, interpret=True)
            for bq, bk in [(128, 128), (64, 32), (96, 48), (32, 64)]]
    base = outs[0]
    for out in outs[1:]:
        np.testing.assert_array_equal(np.asarray(base[1]),
                                      np.asarray(out[1]))  # nnz counter
        np.testing.assert_array_equal(np.asarray(base[2]),
                                      np.asarray(out[2]))  # popcount
        np.testing.assert_allclose(np.asarray(base[0]), np.asarray(out[0]),
                                   rtol=1e-5, atol=1e-5)


def test_bitmap_and_reuse_bit_identical_across_blocks():
    sas = jax.random.uniform(jax.random.PRNGKey(0), (3, 5, 96, 96)) * 2e-3
    base = patch_bitmap(sas, 16, 1e-3, br=64, interpret=True)
    for br in (8, 24, 96, 256):
        got = patch_bitmap(sas, 16, 1e-3, br=br, interpret=True)
        np.testing.assert_array_equal(np.asarray(base[0]),
                                      np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(base[1]),
                                      np.asarray(got[1]))

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 8))
    x_ref = x + 1e-3 * jax.random.normal(jax.random.PRNGKey(2), (2, 96, 8))
    d0, a0 = patch_delta(x, x_ref, patch=16, threshold=1e-3, bp=8,
                         interpret=True)
    for bp in (1, 3, 6):             # 96/16 = 6 patches -> ragged plans
        d, a = patch_delta(x, x_ref, patch=16, threshold=1e-3, bp=bp,
                           interpret=True)
        np.testing.assert_array_equal(np.asarray(a0), np.asarray(a))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d))


def test_autotune_probe_hooks_cover_knobs():
    # every family advertises knobs that are real KernelPolicy fields and
    # produces candidates whose keys match exactly
    for op, (modname, _) in autotune._OPS.items():
        mod = autotune._op_module(op)
        assert mod.AUTOTUNE_KNOBS == autotune._op_knobs(op)
        geom = {"self_attention": (1, 2, 64, 8, 16),
                "cross_attention": (1, 2, 64, 8, 77),
                "bitmap": (64, 64, 16),
                "reuse": (1, 64, 8, 8)}[op]
        cands = mod.autotune_candidates(geom)
        assert cands
        for blocks in cands:
            assert set(blocks) == set(mod.AUTOTUNE_KNOBS)
            for name in blocks:
                assert hasattr(KernelPolicy(), name)


# ----------------------------------------------------------------------------
# Policy surface: autotuned preset, parse, describe
# ----------------------------------------------------------------------------
def test_autotuned_preset_parse_and_describe():
    pol = KernelPolicy.autotuned()
    assert pol.tuned and pol.self_attention == "fused"
    assert KernelPolicy.parse("autotuned") == pol
    # autotuned differs from fused ONLY by the tuned bit
    assert dataclasses.replace(pol, tuned=False) == KernelPolicy.fused()

    spec = KernelPolicy.parse("ffn=dbsc,ffn_quant=int8,tuned=true")
    assert spec.ffn == "dbsc" and spec.ffn_quant == "int8" and spec.tuned
    desc = spec.describe()
    assert desc["tuned"] is True and desc["ffn_quant"] == "int8"

    with pytest.raises(ValueError, match="ffn_quant"):
        KernelPolicy(ffn_quant="int4")
    with pytest.raises(ValueError, match="tuned"):
        KernelPolicy.parse("tuned=maybe")


# ----------------------------------------------------------------------------
# int8 dot_general datapath
# ----------------------------------------------------------------------------
def test_int8_accumulators_bitwise_vs_model():
    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((96, 40), dtype=np.float32))
    w = jnp.array(rng.standard_normal((40, 56), dtype=np.float32))
    imp = jnp.array(rng.random(96) < 0.5)
    for important in (None, imp):
        ref = bitslice_matmul(x, w, important=important, use_kernel=False)
        kern = bitslice_matmul(x, w, important=important, use_kernel=True,
                               interpret=True)
        i8 = bitslice_matmul(x, w, important=important, quant_path="int8")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(i8))
        np.testing.assert_array_equal(np.asarray(kern), np.asarray(i8))
    with pytest.raises(ValueError, match="quant_path"):
        bitslice_matmul(x, w, quant_path="int4")


def test_int8_operands_are_really_int8():
    # the point of the path is the operand dtype XLA sees: int8 inputs,
    # int32 accumulator (hardware integer units), not widened casts
    hi = jnp.full((8, 16), 63, jnp.int32)
    lo = jnp.full((8, 16), 63, jnp.int32)
    w = jnp.full((16, 4), -128, jnp.int32)
    prec = jnp.ones((8, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(bitslice_matmul_int8)(hi, lo, w, prec)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    for eqn in dots:
        assert all(v.aval.dtype == jnp.int8 for v in eqn.invars)
        assert eqn.outvars[0].aval.dtype == jnp.int32
    # worst-case magnitudes round-trip exactly
    np.testing.assert_array_equal(
        np.asarray(bitslice_matmul_int8(hi, lo, w, prec)),
        np.asarray(bitslice_matmul_ref(hi, lo, w, prec)))


# ----------------------------------------------------------------------------
# Engine-level: routing moves nothing but wall-clock
# ----------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine_outputs():
    from repro.diffusion.engine import DiffusionEngine
    from repro.diffusion.pipeline import PipelineConfig, energy_report
    from repro.diffusion.sampler import DDIMConfig

    cfg = PipelineConfig.smoke()
    cfg = dataclasses.replace(
        cfg, ddim=DDIMConfig(num_inference_steps=2, guidance_scale=1.0,
                             tips_active_iters=1))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, cfg.text.max_len),
                              0, cfg.text.vocab_size)
    outs = {}
    for name, pol in [
            ("reference", KernelPolicy.reference()),
            ("fused", KernelPolicy.fused()),
            ("autotuned", KernelPolicy.autotuned()),
            ("dbsc_model", KernelPolicy.parse("ffn=dbsc")),
            ("dbsc_int8", KernelPolicy.parse("ffn=dbsc,ffn_quant=int8"))]:
        eng = DiffusionEngine(cfg, key=jax.random.PRNGKey(0),
                              kernel_policy=pol)
        out = eng.generate(toks, jax.random.PRNGKey(2))
        outs[name] = (np.asarray(out.images),
                      energy_report(cfg, out.stats).summary())
    return outs


def test_engine_bit_identical_across_ffn_quant(engine_outputs):
    # int8 vs modeled DBSC: same integers -> same image, same ledger
    img_model, rep_model = engine_outputs["dbsc_model"]
    img_int8, rep_int8 = engine_outputs["dbsc_int8"]
    np.testing.assert_array_equal(img_int8, img_model)
    assert rep_int8 == rep_model


def test_engine_bit_identical_across_tuned_blocks(engine_outputs):
    # autotuned == fused routing with (possibly) different blocks: block
    # shape is a pure wall-clock lever — image and ledger are pinned
    img_fused, rep_fused = engine_outputs["fused"]
    img_tuned, rep_tuned = engine_outputs["autotuned"]
    np.testing.assert_array_equal(img_tuned, img_fused)
    assert rep_tuned == rep_fused


def test_engine_energy_headline_identical_across_all_policies(
        engine_outputs):
    # integer-counter exactness: the mJ/iter headline never moves with
    # kernel routing, block shape or the int8 datapath
    base = engine_outputs["reference"][1]
    for name, (_, rep) in engine_outputs.items():
        assert rep["mj_per_iter_with_ema"] \
            == base["mj_per_iter_with_ema"], name


def test_engine_int8_image_bounded_vs_float_reference(engine_outputs):
    # vs the FLOAT reference FFN the int8 image is only BOUNDED: the
    # reference fake-quantizes on per-sample scales and accumulates in
    # f32, the DBSC path quantizes on one shared scale and accumulates
    # integers — different numerics, same model (pinned here so the
    # bound is part of the contract, not a hope)
    img_ref = engine_outputs["reference"][0]
    img_int8 = engine_outputs["dbsc_int8"][0]
    rel = (np.linalg.norm(img_int8.astype(np.float64)
                          - img_ref.astype(np.float64))
           / max(np.linalg.norm(img_ref.astype(np.float64)), 1e-12))
    assert rel < 0.05, rel

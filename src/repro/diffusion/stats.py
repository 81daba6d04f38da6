"""Stats pytree for the jittable diffusion engine.

The seed implementation threaded a string-keyed ``stats: dict`` through the
UNet forward and returned one dict per denoising iteration.  That shape is
hostile to whole-loop ``jax.lax.scan``/``jax.jit``: the dict is mutated in
place, its insertion order is an accident of control flow, and per-iteration
collection forces a Python-level sampler loop.

``UNetStats`` replaces it: a frozen dataclass registered as a pytree whose
*static* part (the layer order — ``(tag, resolution)`` pairs derived from
``UNetConfig``) lives in the treedef, and whose *dynamic* part (one
``PSSAStats`` + one ``TIPSResult`` per transformer block, in that fixed
order) are the leaves.  Because the treedef is identical at every denoising
step, a ``lax.scan`` over the sampler stacks every leaf along a leading
``num_steps`` axis — the whole 25-iteration stats trajectory comes back as
one pytree of ``(25, ...)`` arrays.

Parity path: ``step(i)`` / ``unstack()`` recover the per-iteration view and
``as_dict()`` reproduces the seed's ``{"pssa": {"down0.0@16": ...}, ...}``
dict exactly, so every downstream consumer (energy ledger, benchmarks) can
read either representation.  See DESIGN.md §3.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.pssa import PSSAStats
from repro.core.tips import TIPSResult


@dataclasses.dataclass(frozen=True)
class LayerKey:
    """Static identity of one transformer block: tag + feature-map res."""
    tag: str
    resolution: int

    @property
    def name(self) -> str:
        return f"{self.tag}@{self.resolution}"


def attn_layer_order(cfg) -> Tuple[LayerKey, ...]:
    """Transformer blocks in forward-traversal order, derived from config.

    The canonical leaf order of every stats pytree, ``LedgerAccum``
    column order, and reuse-cache layer order — the denoiser contract's
    layer-order rule (DESIGN.md §11).  A config that defines its own
    ``layer_order()`` hook (every registered denoiser family does) is the
    source of truth; plain UNet-shaped configs fall back to the UNet
    traversal formula below.
    """
    order_fn = getattr(cfg, "layer_order", None)
    if callable(order_fn):
        return order_fn()
    return _unet_attn_layer_order(cfg)


def _unet_attn_layer_order(cfg) -> Tuple[LayerKey, ...]:
    """UNet traversal: down stages (attn at ``latent >> i``), optional mid
    block, then up stages (stage ``j`` revisits resolution
    ``latent >> rev[j]``) — mirrors ``unet_forward`` exactly.  A stack of
    depth 1 is one key named after its resnet (``down1.0``); a deeper one
    has a key per basic block (``down2.0.0`` ... ``down2.0.9``), and the
    mid block takes the last stage's depth.
    """
    order = []
    nstages = len(cfg.block_channels)
    depth = getattr(cfg, "transformer_depth", 1)

    def stack(tag, stage):
        d = depth if isinstance(depth, int) else depth[stage]
        res = cfg.latent_size >> stage
        if d == 1:
            return [LayerKey(tag, res)]
        return [LayerKey(f"{tag}.{k}", res) for k in range(d)]

    for i, has_attn in enumerate(cfg.down_attn):
        if not has_attn:
            continue
        for r in range(cfg.resnets_per_down):
            order += stack(f"down{i}.{r}", i)
    if cfg.has_mid_block:
        order += stack("mid", nstages - 1)
    for j, i in enumerate(reversed(range(nstages))):
        if not cfg.down_attn[i]:
            continue
        for r in range(cfg.resnets_per_up):
            order += stack(f"up{j}.{r}", i)
    return tuple(order)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class UNetStats:
    """Per-layer PSSA/TIPS stats in fixed, config-derived order.

    ``layers`` is static (treedef); ``pssa``/``tips`` are tuples of
    per-layer stat pytrees in the same order.  Leaves are scalars (or
    per-query arrays) for a single forward pass, and gain a leading
    ``num_steps`` axis after a scanned sampler run.

    ``reuse`` carries per-layer ``reuse.ReuseRowCounters`` (same order)
    when the forward ran with a temporal-reuse cache; it stays the empty
    tuple — contributing no leaves, so every existing treedef is
    unchanged — on the dense path.
    """
    layers: Tuple[LayerKey, ...]
    pssa: Tuple[PSSAStats, ...]
    tips: Tuple[TIPSResult, ...]
    reuse: Tuple = ()

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return (self.pssa, self.tips, self.reuse), self.layers

    @classmethod
    def tree_unflatten(cls, layers, children):
        pssa, tips, reuse = children
        return cls(layers=layers, pssa=tuple(pssa), tips=tuple(tips),
                   reuse=tuple(reuse))

    # -- views -----------------------------------------------------------
    def __len__(self) -> int:
        return len(self.layers)

    @property
    def num_steps(self) -> int:
        """Leading (scan) axis length; 0 for an unstacked single pass."""
        if not self.pssa:
            return 0
        lead = self.pssa[0].nnz
        return int(lead.shape[0]) if getattr(lead, "ndim", 0) >= 1 else 0

    def step(self, i: int) -> "UNetStats":
        """Per-iteration view of a stacked (scanned) stats pytree."""
        return jax.tree_util.tree_map(lambda x: x[i], self)

    def unstack(self) -> list:
        """Stacked stats -> list of per-step ``UNetStats`` (parity path)."""
        n = self.num_steps
        if n == 0:
            return [self]
        return [self.step(i) for i in range(n)]

    def as_dict(self) -> dict:
        """The seed's ``{"pssa": {...}, "tips": {...}}`` string-keyed view."""
        return {
            "pssa": {k.name: s for k, s in zip(self.layers, self.pssa)},
            "tips": {k.name: t for k, t in zip(self.layers, self.tips)},
        }

    # -- host transfer ---------------------------------------------------
    def ledger_fetch(self) -> "UNetStats":
        """Pull ONLY the scalar ledger leaves to host, in one transfer.

        A sharded engine keeps the stacked stats pytree on device — the
        per-row leaves (``TIPSResult.important`` / ``.cas``) batch-sharded
        across the mesh — until the energy ledger reads it.  The ledger
        consumes just the PSSA byte counters and the TIPS low-precision
        ratios, all scalars per (step, layer): this fetches exactly those
        in a single ``jax.device_get`` (instead of one device round-trip
        per ``float(...)`` in the ledger loops) and leaves the per-row
        leaves where they are.  Values are unchanged — host copies of the
        same arrays — so every report is bit-identical to an on-device
        read.
        """
        pssa_np, low_np, reuse_np = jax.device_get(
            (self.pssa, tuple(t.low_precision_ratio for t in self.tips),
             self.reuse))
        tips_np = tuple(
            t._replace(low_precision_ratio=low)
            for t, low in zip(self.tips, low_np))
        return UNetStats(layers=self.layers, pssa=tuple(pssa_np),
                         tips=tips_np, reuse=tuple(reuse_np))

    # -- construction ----------------------------------------------------
    @classmethod
    def from_layer_list(cls, layers, pssa, tips, reuse=()) -> "UNetStats":
        layers, pssa, tips = tuple(layers), tuple(pssa), tuple(tips)
        reuse = tuple(reuse)
        assert len(layers) == len(pssa) == len(tips), \
            (len(layers), len(pssa), len(tips))
        assert not reuse or len(reuse) == len(layers), \
            (len(reuse), len(layers))
        return cls(layers=layers, pssa=pssa, tips=tips, reuse=reuse)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SlotStats:
    """Per-layer PER-ROW integer counters (continuous-batching stats).

    The slot-serving counterpart of ``UNetStats``: same static layer order,
    but each layer carries ``pssa.PSSARowCounters`` / ``tips.TIPSRowCounters``
    whose leaves are (B,) integer vectors — one entry per batch row.  Rows
    sit at heterogeneous denoising steps under continuous batching, so the
    runtime scatters them into per-iteration ``LedgerAccum`` buckets
    instead of folding them at the source.  Integer addition is exact and
    associative, so any scatter order/occupancy reproduces the one-shot
    folded counters bit-for-bit (DESIGN.md §8).
    """
    layers: Tuple[LayerKey, ...]
    pssa: Tuple                     # per-layer PSSARowCounters
    tips: Tuple                     # per-layer TIPSRowCounters
    reuse: Tuple = ()               # per-layer ReuseRowCounters (or empty)

    def tree_flatten(self):
        return (self.pssa, self.tips, self.reuse), self.layers

    @classmethod
    def tree_unflatten(cls, layers, children):
        pssa, tips, reuse = children
        return cls(layers=layers, pssa=tuple(pssa), tips=tuple(tips),
                   reuse=tuple(reuse))

    def __len__(self) -> int:
        return len(self.layers)

    def counter_matrices(self):
        """Stack per-layer row counters: three (B, L) integer arrays.

        Columns follow ``layers`` order — the same order ``LedgerAccum``
        buckets use.  Returns (nnz, ones_xor, important).
        """
        nnz = jnp.stack([c.nnz for c in self.pssa], axis=1)
        ones_xor = jnp.stack([c.ones_xor for c in self.pssa], axis=1)
        imp = jnp.stack([t.important for t in self.tips], axis=1)
        return nnz, ones_xor, imp

    def reuse_counter_matrices(self):
        """Stack per-layer reuse counters: two (B, L) integer arrays.

        Returns (computed, total) in ``layers`` column order, or ``None``
        when the forward ran the dense path (no reuse counters).
        """
        if not self.reuse:
            return None
        computed = jnp.stack([r.computed for r in self.reuse], axis=1)
        total = jnp.stack([r.total for r in self.reuse], axis=1)
        return computed, total

    @classmethod
    def from_layer_list(cls, layers, pssa, tips, reuse=()) -> "SlotStats":
        layers, pssa, tips = tuple(layers), tuple(pssa), tuple(tips)
        reuse = tuple(reuse)
        assert len(layers) == len(pssa) == len(tips), \
            (len(layers), len(pssa), len(tips))
        assert not reuse or len(reuse) == len(layers), \
            (len(reuse), len(layers))
        return cls(layers=layers, pssa=pssa, tips=tips, reuse=reuse)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class LedgerAccum:
    """Per-DDIM-iteration integer ledger buckets for slot serving.

    One row per denoising iteration, one column per transformer block (in
    ``attn_layer_order``): ``nnz`` / ``ones_xor`` are the PSSA counters,
    ``imp`` the TIPS important-token counts, ``rows`` the number of
    accounted (active-slot) request rows that have executed the iteration.
    All integer — accumulation across steps, slots, and occupancy patterns
    is exact, so the energy report assembled from a drained accumulator is
    bit-identical to the same requests served one-shot
    (``pipeline.energy_report_from_accum``).  Counters are int32 without
    ``jax_enable_x64`` (exact to 2^31 — the same bound ``pssa.compress_stats``
    documents); a smoke-geometry serving run sits orders of magnitude below
    it.
    """
    nnz: jax.Array             # (num_steps, L) int
    ones_xor: jax.Array        # (num_steps, L) int
    imp: jax.Array             # (num_steps, L) int
    rows: jax.Array            # (num_steps,) int
    reuse_computed: jax.Array  # (num_steps, L) int — gathered patches
    reuse_total: jax.Array     # (num_steps, L) int — patch-grid size

    def tree_flatten(self):
        return (self.nnz, self.ones_xor, self.imp, self.rows,
                self.reuse_computed, self.reuse_total), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def zeros(cls, num_steps: int, num_layers: int) -> "LedgerAccum":
        x64 = bool(jax.config.read("jax_enable_x64"))
        dt = jnp.int64 if x64 else jnp.int32
        return cls(nnz=jnp.zeros((num_steps, num_layers), dt),
                   ones_xor=jnp.zeros((num_steps, num_layers), dt),
                   imp=jnp.zeros((num_steps, num_layers), dt),
                   rows=jnp.zeros((num_steps,), dt),
                   reuse_computed=jnp.zeros((num_steps, num_layers), dt),
                   reuse_total=jnp.zeros((num_steps, num_layers), dt))

    def scatter(self, step_idx: jax.Array, active: jax.Array,
                slot_stats: SlotStats) -> "LedgerAccum":
        """Add one slot step's per-row counters into their iteration buckets.

        ``step_idx`` (B,) is each slot's DDIM iteration for the step just
        executed; ``active`` (B,) masks unoccupied slots: their counters
        (UNet garbage) are zeroed BEFORE the scatter, so occupancy can
        never move a bucket.  Out-of-range indices (retired slots) are
        dropped, belt-and-braces on top of the mask.
        """
        nnz, ones_xor, imp = slot_stats.counter_matrices()
        gate = active.astype(self.nnz.dtype)[:, None]
        reuse = slot_stats.reuse_counter_matrices()
        if reuse is None:
            reuse_computed, reuse_total = self.reuse_computed, self.reuse_total
        else:
            computed, total = reuse
            reuse_computed = self.reuse_computed.at[step_idx].add(
                computed.astype(self.nnz.dtype) * gate, mode="drop")
            reuse_total = self.reuse_total.at[step_idx].add(
                total.astype(self.nnz.dtype) * gate, mode="drop")
        return LedgerAccum(
            nnz=self.nnz.at[step_idx].add(
                nnz.astype(self.nnz.dtype) * gate, mode="drop"),
            ones_xor=self.ones_xor.at[step_idx].add(
                ones_xor.astype(self.nnz.dtype) * gate, mode="drop"),
            imp=self.imp.at[step_idx].add(
                imp.astype(self.nnz.dtype) * gate, mode="drop"),
            rows=self.rows.at[step_idx].add(
                active.astype(self.rows.dtype), mode="drop"),
            reuse_computed=reuse_computed,
            reuse_total=reuse_total)


def coerce_per_step_stats(stats) -> list:
    """Normalize any supported stats shape to a per-iteration list.

    Accepts a stacked ``UNetStats`` (scan output), a single ``UNetStats``,
    a list of ``UNetStats``, or the legacy list-of-dicts — returns a list
    with one entry per denoising iteration.
    """
    if isinstance(stats, UNetStats):
        return stats.unstack()
    return list(stats)

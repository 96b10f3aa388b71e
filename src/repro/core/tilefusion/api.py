"""Unified tile-fusion dispatch — the single fused-matmul entrypoint.

``tile_fused_matmul(a, b_or_a1, c)`` computes ``D = a @ (b_or_a1 @ c)``
(GeMM-SpMM when ``b_or_a1`` is dense, SpMM-SpMM when it is a ``CSR``) and
owns the two decisions every call site used to repeat by hand:

  1. **Inspector amortization (paper §4.2.3).**  The Algorithm-1 scheduler
     runs once per (matrix content, tile size, cache budget) and the
     resulting ``DeviceSchedule`` is memoized in a content-keyed cache; a
     second call with the same sparsity pattern skips inspection entirely.
     This is the inspector/executor separation of sparse tiling
     (Cheshmi et al.) realized as a process-wide cache.

  2. **Executor selection (Eq. 3 + capability).**  ``backend="auto"`` picks
     between the Pallas wavefront-0 kernels (uniform schedules on capable
     hardware — TPU, or interpret mode forced via ``PALLAS_INTERPRET=1``;
     both GeMM-SpMM and SpMM-SpMM lower), the XLA vmapped executor, and the
     unfused two-call baseline using the schedule's Eq-3 traffic model:
     patterns that fuse nothing (or would move more bytes fused than
     unfused) fall back to the unfused code.  An explicit ``backend=``
     overrides the choice.

**Hybrid-ELL width cap (``width_cap``).**  Every ELL the executors stream
(wavefront-1 body, SpMM-SpMM op-1, the unfused full-matrix format) is
packed by the shared ``formats.HybridELL`` packer with a width cap —
"auto" picks the traffic-optimal cap from the degree distribution, so one
max-degree hub row of a power-law graph no longer inflates the padded
allocation.  The capped tails travel as COO spill lanes: the unfused
full-matrix SpMM folds them per row (virtual rows of the body's width,
one sorted update each), the fused, Pallas and sharded executors
scatter-add them lane by lane.  The resolved cap is part
of the schedule and ELL cache keys, and the autotune sweep tries
candidate caps alongside tile sizes.

**Tile-size autotuning (``spec.autotune``).**  ``get_schedule`` /
``tile_fused_matmul`` take ``FusionSpec(autotune=True)`` to sweep a small
``ct_size`` × ``cache_size`` grid (``AUTOTUNE_CT_GRID`` ×
``AUTOTUNE_CACHE_SCALES``, plus the caller's own knobs) and keep the
candidate whose Eq-3 predicted fast-memory traffic, scaled by the
schedule's padded-FLOPs overhead, scores best.  The winner is pinned so it
never predicts more traffic than the default ``ct_size=2048`` schedule, and
the sweep result is
memoized in the same content-keyed cache: one sweep per pattern, every
later call is a hit.  The vectorized O(nnz) inspector is what makes the
sweep affordable (candidate count × inspection cost).

**Cache budget.**  Both the schedule cache and the full-matrix ELL cache
are LRU-bounded at ``REPRO_SCHEDULE_CACHE_ENTRIES`` entries each (env var,
default 128); streaming workloads that touch unbounded pattern sets evict
oldest-first instead of growing without bound.
``schedule_cache_stats()`` reports hits/misses/evictions plus live entry
counts of both caches, and the host seconds spent inspecting
(``inspect_s``) and packing ELLs (``pack_s``, with ``ell_hits`` /
``ell_misses``, and the spill fold's ``spill_lanes`` and
``spill_virtual_rows``).  The same work shows in a profile as the host spans
``repro.get_schedule``, ``repro.inspect``, ``repro.pack``,
``repro.digest`` and ``repro.dispatch`` (``repro.trace``).

**One knob object (``spec=``).**  Every dispatch knob below lives on a
frozen ``FusionSpec`` (``spec.py``) and callers pass ``spec=``; the spec's
resolved form (width cap concretized, mesh reduced to ``mesh_key``, inert
shard knobs collapsed on trivial meshes) is the schedule-cache key tail —
shared verbatim by the content key, the autotune key, the bucket publish,
and the custom_vjp backward, so a knob cannot steer dispatch without
keying the cache.  ``spec=`` is the only way to pass a knob: the seam takes
no knob keywords.

**Sharded dispatch (``spec.mesh``).**  A non-trivial ``jax.sharding.Mesh``
partitions the wavefront-0 fused-tile grid row-block over the mesh's row
shards, contiguous tile groups balanced by their Eq-3 cost; the per-shard
executor runs under ``shard_map`` (wavefront 0 is communication-free by
the fusion criterion) and the wavefront-1 halo rows are all-gathered over
the row axis.  The output combine is chosen by priced bytes
(``shard_combine="auto"``): the row-remapped reduce-scatter emits
per-shard owner blocks (zero combine collectives — partials are
owner-disjoint by construction) with psum retained as the simple
fallback.  Multi-axis meshes can split the dense operand's columns over
the trailing axis (``shard_layout="1.5d"``) or additionally peel a depth
axis that replicates wavefront-0 compute and splits the wavefront-1 halo
per depth layer (``"2.5d"``, staged per-layer halo gathers + one depth
psum); ``cost_model.choose_mesh_layout`` weighs all rungs — and the
single-device fallback — by per-device critical-path bytes.
``spec.overlap`` ("auto" | bool) issues the wavefront-1 halo all-gather
*before* the wavefront-0 body so the collective hides under
communication-free compute (double-buffered halo tables;
``shard_comm_model`` prices the hidden bytes as free only up to the
modeled wf0 window).  ``spec.n_repl`` pins the total operand-replication
factor the layout must provide.  The mesh's (axis names, shape) plus the
shard knobs join the schedule-cache key; ``schedule_cache_stats()``
reports mesh-keyed entries as ``mesh_entries`` with per-layout counters
(``layout_1d`` / ``layout_15d`` / ``layout_25d`` / ``layout_fallback``)
plus ``spec_entries`` (distinct resolved specs among live keys), and a
trivial mesh falls back to single-device dispatch.  CPU CI exercises the
real multi-device path via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  See ``sharded.py``.

Everything outside ``core/tilefusion`` (models, examples, the benchmark)
routes through this module; later PRs extend the seam (GPU backend, new layout
rungs) by adding ``FusionSpec`` fields without touching call sites.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ...trace import scope, span
from ..sparse.formats import (CSR, DEFAULT_WIDTH_QUANTILE,
                              csr_content_digest, hybrid_width_cap)
from . import cost_model, fused_ops, reorder, sharded
from .schedule import DeviceSchedule, to_device_schedule
from .scheduler import Schedule, build_schedule
from .spec import FusionSpec


def _shard_for_mesh(a: CSR, sched, dsched, mk: tuple, *, b_col: int,
                    c_col: int, b_is_sparse: bool, width_cap,
                    shard_combine: str, shard_layout: str,
                    dtype_bytes: int = 4, overlap="auto",
                    n_repl: int | None = None, serial_bytes: float = 0.0):
    """Mesh-shape-aware shard build: resolve how the mesh's axes are used
    (pure-1D row shards, 1.5D row × column-replica, 2.5D row × replica ×
    depth) and which output combine runs, then build the per-shard
    schedule.

    ``shard_layout="auto"`` consults ``cost_model.choose_mesh_layout``,
    which weighs every layout's per-device critical-path bytes (halo
    discounted by the ``overlap`` window, combine, depth psum) plus the
    serial compute split across the row shards against the operand bytes
    replication copies; when the chooser's winner is the single-device
    fallback, the entry carries ``shard=None`` and dispatch stays
    Eq-3-consistent with ``select_backend``.  ``n_repl`` restricts the
    candidates to layouts whose total replication factor (column replicas
    × depth) matches, or — with an explicit layout — validates it.
    ``shard_combine="auto"`` defers to ``shard_comm_model``'s
    psum-vs-reduce-scatter pricing inside the builder."""
    from .scheduler import resolve_mesh_layout
    shape = mk[1]
    layout = shard_layout
    # wf0's Eq-3 share bounds the overlap window the chooser prices; the
    # builder re-resolves "auto" overlap with its exact per-tile costs
    wf0_bytes = float(serial_bytes) * float(getattr(sched, "fused_ratio",
                                                    0.0))
    if layout == "auto":
        operand_bytes = (
            float(a.nnz) * (dtype_bytes + cost_model.INDEX_BYTES)
            + float(dsched.n_i * b_col) * dtype_bytes)
        choice = cost_model.choose_mesh_layout(
            shape, halo_rows=int(dsched.wf1_dep_rows().shape[0]),
            n_i=dsched.n_i, n_j=dsched.n_j, c_col=c_col,
            operand_bytes=operand_bytes, dtype_bytes=dtype_bytes,
            serial_bytes=float(serial_bytes), overlap=overlap,
            wf0_bytes=wf0_bytes)
        if n_repl is not None:
            cands = {k: v for k, v in choice["candidates"].items()
                     if k != "fallback"
                     and v["n_repl"] * v["n_depth"] == int(n_repl)}
            if not cands:
                raise ValueError(
                    f"n_repl={n_repl} is unsatisfiable on mesh shape "
                    f"{shape}: no layout replicates the operands "
                    f"{n_repl}x")
            rank = ("total_per_device" if serial_bytes > 0.0
                    else "total_bytes")
            layout = min(cands, key=lambda k: cands[k][rank])
        else:
            layout = choice["layout"]
        if layout == "fallback":
            return None
    else:
        _, nr, nd = resolve_mesh_layout(shape, layout)
        if n_repl is not None and nr * nd != int(n_repl):
            raise ValueError(
                f"n_repl={n_repl} does not match layout {layout!r} on "
                f"mesh shape {shape} (resolves to {nr}x{nd} replicas)")
    return sharded.build_sharded_schedule(
        a, sched, dsched, shape, b_col=b_col, c_col=c_col,
        b_is_sparse=b_is_sparse, width_cap=width_cap, layout=layout,
        combine=shard_combine, dtype_bytes=dtype_bytes, overlap=overlap)


def _shard_knobs_key(mk: tuple | None, shard_combine: str,
                     shard_layout: str) -> tuple:
    """Validated cache-key component for the sharding knobs: a typo'd knob
    must fail loudly (never silently fall back to another layout), and on
    a trivial mesh the pair collapses to (None, None) so ``mesh=None`` and
    a 1-device mesh keep sharing entries regardless of the (then inert)
    knob values."""
    from .scheduler import MESH_LAYOUTS
    if shard_combine not in sharded.COMBINE_MODES + ("auto",):
        raise ValueError(
            f"shard_combine={shard_combine!r}; expected one of "
            f"{sharded.COMBINE_MODES + ('auto',)}")
    if shard_layout not in MESH_LAYOUTS + ("auto",):
        raise ValueError(f"shard_layout={shard_layout!r}; expected one of "
                         f"{MESH_LAYOUTS + ('auto',)}")
    if mk is None:
        return (None, None)
    return (str(shard_combine), str(shard_layout))


def _coerce_spec(spec, caller: str) -> FusionSpec:
    """``spec=None`` is the default spec; anything but a FusionSpec raises."""
    if spec is None:
        return FusionSpec()
    if not isinstance(spec, FusionSpec):
        raise TypeError(f"{caller}() spec= expects a FusionSpec, got "
                        f"{type(spec).__name__}")
    return spec


def _spec_key(spec: FusionSpec, *, cap, mk, sk) -> tuple:
    """THE resolved-spec cache-key tail, shared by every key site (content
    key, autotune key, bucket publish).  ``cap``/``mk``/``sk`` are the
    already-resolved width cap, mesh key, and shard-knob pair; on a
    trivial mesh the overlap/n_repl knobs are inert and collapse to None
    so ``mesh=None`` entries share regardless of their values.
    ``spec.dtype_bytes`` must be resolved (int) by the time a key is cut."""
    if mk is None:
        ov, nr = None, None
    else:
        ov = spec.overlap
        nr = None if spec.n_repl is None else int(spec.n_repl)
    return (int(spec.p), float(spec.cache_size), int(spec.ct_size),
            bool(spec.uniform_split), cap, mk, sk, ov, nr,
            bool(spec.transpose), int(spec.dtype_bytes), spec.reorder)


#: Valid ``backend=`` values for tile_fused_matmul.
BACKENDS = ("auto", "pallas", "xla", "unfused", "sharded")

#: Below this Eq-2 fused ratio the schedule fuses so little that the fused
#: executor's padding/scatter overhead cannot pay for itself — dispatch to
#: the unfused baseline instead.
MIN_FUSED_RATIO = 0.02

#: Minimum modeled Eq-3 traffic saving the tiled executors must clear.  The
#: byte model prices data movement only; the tile loop's fixed costs (per-
#: tile gathers, wavefront barrier, D1 scatter) are off-model, so a saving
#: in the low single digits reliably loses to the plain hybrid SpMM in wall
#: clock (measured on hub-heavy power-law graphs, where ~5% modeled saving
#: ran ~30% slower fused).  Friendly patterns (banded, block-diagonal)
#: model 25%+ and clear this floor easily.
MIN_TRAFFIC_SAVING = 0.10

#: The paper's ct_size heuristic (§4: ratio gains saturate past 2048); the
#: autotune sweep is anchored on it — the winner never predicts more Eq-3
#: traffic than this default.
DEFAULT_CT_SIZE = 2048

#: Coarse tile sizes the autotune sweep tries (the caller's ct_size and the
#: 2048 anchor are always added).
AUTOTUNE_CT_GRID = (512, 1024, 2048, 4096)

#: Cache-budget scales the sweep tries per tile size: the full budget and a
#: half budget (step 2 splits earlier, trading padding for locality).
AUTOTUNE_CACHE_SCALES = (1.0, 0.5)

#: Env var capping both the schedule cache and the ELL cache (entries).
CACHE_ENTRIES_ENV = "REPRO_SCHEDULE_CACHE_ENTRIES"
DEFAULT_CACHE_ENTRIES = 128


# --------------------------------------------------------------------------
# Inspector cache
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ScheduleEntry:
    """One memoized inspection: host schedule + device schedule + metadata.

    Entries live until evicted LRU (``REPRO_SCHEDULE_CACHE_ENTRIES``; the
    amortization contract: one pattern, many runs).  Workloads that stream
    *new* patterns either rely on the LRU bound or call
    ``clear_schedule_cache()`` between phases.
    """

    sched: Schedule
    dsched: DeviceSchedule
    b_col: int
    c_col: int
    b_is_sparse: bool
    #: Eq-3-derived fast-memory traffic prediction, computed once at build
    #: (select_backend reads it on every "auto" call)
    traffic_model: dict = dataclasses.field(default_factory=dict)
    #: set on autotune winners: the (ct_size, cache_size, width_cap) the
    #: sweep picked
    autotuned: tuple | None = None
    #: resolved hybrid-ELL width cap the schedule was packed with (None =
    #: pad-to-max); part of the cache key, consumed by the executors
    width_cap: int | None = None
    #: ``sharded.mesh_key`` of the mesh this entry was inspected for (None
    #: for single-device entries); part of the cache key — the same matrix
    #: on a different mesh shape is a different schedule
    mesh_key: tuple | None = None
    #: per-shard restructuring (``sharded.ShardedSchedule``) when the entry
    #: was built for a non-trivial mesh and the grid is uniform; None means
    #: dispatch falls back to single-device execution
    shard: object = None
    #: content digest of the matrix this entry was inspected (or patched)
    #: for.  Bucket-keyed entries are looked up by *shape bucket*, not
    #: content, so the dispatch verifies this against the request before
    #: trusting a hit; None on autotune sweep entries
    content_digest: bytes | None = None
    #: the ``(rows, cols, width_cap)`` shape bucket this entry serves
    #: (``serving.ServingTier``), None for plain content-keyed entries
    bucket: tuple | None = None
    #: True when this entry was inspected on ``a.transpose()`` — the
    #: SpMM-SpMM custom_vjp's backward schedule, keyed by the *forward*
    #: digest plus this bit so fwd and bwd entries live side by side
    transpose: bool = False
    #: itemsize of the dense operand the entry prices traffic for; part of
    #: the cache key (bf16 and f32 move different bytes through Eq 3)
    dtype_bytes: int = 4
    #: reorder transform baked into the schedule ("rcm" | "similarity";
    #: None = identity ordering — including ``reorder="auto"`` builds
    #: where no candidate cleared the Eq-3 floor)
    reorder: str | None = None
    #: the symmetric row/col permutation the schedule was inspected under
    #: (``perm[new] = old``) and its inverse; dispatch permutes the dense
    #: operands in and the output back out — callers never apply/undo it
    reorder_perm: np.ndarray | None = None
    reorder_inv: np.ndarray | None = None


_schedule_cache: "collections.OrderedDict" = collections.OrderedDict()
_ell_cache: "collections.OrderedDict" = collections.OrderedDict()
#: ``inspect_s`` sums the host seconds of every inspection (cache misses
#: and the serving tier's incremental patches), ``pack_s`` those of every
#: ELL pack (``_csr_ell`` misses and the op-1 pack); ``spill_lanes`` and
#: ``spill_virtual_rows`` sum the spill fold over ``_csr_ell`` misses
_stats = {"hits": 0, "misses": 0, "evictions": 0, "ell_evictions": 0,
          "autotune_sweeps": 0, "incremental_patches": 0,
          "inspect_s": 0.0, "pack_s": 0.0, "ell_hits": 0, "ell_misses": 0,
          "spill_lanes": 0, "spill_virtual_rows": 0}
_lock = threading.Lock()
#: The ELL cache has its own lock so its atomic check-and-build (which can
#: allocate a full-matrix padded ELL) never stalls schedule-cache hits.
#: Lock order where both are held: _lock, then _ell_lock.
_ell_lock = threading.Lock()


def count_inspect(seconds: float) -> None:
    """Add one inspection's host seconds to ``inspect_s``."""
    with _lock:
        _stats["inspect_s"] += seconds


def count_pack(seconds: float) -> None:
    """Add one ELL pack's host seconds to ``pack_s``."""
    with _ell_lock:
        _stats["pack_s"] += seconds


def _cache_budget() -> int:
    """Per-cache entry cap from ``REPRO_SCHEDULE_CACHE_ENTRIES`` (>= 1)."""
    raw = os.environ.get(CACHE_ENTRIES_ENV, "")
    try:
        return max(int(raw), 1)
    except ValueError:
        return DEFAULT_CACHE_ENTRIES


def _cache_get(cache, key):
    """LRU lookup; caller holds ``_lock``."""
    value = cache.get(key)
    if value is not None:
        cache.move_to_end(key)
    return value


def _cache_put(cache, key, value, evict_key: str = "evictions") -> None:
    """LRU insert with oldest-first eviction; caller holds the cache's lock.

    Each cache bumps its own eviction counter (``evict_key``) so the two
    locks never contend on one non-atomic ``+=``."""
    cache[key] = value
    cache.move_to_end(key)
    budget = _cache_budget()
    while len(cache) > budget:
        cache.popitem(last=False)
        _stats[evict_key] += 1


def _content_key(a: CSR) -> bytes:
    """Content hash of a CSR matrix (``formats.csr_content_digest``).  The
    schedule *structure* depends only on the pattern, but the DeviceSchedule
    bakes in the values (ELL), so the key covers both — same pattern with
    new values rebuilds, same matrix content always hits."""
    return csr_content_digest(a)


def _resolve_width_cap(a: CSR, width_cap) -> int | None:
    """Resolve the ``width_cap`` knob to a concrete cap (the cache key).

    ``"auto"`` derives the traffic-optimal cap from the matrix's own degree
    distribution (``formats.hybrid_width_cap``); ``None`` disables capping
    (pad-to-max, the pre-hybrid layout); an int is clamped to >= 1."""
    if width_cap is None:
        return None
    if width_cap == "auto":
        # memoized per CSR instance (treated as immutable, like the content
        # digest): the cap search sorts the degree distribution once, not
        # once per hot-path call
        cap = getattr(a, "_auto_width_cap", None)
        if cap is None:
            cap = hybrid_width_cap(np.diff(a.indptr))
            object.__setattr__(a, "_auto_width_cap", cap)
        return cap
    return max(int(width_cap), 1)


def _candidate_width_caps(a: CSR, caller_cap: int | None) -> list:
    """Caps the autotune sweep tries: the caller's, the traffic-optimal,
    the high-quantile, and pad-to-max (as an explicit max-degree cap)."""
    counts = np.diff(a.indptr)
    w_max = max(int(counts.max()), 1) if counts.size else 1
    caps = {w_max if caller_cap is None else caller_cap,
            hybrid_width_cap(counts),
            hybrid_width_cap(counts, DEFAULT_WIDTH_QUANTILE),
            w_max}
    return sorted(caps)


def _packed_ell_bytes(a: CSR, dsched: DeviceSchedule, b_is_sparse: bool,
                      dtype_bytes: int = 4) -> float:
    """Bytes the executors stream for the *packed* sparse operands: the
    wavefront-1 hybrid body (col+val per slot, padding included) plus 3
    elements per spill lane, and — for SpMM-SpMM — the op-1 hybrid at the
    schedule's cap (op-1 ≈ A, the cost model's standing caveat).  This is
    the term the width cap actually moves (Eq-3 traffic is cap-invariant),
    so the autotune sweep scores with it.  Value slots are priced at the
    operand itemsize, column-index slots always at ``INDEX_BYTES``."""
    vals = float(dsched.ell_cols1.size + dsched.spill_rows1.size)
    idx = float(dsched.ell_cols1.size
                + (cost_model.SPILL_ELEMENTS - 1) * dsched.spill_rows1.size)
    if b_is_sparse:
        # one arithmetic, owned by cost_model (a.n_cols = no-cap sentinel:
        # no row can be wider, so the clamp resolves it to pad-to-max)
        w = cost_model._capped_body_width(
            a, dsched.width_cap if dsched.width_cap is not None
            else max(a.n_cols, 1))
        spill = int(cost_model._spill_cumsum(a, w)[-1])
        vals += float(a.n_rows * w + spill)
        idx += float(a.n_rows * w + (cost_model.SPILL_ELEMENTS - 1) * spill)
    return vals * dtype_bytes + idx * cost_model.INDEX_BYTES


def get_schedule(a: CSR, *, b_col: int, c_col: int,
                 b_is_sparse: bool = False,
                 spec: FusionSpec | None = None) -> ScheduleEntry:
    """Run Algorithm 1 once per (content, resolved spec) and memoize;
    subsequent calls with the same key return the cached entry without
    touching the scheduler.

    Every knob lives on the ``FusionSpec`` (``spec=``).
    ``spec.dtype_bytes=None`` defaults to 4 here — without operands there
    is nothing to infer from (``tile_fused_matmul`` infers before it
    reaches this point).

    Note: ``spec.uniform_split`` defaults to True (unlike raw
    ``build_schedule``) — the uniform variant is what the zero-padding XLA
    fast path and the Pallas kernel's grid map 1:1 onto.  Call sites that
    want the paper's recursive step-2 splitting set it explicitly.

    ``spec.autotune=True`` replaces the single inspection with a memoized
    Eq-3 sweep over tile sizes, cache budgets, and hybrid width caps (see
    module docs); the spec's own ``ct_size`` / ``cache_size`` /
    ``width_cap`` then seed the candidate grid instead of being used
    verbatim.

    ``spec.width_cap`` bounds the hybrid-ELL body width (wavefront 1
    always; op-1 packing and Eq-3 op-1 pricing when ``b_is_sparse``):
    ``"auto"`` (default) picks the traffic-optimal cap from the degree
    distribution, ``None`` disables capping (pad-to-max).  The resolved
    cap is part of the cache key — changing it can never reuse a stale
    schedule.

    ``spec.mesh`` (a ``jax.sharding.Mesh``) additionally partitions the
    wavefront-0 tile grid over the mesh's devices (row-block,
    Eq-3-balanced) and attaches the per-shard arrays + halo index sets as
    ``entry.shard``.  ``spec.shard_layout``
    ("auto" | "1d" | "1.5d" | "2.5d") picks how a multi-axis mesh's axes
    are used, ``spec.shard_combine`` ("auto" | "psum" | "reduce_scatter")
    the output combine, ``spec.overlap`` whether the wavefront-1 halo
    gather hides under wavefront-0 compute, and ``spec.n_repl`` the
    required operand-replication factor; all join the cache key alongside
    the mesh's (axis names, shape): the same matrix on a different mesh
    shape or layout re-inspects.  A trivial (single-device or None) mesh
    keys and dispatches exactly like no mesh — the then-inert shard knobs
    collapse out of the key.  When ``"auto"`` layout pricing concludes
    even the best mesh layout moves more bytes than single-device
    execution, ``entry.shard`` stays None (the priced fallback).

    ``spec.bucket`` (the serving tier's knob — see ``serving.ServingTier``)
    replaces the content digest in the cache key with the given shape
    bucket, so every request padded into the same bucket shares one
    entry instead of each pattern minting its own.  Because the key no
    longer pins the content, a hit is only trusted when the entry's
    ``content_digest`` matches the request (the tier keeps it current via
    ``store_bucket_schedule``); a mismatch re-inspects and *replaces* the
    entry under the same key — never a second cache slot, so N patterns
    in one bucket occupy exactly one entry.  v1 is single-device:
    ``bucket`` with ``autotune`` or a non-trivial ``mesh`` raises.

    ``spec.transpose=True`` inspects ``a.transpose()`` instead — the
    SpMM-SpMM backward's schedule.  The key stays on the *forward*
    matrix's digest plus the transpose bit, so the fwd/bwd pair of one
    training step shares one digest computation and shows up side by
    side in the cache (``schedule_cache_stats()["transpose_entries"]``).
    ``b_col`` / ``c_col`` are the dimensions of the transposed product —
    the caller passes them already swapped.

    ``spec.dtype_bytes`` is the dense operand's itemsize; it scales the
    Eq-3 value traffic (index traffic stays at 4 bytes) and joins the
    cache key so bf16 and f32 runs of one pattern price — and autotune —
    separately.

    ``spec.reorder`` makes bandwidth-reducing reordering a schedule
    transform: the pattern is symmetrically permuted (RCM or the
    similarity grouping; ``"auto"`` tries both) before inspection, the
    candidate priced by the same Eq-3 model as dispatch, and — when it
    applies — the permutation baked into the entry
    (``reorder_perm``/``reorder_inv``); ``tile_fused_matmul`` permutes
    the dense operands in and the output back out, so callers never see
    the reordered frame.  ``"auto"`` applies only when the modeled fused
    traffic beats the identity by ``MIN_TRAFFIC_SAVING`` and skips
    rectangular patterns; a forced ordering raises on them.  The knob
    joins the cache key (``_spec_key``); it does not compose with
    ``bucket``."""
    with span("get_schedule"):
        spec = _coerce_spec(spec, "get_schedule")
        if spec.dtype_bytes is None:
            spec = dataclasses.replace(spec, dtype_bytes=4)
        else:
            spec = dataclasses.replace(spec, dtype_bytes=int(spec.dtype_bytes))
        transpose = spec.transpose
        a_eff = a.transpose() if transpose else a
        cap = _resolve_width_cap(a_eff, spec.width_cap)
        mk = sharded.mesh_key(spec.mesh)
        sk = _shard_knobs_key(mk, spec.shard_combine, spec.shard_layout)
        bucket = spec.bucket
        if bucket is not None:
            if spec.autotune:
                raise ValueError("bucket= does not compose with autotune=True "
                                 "(the sweep is per-content; bucket entries "
                                 "are shape-keyed)")
            if mk is not None:
                raise ValueError("bucket= is single-device (v1); pass a "
                                 "trivial mesh or none")
            if transpose:
                raise ValueError("bucket= is a serving (inference) knob; it "
                                 "does not compose with transpose=True")
            if spec.reorder is not None:
                raise ValueError("bucket= does not compose with reorder= — "
                                 "the incremental inspector patches by row "
                                 "position, which a baked permutation would "
                                 "silently invalidate")
        if spec.autotune:
            return _autotune_schedule(a, b_col=b_col, c_col=c_col,
                                      b_is_sparse=b_is_sparse, spec=spec,
                                      cap=cap, mk=mk, sk=sk)
        digest = _content_key(a)
        keybase = ("bucket", bucket) if bucket is not None else digest
        key = (keybase, b_col, c_col, b_is_sparse,
               _spec_key(spec, cap=cap, mk=mk, sk=sk))
        with _lock:
            entry = _cache_get(_schedule_cache, key)
            if entry is not None and (bucket is None
                                      or entry.content_digest == digest):
                _stats["hits"] += 1
                return entry
        t0 = time.perf_counter()
        with span("inspect"):
            sched = build_schedule(a_eff, b_col=b_col, c_col=c_col,
                                   p=spec.p, cache_size=spec.cache_size,
                                   ct_size=spec.ct_size,
                                   b_is_sparse=b_is_sparse,
                                   uniform_split=spec.uniform_split,
                                   width_cap=cap)
            dsched = to_device_schedule(a_eff, sched, width_cap=cap)
            tm = dsched.hbm_traffic_model(b_col, c_col,
                                          dtype_bytes=spec.dtype_bytes)
            a_sched = a_eff
            applied = perm = inv = None
            if spec.reorder is not None:
                picked = _priced_reorder(a_eff, spec, cap=cap, b_col=b_col,
                                         c_col=c_col, b_is_sparse=b_is_sparse,
                                         base_tm=tm)
                if picked is not None:
                    applied, perm, inv, a_sched, sched, dsched, tm = picked
            tm["packed_ell_bytes"] = _packed_ell_bytes(
                a_sched, dsched, b_is_sparse, spec.dtype_bytes)
            shard = None
            if mk is not None:
                shard = _shard_for_mesh(
                    a_sched, sched, dsched, mk, b_col=b_col, c_col=c_col,
                    b_is_sparse=b_is_sparse, width_cap=cap,
                    shard_combine=sk[0], shard_layout=sk[1],
                    dtype_bytes=spec.dtype_bytes, overlap=spec.overlap,
                    n_repl=spec.n_repl, serial_bytes=tm["fused_bytes"])
                if shard is not None:
                    tm["sharded"] = shard.comm_model
        entry = ScheduleEntry(sched=sched, dsched=dsched, b_col=b_col,
                              c_col=c_col, b_is_sparse=b_is_sparse,
                              traffic_model=tm, width_cap=cap,
                              mesh_key=mk, shard=shard,
                              content_digest=digest,
                              bucket=bucket,
                              transpose=transpose,
                              dtype_bytes=spec.dtype_bytes,
                              reorder=applied, reorder_perm=perm,
                              reorder_inv=inv)
        with _lock:
            _stats["misses"] += 1
            _stats["inspect_s"] += time.perf_counter() - t0
            _cache_put(_schedule_cache, key, entry)
        return entry


def _priced_reorder(a_eff: CSR, spec: FusionSpec, *, cap, b_col: int,
                    c_col: int, b_is_sparse: bool, base_tm: dict):
    """Resolve ``spec.reorder`` into an applied schedule transform.

    Builds a full candidate schedule per ordering (RCM, or the
    binary-row-merging similarity grouping; ``"auto"`` tries both) on the
    symmetrically permuted pattern and prices it with the same Eq-3
    tile-cost aggregation the dispatch floor uses (``fused_bytes`` is the
    ``tile_costs_batch`` sum).  A forced ordering always applies; "auto"
    applies the best candidate only when its modeled fused traffic beats
    the identity ordering by ``MIN_TRAFFIC_SAVING`` — the same
    bytes-model-vs-off-model-fixed-costs floor ``select_backend`` trusts —
    so "auto" can never raise modeled traffic.  Returns ``(name, perm,
    inv, a_perm, sched, dsched, tm)`` or None for the identity.

    The symmetric permutation P·A·Pᵀ needs a square matrix; "auto" skips
    rectangular patterns quietly, a forced ordering raises (the old
    ``permute_csr`` silently corrupted this case)."""
    if a_eff.n_rows != a_eff.n_cols:
        if spec.reorder == "auto":
            return None
        raise ValueError(
            f"reorder={spec.reorder!r} needs a square matrix (symmetric "
            f"permutation P·A·Pᵀ); got ({a_eff.n_rows}, {a_eff.n_cols}). "
            f"Use reorder='auto' to skip rectangular patterns.")
    names = (("rcm", "similarity") if spec.reorder == "auto"
             else (spec.reorder,))
    best = None
    for name in names:
        fn = reorder.rcm_order if name == "rcm" else reorder.similarity_order
        cand_perm = fn(a_eff)
        a_p = reorder.permute_csr(a_eff, cand_perm)
        sched_p = build_schedule(a_p, b_col=b_col, c_col=c_col, p=spec.p,
                                 cache_size=spec.cache_size,
                                 ct_size=spec.ct_size,
                                 b_is_sparse=b_is_sparse,
                                 uniform_split=spec.uniform_split,
                                 width_cap=cap)
        dsched_p = to_device_schedule(a_p, sched_p, width_cap=cap)
        tm_p = dsched_p.hbm_traffic_model(b_col, c_col,
                                          dtype_bytes=spec.dtype_bytes)
        if best is None or tm_p["fused_bytes"] < best[5]["fused_bytes"]:
            best = (name, cand_perm, a_p, sched_p, dsched_p, tm_p)
    name, cand_perm, a_p, sched_p, dsched_p, tm_p = best
    if (spec.reorder == "auto"
            and cost_model.reorder_gain(base_tm, tm_p) < MIN_TRAFFIC_SAVING):
        return None
    inv = np.empty_like(cand_perm)
    inv[cand_perm] = np.arange(cand_perm.shape[0])
    return name, cand_perm, inv, a_p, sched_p, dsched_p, tm_p


def store_bucket_schedule(entry: ScheduleEntry, *, bucket: tuple,
                          patched: bool = False,
                          spec: FusionSpec | None = None) -> ScheduleEntry:
    """Publish a serving-tier entry (headroom-padded at bucket build, or
    patched by the incremental inspector) under its bucket cache key,
    replacing whatever the bucket held.

    The key is cut by the same ``_spec_key`` helper ``get_schedule`` uses
    (bucket keybase, the entry's own resolved width cap, trivial-mesh
    collapse, transpose forced off — buckets are inference-only), so the
    next ``tile_fused_matmul(..., spec=...bucket...)`` dispatch finds this
    entry; ``entry.content_digest`` must already name the pattern it
    serves.  ``patched=True`` counts the publish as an incremental patch
    in ``schedule_cache_stats()``."""
    if entry.content_digest is None:
        raise ValueError("bucket entries need content_digest set")
    spec = _coerce_spec(spec, "store_bucket_schedule")
    spec = dataclasses.replace(
        spec, transpose=False, mesh=None, reorder=None,
        dtype_bytes=4 if spec.dtype_bytes is None else int(spec.dtype_bytes))
    key = (("bucket", tuple(bucket)), entry.b_col, entry.c_col,
           entry.b_is_sparse,
           _spec_key(spec, cap=entry.width_cap, mk=None, sk=(None, None)))
    entry.bucket = tuple(bucket)
    with _lock:
        if patched:
            _stats["incremental_patches"] += 1
        _cache_put(_schedule_cache, key, entry)
    return entry


def _autotune_schedule(a: CSR, *, b_col: int, c_col: int,
                       b_is_sparse: bool, spec: FusionSpec, cap: int | None,
                       mk: tuple | None, sk: tuple) -> ScheduleEntry:
    """Eq-3 tile-size × width-cap sweep, memoized under its own entry.

    Candidates: (AUTOTUNE_CT_GRID ∪ {spec.ct_size, 2048}) ×
    AUTOTUNE_CACHE_SCALES × candidate width caps
    (``_candidate_width_caps``).  Ranking: Eq-3 predicted fast-memory
    traffic (``fused_bytes``) scaled by the schedule's padded-FLOPs
    overhead, plus the packed-ELL bytes the cap actually moves; restricted
    to candidates whose raw traffic does not exceed the default
    ``ct_size=2048`` schedule's at the caller's cap — the anchor itself is
    always a candidate, so the sweep can only improve on the paper's
    heuristic, never regress it.

    ``cap`` / ``mk`` / ``sk`` are the caller-resolved width cap, mesh key,
    and shard-knob pair; the key is the same ``_spec_key`` tail as every
    other cache site, under the "autotune" prefix.
    """
    transpose = spec.transpose
    cache_size = spec.cache_size
    key = ("autotune", _content_key(a), b_col, c_col, b_is_sparse,
           _spec_key(spec, cap=cap, mk=mk, sk=sk))
    with _lock:
        entry = _cache_get(_schedule_cache, key)
        if entry is not None:
            _stats["hits"] += 1
            return entry

    a_eff = a.transpose() if transpose else a
    cts = sorted(set(AUTOTUNE_CT_GRID) | {spec.ct_size, DEFAULT_CT_SIZE})
    if cap is None:
        # pad-to-max resolves to the max-degree cap so keys stay concrete
        counts = np.diff(a_eff.indptr)
        anchor_cap = max(int(counts.max()), 1) if counts.size else 1
    else:
        anchor_cap = cap
    # the cap only reaches Algorithm 1 through the sparse-op-1 Eq-3 charge;
    # for dense B every cap yields the identical host schedule, so sweeping
    # caps there would just re-run the same inspection — keep the caller's
    caps = _candidate_width_caps(a_eff, cap) if b_is_sparse \
        else [anchor_cap]
    candidates: dict = {}
    for ct in cts:
        for scale in AUTOTUNE_CACHE_SCALES:
            for cand_cap in caps:
                cand_spec = dataclasses.replace(
                    spec, autotune=False, cache_size=cache_size * scale,
                    ct_size=ct, width_cap=cand_cap, mesh=None)
                cand = get_schedule(a, b_col=b_col, c_col=c_col,
                                    b_is_sparse=b_is_sparse,
                                    spec=cand_spec)
                candidates[(ct, cache_size * scale, cand_cap)] = cand

    def traffic(e: ScheduleEntry) -> float:
        return e.traffic_model["fused_bytes"]

    def score(e: ScheduleEntry) -> float:
        return (traffic(e)
                * (1.0 + e.dsched.padded_flops_overhead(b_col, c_col))
                + e.traffic_model["packed_ell_bytes"])

    anchor = candidates[(DEFAULT_CT_SIZE, cache_size, anchor_cap)]
    eligible = {k: e for k, e in candidates.items()
                if traffic(e) <= traffic(anchor)}
    best_key = min(eligible, key=lambda k: score(eligible[k]))
    # each candidate's inspection already counted into inspect_s
    best = dataclasses.replace(eligible[best_key], autotuned=best_key)
    if mk is not None:
        # the sweep's candidates are mesh-free; shard the winner (a fresh
        # traffic_model dict so the single-device candidate stays untouched).
        # A reordered winner must be sharded on the *permuted* matrix its
        # schedule was inspected under, not the caller's ordering.
        a_shard = (reorder.permute_csr(a_eff, best.reorder_perm)
                   if best.reorder_perm is not None else a_eff)
        shard = _shard_for_mesh(a_shard, best.sched, best.dsched, mk,
                                b_col=b_col, c_col=c_col,
                                b_is_sparse=b_is_sparse,
                                width_cap=best.width_cap,
                                shard_combine=sk[0],
                                shard_layout=sk[1],
                                dtype_bytes=spec.dtype_bytes,
                                overlap=spec.overlap, n_repl=spec.n_repl,
                                serial_bytes=best.traffic_model[
                                    "fused_bytes"])
        tm = dict(best.traffic_model)
        if shard is not None:
            tm["sharded"] = shard.comm_model
        best = dataclasses.replace(best, mesh_key=mk, shard=shard,
                                   traffic_model=tm)
    with _lock:
        # first-wins publish: a concurrent sweep on the same key may have
        # finished while we ran (the candidates it used were memoized, so
        # the duplicate work is bounded); only the published sweep counts
        existing = _cache_get(_schedule_cache, key)
        if existing is not None:
            _stats["hits"] += 1
            return existing
        _stats["autotune_sweeps"] += 1
        _cache_put(_schedule_cache, key, best)
    return best


def _csr_ell(a: CSR, width_cap: int | None = None) -> fused_ops.FoldedELL:
    """Memoized full-matrix hybrid ELL (the unfused executor's format),
    keyed on (content, width cap).  A miss counts its spill lanes and
    virtual rows into ``schedule_cache_stats()``.

    Check-and-insert happens under a single ``_ell_lock`` acquisition: the
    previous read-then-write pattern let two threads race past the miss
    check and both build (and publish) the ELL arrays.  The dedicated lock
    means a large build never blocks schedule-cache hits.

    The build runs under ``jax.ensure_compile_time_eval()``: a miss can
    happen inside a trace (the custom_vjp backward builds the Aᵀ ELL while
    ``jax.grad`` traces), and ``jnp.asarray`` under an active trace yields
    a *tracer* — caching that would poison every later trace with a leaked
    value.  The guard forces concrete arrays no matter where the miss
    lands."""
    key = (_content_key(a), width_cap)
    with _ell_lock:
        ell = _cache_get(_ell_cache, key)
        if ell is not None:
            _stats["ell_hits"] += 1
            return ell
        t0 = time.perf_counter()
        with span("pack"), jax.ensure_compile_time_eval():
            ell = fused_ops.csr_to_ell(a, width_cap=width_cap)
        _stats["ell_misses"] += 1
        _stats["pack_s"] += time.perf_counter() - t0
        _stats["spill_lanes"] += int(np.maximum(
            np.diff(a.indptr) - ell.cols.shape[1], 0).sum())
        _stats["spill_virtual_rows"] += ell.vrows.shape[0]
        _cache_put(_ell_cache, key, ell, evict_key="ell_evictions")
    return ell


def clear_schedule_cache() -> None:
    with _lock, _ell_lock:
        _schedule_cache.clear()
        _ell_cache.clear()
        for k in _stats:
            _stats[k] = 0


def schedule_cache_stats() -> dict:
    """Counters plus live entry counts of both process-wide caches.
    ``mesh_entries`` counts the live schedule entries inspected for a
    non-trivial mesh (the sharded-dispatch tier's cache footprint), broken
    down by the layout the dispatch resolved: ``layout_1d`` (pure row
    shards), ``layout_15d`` (column-replicated 1.5D), ``layout_25d``
    (depth-replicated 2.5D), ``layout_fallback`` (mesh-keyed entries that
    dispatch single-device — non-uniform grids, or layouts the chooser
    priced worse than serial).  ``spec_entries`` counts the distinct
    resolved ``FusionSpec`` key tails among live schedule entries — how
    many knob combinations the process actually runs (N matrices under
    one spec keep it at 1).  ``bucket_entries`` counts the live
    shape-bucket entries of the serving tier — N patterns mapping to K
    buckets should hold this (and evictions) at K, the LRU-thrash
    regression the serving tests pin.  ``transpose_entries`` counts the
    live backward-pass (``transpose=True``) schedules the SpMM-SpMM
    custom_vjp inspected — one per (graph, shape) when the transpose
    cache amortizes correctly; the GeMM-SpMM backward mints none.
    ``inspect_s`` and ``pack_s`` are the host seconds spent inspecting
    and packing ELLs since the last clear, ``ell_hits`` / ``ell_misses``
    count the full-matrix ELL cache's lookups, and ``spill_lanes`` /
    ``spill_virtual_rows`` (summed over misses) say how much of those
    ELLs spilled and into how many rows it was folded."""
    with _lock, _ell_lock:
        mesh_entries = layout_1d = layout_15d = layout_25d = 0
        layout_fallback = bucket_entries = transpose_entries = 0
        reorder_entries = 0
        for e in _schedule_cache.values():
            if e.bucket is not None:
                bucket_entries += 1
            if e.transpose:
                transpose_entries += 1
            if e.reorder is not None:
                reorder_entries += 1
            if e.mesh_key is None:
                continue
            mesh_entries += 1
            if e.shard is None:
                layout_fallback += 1
            elif e.shard.layout == "2.5d":
                layout_25d += 1
            elif e.shard.layout == "1.5d":
                layout_15d += 1
            else:
                layout_1d += 1
        # every schedule-cache key ends in the resolved-spec tail
        # (_spec_key), for both content and "autotune"-prefixed keys
        spec_entries = len({k[-1] for k in _schedule_cache})
        return dict(_stats, entries=len(_schedule_cache),
                    ell_entries=len(_ell_cache),
                    mesh_entries=mesh_entries,
                    bucket_entries=bucket_entries,
                    transpose_entries=transpose_entries,
                    reorder_entries=reorder_entries,
                    spec_entries=spec_entries,
                    layout_1d=layout_1d, layout_15d=layout_15d,
                    layout_25d=layout_25d,
                    layout_fallback=layout_fallback)


# --------------------------------------------------------------------------
# Backend selection (Eq-3 cost model + capability checks)
# --------------------------------------------------------------------------
def _pallas_capable() -> bool:
    """Capability gate shared by the GeMM-SpMM and SpMM-SpMM Pallas arms;
    the logic lives with the kernels' own mode resolution
    (``kernels.config``) so dispatch and execution can never disagree."""
    from ...kernels.config import compiled_or_forced
    return compiled_or_forced()


def pallas_vmem_bytes(entry: ScheduleEntry) -> dict:
    """Scoped-VMEM working set of each Pallas kernel the entry's executor
    runs — ``"wf0"`` (the op pair's fused-tile kernel) and, when wavefront
    1 has rows, ``"wf1"`` (the ELL SpMM over the completed D1) — from the
    kernels' own estimates.  Both the SpMM-SpMM wf0 kernel and the wf1
    kernel stage a whole dense operand plus a one-hot that is ``n`` wide,
    so their working sets grow with the problem, not with the tile."""
    from ...kernels import spmm, tile_fused_gemm_spmm, tile_fused_spmm_spmm
    ds = entry.dsched
    isz, c_col = entry.dtype_bytes, entry.c_col
    t, n = ds.t_pad, ds.n_i
    j0, w0 = ds.ell_cols0.shape[1:]
    if entry.b_is_sparse:
        # the op-1 body is at most cap wide (pad-to-max: at most n)
        w1 = ds.width_cap if ds.width_cap is not None else n
        out = {"wf0": tile_fused_spmm_spmm.vmem_bytes(
            t, w1, j0, w0, n, c_col, isz)}
    else:
        out = {"wf0": tile_fused_gemm_spmm.vmem_bytes(
            j0, w0, t, entry.b_col, c_col, isz)}
    if ds.j_rows1.size:
        out["wf1"] = spmm.vmem_bytes(spmm.BLOCK_ROWS, ds.ell_cols1.shape[2],
                                     n, c_col, isz)
    return out


def pallas_fits_vmem(entry: ScheduleEntry) -> bool:
    """True when every kernel of the entry's Pallas executor fits the
    scoped-VMEM budget it is compiled under (``kernels.config
    .VMEM_BUDGET``).  Above it ``select_backend`` resolves to the XLA
    executor instead of handing Mosaic an unallocatable kernel."""
    from ...kernels.config import VMEM_BUDGET
    return max(pallas_vmem_bytes(entry).values()) <= VMEM_BUDGET


def select_backend(entry: ScheduleEntry) -> str:
    """Resolve ``backend="auto"`` for an inspected schedule."""
    tm = entry.traffic_model
    if entry.shard is not None:
        # the entry was inspected for a non-trivial mesh (>1 device) and the
        # grid partitioned; honoring the mesh outranks every local backend,
        # including the unfused fallback — even a fusion-free schedule still
        # distributes op-1 rows and wavefront-1 work across the devices
        return "sharded"
    if (entry.sched.fused_ratio < MIN_FUSED_RATIO
            or tm["traffic_saving"] <= MIN_TRAFFIC_SAVING):
        # fusion saves no traffic (or too little to cover the tile loop's
        # off-model fixed costs) — Eq 3 says the intermediate round-trips
        # memory either way, so take the simpler code
        return "unfused"
    if (fused_ops._is_uniform(entry.dsched) and _pallas_capable()
            and pallas_fits_vmem(entry)):
        # both op pairs lower to Pallas kernels on a uniform grid (GeMM-SpMM
        # and, via the hybrid op-1 gather, SpMM-SpMM)
        return "pallas"
    return "xla"


def _require_uniform(ds: DeviceSchedule) -> None:
    if not fused_ops._is_uniform(ds):
        raise ValueError(
            "backend='pallas' needs a uniform schedule; inspect with "
            "uniform_split=True (the default) or use backend='xla'")


def _wf1_pallas(ds: DeviceSchedule, d: jax.Array, d1: jax.Array,
                dtype) -> jax.Array:
    """Post-barrier wavefront 1 for the Pallas paths: hybrid ELL body via
    the Pallas SpMM kernel over the completed D1, then the spill lanes
    (hub-row tails past the width cap) as one scatter-add."""
    from ...kernels import ops as kops
    c_col = d.shape[1]
    with scope("wf1"):
        if ds.j_rows1.size:
            t1, j1, w1 = ds.ell_cols1.shape
            with span("upload"):
                cols1 = jnp.asarray(ds.ell_cols1.reshape(t1 * j1, w1))
                vals1 = jnp.asarray(ds.ell_vals1.reshape(t1 * j1, w1), dtype)
            rows1 = kops.spmm_ell(cols1, vals1, d1)
            d = d.at[ds.j_rows1.reshape(-1)].set(rows1.reshape(-1, c_col),
                                                 mode="drop")
        if ds.spill_rows1.size:
            with span("upload"):
                spill = (jnp.asarray(ds.spill_rows1),
                         jnp.asarray(ds.spill_cols1),
                         jnp.asarray(ds.spill_vals1, dtype))
            d = fused_ops._spill_add(d, *spill, d1)
    return d


def _gemm_spmm_pallas(entry: ScheduleEntry, b: jax.Array,
                      c: jax.Array) -> jax.Array:
    """Wavefront 0 through the Pallas kernel, wavefront 1 via the ELL SpMM
    kernel over the spilled D1 — the pallas_call boundary is the barrier."""
    from ...kernels import ops as kops
    ds = entry.dsched
    _require_uniform(ds)
    t, n_t = ds.t_pad, ds.n_tiles0
    if b.shape[0] != ds.n_i:
        raise ValueError(f"b has {b.shape[0]} rows, schedule expects {ds.n_i}")
    b_pad = jnp.pad(b, ((0, n_t * t - b.shape[0]), (0, 0)))
    with span("upload"):
        cols0 = jnp.asarray(ds.ell_cols0)
        vals0 = jnp.asarray(ds.ell_vals0, b.dtype)
    c_col = c.shape[1]
    with scope("wf0"):
        d1, rows0 = kops.tile_fused_gemm_spmm_wf0(cols0, vals0, b_pad, c, t=t)
        d = jnp.zeros((ds.n_j, c_col), b.dtype).at[
            ds.j_rows0.reshape(-1)].set(rows0.reshape(-1, c_col),
                                        mode="drop")
    return _wf1_pallas(ds, d, d1[: ds.n_i], b.dtype)


def _spmm_spmm_pallas(entry: ScheduleEntry, a1: CSR,
                      c: jax.Array) -> jax.Array:
    """SpMM-SpMM wavefront 0 through the Pallas kernel: hybrid op-1 ELL
    (shared packer, spill pre-accumulated outside the kernel) feeds the
    tile-local second SpMM; wavefront 1 runs over the spilled D1."""
    from ...kernels import ops as kops
    ds = entry.dsched
    _require_uniform(ds)
    t, n_t = ds.t_pad, ds.n_tiles0
    if a1.n_rows != ds.n_i:
        raise ValueError(
            f"op-1 has {a1.n_rows} rows, schedule expects {ds.n_i}")
    if c.shape[0] != a1.n_cols:
        raise ValueError(
            f"c has {c.shape[0]} rows, op-1 has {a1.n_cols} columns")
    c_col = c.shape[1]
    o_cols, o_vals, spill_flat, spill_cols, spill_vals = fused_ops._op1_ell(
        a1, ds, width_cap=ds.width_cap)
    with span("upload"):
        op1 = (jnp.asarray(o_cols), jnp.asarray(o_vals, c.dtype))
        spill = (jnp.asarray(spill_flat), jnp.asarray(spill_cols),
                 jnp.asarray(spill_vals, c.dtype))
        wf0 = (jnp.asarray(ds.ell_cols0), jnp.asarray(ds.ell_vals0, c.dtype))
    d1_spill = jnp.zeros((n_t * t, c_col), c.dtype)
    if spill_flat.size:
        d1_spill = fused_ops._spill_add(d1_spill, *spill, c)
    with scope("wf0"):
        d1, rows0 = kops.tile_fused_spmm_spmm_wf0(*op1, d1_spill, *wf0, c,
                                                  t=t)
        d = jnp.zeros((ds.n_j, c_col), c.dtype).at[
            ds.j_rows0.reshape(-1)].set(rows0.reshape(-1, c_col),
                                        mode="drop")
    return _wf1_pallas(ds, d, d1[: ds.n_i], c.dtype)


# --------------------------------------------------------------------------
# The entrypoint
# --------------------------------------------------------------------------
def _dispatch(a: CSR, b_or_a1, c, *, backend: str,
              spec: FusionSpec) -> jax.Array:
    """The schedule-then-execute tail of ``tile_fused_matmul`` — everything
    past the custom_vjp seam.  ``spec.transpose=True`` runs the product
    with all sparse operands transposed (``D = aᵀ·(bᵀ·c)`` structurally —
    for the GeMM-SpMM pair only ``a`` is sparse, so ``D = aᵀ·(b·c)``),
    serving the SpMM-SpMM backward from the transpose-keyed schedule
    entry."""
    with span("dispatch"):
        b_is_sparse = isinstance(b_or_a1, CSR)
        transpose = spec.transpose
        width_cap = spec.width_cap
        a_run = a.transpose() if transpose else a
        a1_run = (b_or_a1.transpose() if (b_is_sparse and transpose)
                  else b_or_a1)

        def run_unfused():
            if b_is_sparse:
                hell_a = _csr_ell(a_run, _resolve_width_cap(a_run, width_cap))
                hell_a1 = _csr_ell(a1_run,
                                   _resolve_width_cap(a1_run, width_cap))
                return fused_ops.unfused_spmm_spmm(hell_a, hell_a1, c)
            return fused_ops.unfused_gemm_spmm(
                _csr_ell(a_run, _resolve_width_cap(a_run, width_cap)),
                jnp.asarray(b_or_a1), c)

        if backend == "unfused":
            return run_unfused()      # no inspection needed for the baseline

        # the cost model's b_col is the width of the intermediate D1's inputs:
        # dense-B column count for GeMM-SpMM, C's column count for SpMM-SpMM
        # (op 1 is a1 @ c, so D1 is c_col wide and B's dense charge is c_col)
        b_col = c.shape[1] if b_is_sparse else b_or_a1.shape[1]
        if spec.dtype_bytes is None:
            spec = dataclasses.replace(spec, dtype_bytes=(
                cost_model.operand_dtype_bytes(c if b_is_sparse else b_or_a1,
                                               c)))
        entry = get_schedule(a, b_col=b_col, c_col=c.shape[1],
                             b_is_sparse=b_is_sparse, spec=spec)
        chosen = select_backend(entry) if backend == "auto" else backend

        if chosen == "sharded" and entry.shard is None:
            # trivial mesh, a non-uniform grid, or the priced single-device
            # fallback: the XLA executor is the sharded path's one-device twin
            chosen = "xla"
        if chosen == "unfused":
            return run_unfused()      # unpermuted operands — no reorder math
        # an entry built under spec.reorder carries its permutation: permute
        # the row-indexed operands in (P·B / P·A1 — jnp.take, so gradients
        # flow through the linear permutation) and the output back out; the
        # caller never sees the reordered frame
        perm = entry.reorder_perm
        if perm is not None:
            if b_is_sparse:
                a1_run = reorder.permute_rows_cached(a1_run, perm)
        if chosen == "sharded":
            if b_is_sparse:
                d = sharded.sharded_spmm_spmm(entry.shard, entry.dsched,
                                              spec.mesh, a1_run, c)
            else:
                b = jnp.asarray(b_or_a1)
                if perm is not None:
                    b = jnp.take(b, jnp.asarray(perm), axis=0)
                d = sharded.sharded_gemm_spmm(entry.shard, spec.mesh, b, c)
        elif b_is_sparse:
            if chosen == "pallas":
                d = _spmm_spmm_pallas(entry, a1_run, c)
            else:
                d = fused_ops.fused_spmm_spmm(entry.dsched, a1_run, c)
        else:
            b = jnp.asarray(b_or_a1)
            if perm is not None:
                b = jnp.take(b, jnp.asarray(perm), axis=0)
            if chosen == "pallas":
                d = _gemm_spmm_pallas(entry, b, c)
            else:
                d = fused_ops.fused_gemm_spmm(entry.dsched, b, c)
        if perm is not None:
            d = jnp.take(d, jnp.asarray(entry.reorder_inv), axis=0)
        return d


def _bwd_knobs(knobs: dict) -> dict:
    """Knob set for the SpMM-SpMM backward dispatch: the spec flips its
    transpose bit (so the backward of an already-transposed product runs
    on the *forward* schedule — (Aᵀ)ᵀ = A), and the serving ``bucket`` — an
    inference-only shape key — never leaks into training entries.
    Everything else (backend, mesh, tile knobs) carries over so the
    backward lands on the same Eq-3 ``select_backend`` seam."""
    spec = knobs["spec"]
    return dict(backend=knobs["backend"],
                spec=dataclasses.replace(spec, transpose=not spec.transpose,
                                         bucket=None))


def _transpose_spmm(a: CSR, x: jax.Array, *, transpose: bool,
                    width_cap) -> jax.Array:
    """Plain ``Aᵀ·x`` (or ``A·x`` when the forward was transposed) — the
    one sparse product of the GeMM-SpMM backward, served from the same
    content-keyed full-matrix hybrid-ELL cache the unfused executor uses."""
    a_eff = a.transpose() if transpose else a
    return fused_ops.spmm_hybrid(
        _csr_ell(a_eff, _resolve_width_cap(a_eff, width_cap)), x)


def _gemm_spmm_diff(a: CSR, knobs: dict):
    """custom_vjp wrapper for the GeMM-SpMM pair (``D = A·(B·C)``).

    The CSR and the dispatch knobs are closed over (a frozen dataclass of
    ndarrays can't ride through ``nondiff_argnums``, which wants hashable
    statics); only the dense operands are traced.  Backward: one plain
    transposed SpMM ``G = Aᵀ·Ḋ`` (at ``c_col``, off the full-matrix
    hybrid-ELL cache), from which two dense GEMMs derive both cotangents —
    ``dB = G·Cᵀ`` and ``dC = Bᵀ·G``.  Every backend runs this one path:
    a fused ``dB = Aᵀ·(Ḋ·Cᵀ)`` would read ``A`` a second time, so sharing
    ``G`` never adds sparse work, and the backward inspects no schedule.
    """
    def primal(b, c):
        return _dispatch(a, b, c, **knobs)

    def fwd(b, c):
        return primal(b, c), (b, c)

    def bwd(res, dd):
        b, c = res
        spec = knobs["spec"]
        with scope("backward"):
            g = _transpose_spmm(a, dd, transpose=not spec.transpose,
                                width_cap=spec.width_cap)
            with scope("gemm"):
                db = g @ c.T.astype(g.dtype)
                dc = b.T.astype(g.dtype) @ g
        return jnp.asarray(db, b.dtype), jnp.asarray(dc, c.dtype)

    f = jax.custom_vjp(primal)
    f.defvjp(fwd, bwd)
    return f


def _spmm_spmm_diff(a: CSR, a1: CSR, knobs: dict):
    """custom_vjp wrapper for the SpMM-SpMM pair (``D = A·(A1·C)``).

    Only the dense ``C`` differentiates (the sparse operands are host
    CSRs, not traced values).  Its cotangent is itself a fused SpMM-SpMM
    with the operand roles swapped — ``dC = A1ᵀ·(Aᵀ·Ḋ)`` — dispatched
    back through ``tile_fused_matmul`` with the transpose bit flipped, so
    the backward runs the same two-wavefront schedule machinery against
    the cached transpose entries."""
    def primal(c):
        return _dispatch(a, a1, c, **knobs)

    def fwd(c):
        return primal(c), None

    def bwd(_, dd):
        with scope("backward"):
            dc = tile_fused_matmul(a1, a, dd, **_bwd_knobs(knobs))
        return (jnp.asarray(dc, dd.dtype),)

    f = jax.custom_vjp(primal)
    f.defvjp(fwd, bwd)
    return f


def tile_fused_matmul(a: CSR, b_or_a1, c, *, backend: str = "auto",
                      spec: FusionSpec | None = None) -> jax.Array:
    """``D = a @ (b_or_a1 @ c)`` through the tile-fusion schedule.

    Args:
      a: CSR matrix of the second (consumer) operation.
      b_or_a1: dense ``(n_i, b_col)`` array → GeMM-SpMM, or a ``CSR`` →
        SpMM-SpMM (op-1 rows gathered per tile).
      c: dense ``(b_col, c_col)`` (GeMM-SpMM) / ``(n, c_col)`` (SpMM-SpMM).
      backend: "auto" (Eq-3 cost model + capability), or an explicit
        "pallas" / "xla" / "unfused" / "sharded" override that pins one
        executor.  Both op pairs lower to "pallas" (SpMM-SpMM via the
        hybrid op-1 gather) and to "sharded" (shard_map over
        ``spec.mesh``).
      spec: a ``FusionSpec`` carrying every other knob — Algorithm-1 tile
        parameters (``p``, ``cache_size``, ``ct_size``,
        ``uniform_split``), the ``autotune`` sweep, the hybrid-ELL
        ``width_cap``, distribution (``mesh``, ``shard_combine``,
        ``shard_layout``, ``overlap``, ``n_repl``), the serving
        ``bucket``, the backward-pass ``transpose`` bit, and
        ``dtype_bytes`` (None = inferred from the dense operands here).
        ``None`` means the default spec.  See ``spec.FusionSpec`` and
        ``get_schedule`` for per-knob semantics; the resolved spec is the
        schedule-cache key.

    Distribution notes: ``spec.mesh`` partitions the wavefront-0 tile
    grid row-block across the mesh's row shards (Eq-3-balanced);
    wavefront 1 reads an all-gathered halo, per depth layer under the
    2.5D layout, optionally issued *before* wavefront 0 so it overlaps
    communication-free compute (``spec.overlap``).  On a CPU host, force
    a multi-device platform with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  A trivial
    mesh (one device, or ``mesh=None``) falls back to single-device
    dispatch — including for ``backend="sharded"``.

    **Differentiable.**  When a dense operand is a JAX tracer (i.e. under
    ``jax.grad`` / ``jax.vjp`` / ``jax.jit`` of a differentiated
    function), the call routes through a ``jax.custom_vjp``.  The
    GeMM-SpMM backward runs one plain transposed SpMM ``Aᵀ·Ḋ`` and derives
    ``dB`` and ``dC`` from it with dense GEMMs; the SpMM-SpMM backward
    runs the transposed product on this same fused dispatch, off schedule
    entries cached with ``transpose=True`` (inspected once per (content,
    shape), like the forward).  Eager calls with concrete operands — the
    serving hot path — skip the vjp machinery entirely.
    """
    spec = _coerce_spec(spec, "tile_fused_matmul")
    if backend not in BACKENDS:
        raise ValueError(f"backend={backend!r}; expected one of {BACKENDS}")
    c = jnp.asarray(c)
    knobs = dict(backend=backend, spec=spec)
    if isinstance(b_or_a1, CSR):
        if isinstance(c, jax.core.Tracer):
            return _spmm_spmm_diff(a, b_or_a1, knobs)(c)
        return _dispatch(a, b_or_a1, c, **knobs)
    b = jnp.asarray(b_or_a1)
    if isinstance(b, jax.core.Tracer) or isinstance(c, jax.core.Tracer):
        return _gemm_spmm_diff(a, knobs)(b, c)
    return _dispatch(a, b, c, **knobs)

"""Reduction autotuner: pick (method, variant, chain, block_rows) per
problem, the way the paper picks (R, B) per GPU geometry.

The paper's central performance result (Figs. 3/5/11) is that the best
chained-MMA configuration depends on geometry: small thread-blocks
favour chains of R=4..5 while large blocks favour R=1, and the PRAM
model alone (which always says R=1) cannot predict the crossover.  This
module makes that selection automatic:

  * ``candidate_plans``   enumerates the paper's R in {1..5} x block
    geometry sweep as executable ``ReductionPlan``s;
  * ``autotune``          scores candidates either by wall-clock
    measurement (``measure=True``; what you run on real hardware) or by
    an analytical cost model backed by ``core.theory`` — Brent's-theorem
    style: PRAM depth (Eq. 24) + work/parallelism + per-grid-step
    overhead + padding waste — so a plan exists even with no hardware;
  * ``PlanRegistry``      caches winners keyed by (op, n-bucket, dtype,
    backend[, engine][, precision-signature][, mesh-signature]),
    survives a JSON round-trip, and can be pre-seeded from a file
    (``REPRO_AUTOTUNE_CACHE``);
  * ``get_plan``          the one-call entry the framework hooks
    (``integration.reduce_sum(method="auto")`` etc.) consult.

The op universe is NOT hardcoded here: ``candidate_plans`` enumerates
engines and their sweep knobs off the TC-op registry
(``repro.core.dispatch`` — each ``OpSpec`` declares its engines and
each ``EngineSpec`` its sweep axes), ``model_cost`` scores them with
the family cost model (scan ops via ``theory.t_tc_scan`` /
``op_count_scan``) unless the op registers its own cost hook, and the
single executor ``execute_plan`` runs any plan for any op through the
registry's engine runners.  Adding an op or engine is a
``dispatch.register`` call; this module needs no edit.

Problem sizes are bucketed to the next power of two so one tuned plan
serves every n in its octave — the paper's curves are smooth in n, and
this keeps the registry (and the number of compiled kernel variants)
small.

Plans are **mesh-aware**: under a live >1-device mesh the key carries a
mesh signature (``mesh_signature`` — axis names + sizes, e.g.
``data4.model2``) and the sweep tunes the *local per-device* chain
geometry of the size-n global problem (model mode scores the n/D
shard + a cross-mesh combine term; measure mode times the local
execute + hierarchical scalar combine under ``shard_map``).  This is
how the paper's one-f32-partial-per-block design scales past the
device boundary: each device is a "block" producing a single f32
partial, and ``repro.distributed.tc_collectives`` folds them with the
``hierarchical_psum`` tree.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import logging
import math
import os
import queue
import re
import tempfile
import threading
import time
from typing import Callable, Iterator, Optional

import jax

try:  # POSIX advisory file locking; absent on some platforms.
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX host
    fcntl = None

from repro.core import theory

log = logging.getLogger(__name__)

# The paper's experimental sweep: chain length R (Figs. 3/5) and block
# geometry B (threads/block on GPU -> rows per VMEM tile here).
CHAINS = (1, 2, 3, 4, 5)
BLOCK_ROWS = (32, 128, 512)
DEFAULT_M = 128  # MXU tile; the paper's m (=16 in wmma fragments).

# Cost-model constants (arbitrary PRAM-step units; only ratios matter).
# For SLO comparison the model unit gets a nominal wall-clock meaning:
# 1 model unit ~= 1 µs.  Ratios still drive every within-sweep ranking;
# the conversion only anchors the analytical mode's latency estimates
# to the same ms scale a measured sweep reports.
_MODEL_UNIT_US = 1.0
_GRID_STEP_OVERHEAD = 48.0     # sequential grid-step / block-launch cost
_VPU_THROUGHPUT = 8 * 128      # VPU lanes: elements per step
_MXU_THROUGHPUT = 128 * 128    # MXU tile: elements folded per ones-MMA
_PARALLELISM = 8               # concurrent grid workers the model assumes


@dataclasses.dataclass(frozen=True)
class ReductionPlan:
    """One executable reduction configuration.

    ``method`` selects the execution engine (the ``integration.Method``
    namespace); variant/chain/block_rows are the paper's knobs;
    ``split_words`` is the compensated family's bf16-word count (2 =
    hi+lo, 3 = exact f32 — ignored by the plain engines) and
    ``mma_fraction`` the split variant's MXU share.  ``cost`` is the
    score that won the sweep, in microseconds when
    ``source='measured'`` and in model units when ``source='model'``;
    ``error_pct`` is the percent-error estimate the budget-aware sweep
    scored this plan with (None when no budget applied);
    ``latency_ms`` the latency estimate an SLO-objective sweep scored
    it with (None when no objective applied — a plan whose latency_ms
    exceeds the SLO is the visible best-effort fallback).
    """
    method: str   # 'mma' | 'mma_chained' | 'mma_ec' | 'pallas' |
    #               'pallas_ec' | 'mma_dd' | 'pallas_dd' | 'vpu'
    variant: str = "single_pass"
    chain: int = 1
    block_rows: int = 128
    m: int = DEFAULT_M
    split_words: int = 2
    mma_fraction: float = 0.5
    source: str = "model"       # 'model' | 'measured'
    cost: float = 0.0
    error_pct: Optional[float] = None
    latency_ms: Optional[float] = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ReductionPlan":
        return cls(**d)


def bucket_n(n: int) -> int:
    """Round n up to a power of two — the plan-cache granularity."""
    return 1 << max(int(math.ceil(math.log2(max(n, 1)))), 0)


# ---------------------------------------------------- bucket policies
#
# A bucket policy maps a problem size n onto the *bucket cap* the plan
# is tuned — and keyed — at, so one tuned plan serves every shape in
# its bucket.  Correctness contract: every policy's cap is monotone in
# n and >= n, the engine-capability predicates (repro.core.dispatch
# ``capability_reason``) depend only on op/engine/policy — never on n —
# so a plan that is engine-legal at the cap is engine-legal across the
# bucket, and the error model's accumulation term grows with n
# (~eps*sqrt(n)), so a plan whose error meets ``error_budget_pct`` at
# the cap meets it for every smaller n in the bucket.


def _cap_pow2(n: int) -> int:
    return bucket_n(n)


def _cap_geom(n: int, m: int = DEFAULT_M) -> int:
    # Paper-geometry alignment: a chained block folds multiples of the
    # m x m MXU tile, and a full block pass folds m^2 elements (Eq. 5's
    # R*m^2 block coverage).  Caps are m^2-aligned above one block pass
    # and m-aligned below, so the tuned tile geometry divides the cap
    # evenly — Dakkak et al.'s per-segment-size-class tuning, with the
    # class boundaries on the paper's tile sizes instead of octaves.
    n = max(int(n), 1)
    if n <= m:
        return m
    if n <= m * m:
        return math.ceil(n / m) * m
    return math.ceil(n / (m * m)) * (m * m)


# Named bucket policies.  ``None`` (not in this table) opts out of
# bucketing entirely: exact-n keys, one plan per exact shape.
BUCKETS: dict[str, Callable[[int], int]] = {
    "pow2": _cap_pow2,
    "geom": _cap_geom,
}

# bucket argument: a policy name from BUCKETS, or None for exact keys.
BucketArg = Optional[str]

DEFAULT_BUCKET = "pow2"


def bucket_cap(n: int, bucket: BucketArg = DEFAULT_BUCKET) -> int:
    """The bucket cap ``n`` belongs to under ``bucket`` — the size the
    plan is tuned and keyed at.  ``bucket=None`` returns n itself
    (exact keys, no sharing); unknown policy names raise."""
    n = max(int(n), 1)
    if bucket is None:
        return n
    try:
        fn = BUCKETS[bucket]
    except KeyError:
        raise ValueError(
            f"unknown bucket policy {bucket!r} (known: "
            f"{sorted(BUCKETS)} or None for exact keys)") from None
    return fn(n)


def bucket_floor(n: int, bucket: BucketArg = DEFAULT_BUCKET) -> int:
    """Smallest size sharing ``n``'s bucket (the cap's lower boundary).
    With ``bucket=None`` every bucket is the single size n."""
    cap = bucket_cap(n, bucket)
    if bucket is None or cap <= 1:
        return cap
    lo, hi = 1, cap
    while lo < hi:  # first k with bucket_cap(k) == cap (caps monotone)
        mid = (lo + hi) // 2
        if bucket_cap(mid, bucket) >= cap:
            hi = mid
        else:
            lo = mid + 1
    return lo


# engine restriction: None = all engines; a method name = just that
# engine; a tuple of method names = any of those.
Engine = Optional[object]

# mesh argument: None = single device; a jax Mesh (or anything with an
# ordered .shape mapping), an ((axis_name, size), ...) tuple, or a
# signature string ("data4.model2").
MeshArg = Optional[object]


def mesh_axes(mesh: MeshArg) -> Optional[tuple]:
    """Normalise a mesh argument to ``((name, size), ...)`` — or None.

    A mesh whose device product is 1 normalises to None: a single
    device carries no mesh signature, so its plans keep the plain
    (un-suffixed) keys and a 1x1 test mesh shares them.
    """
    if mesh is None:
        return None
    if isinstance(mesh, str):
        axes = []
        for part in mesh.split("."):
            got = re.fullmatch(r"(.*?)(\d+)", part)
            if got is None:
                raise ValueError(
                    f"bad mesh-signature component {part!r} in {mesh!r} "
                    f"(expected '<axis><size>', e.g. 'data4')")
            axes.append((got.group(1), int(got.group(2))))
        axes = tuple(axes)
    elif hasattr(mesh, "shape") and hasattr(mesh.shape, "items"):
        axes = tuple((str(n), int(s)) for n, s in mesh.shape.items())
    else:
        axes = tuple((str(n), int(s)) for n, s in mesh)
    for name, _ in axes:
        # 'stage1' + size 2 would render 'stage12' == ('stage', 12):
        # two meshes colliding on one plan key.  The grammar stays
        # unambiguous by construction instead of growing a separator.
        if not name or name[-1].isdigit():
            raise ValueError(
                f"mesh axis name {name!r} would make the mesh "
                f"signature ambiguous (names must not end in a "
                f"digit); rename the axis")
    if math.prod(s for _, s in axes) <= 1:
        return None
    return axes


def mesh_signature(mesh: MeshArg) -> str:
    """Mesh signature string: axis names + sizes in mesh order, joined
    with '.', e.g. ``"data4.model2"`` — ``""`` for a single device.
    The signature is the plan key's mesh component (see ``plan_key``),
    so two runs on identically-shaped meshes share tuned plans while a
    re-sharded run tunes afresh."""
    axes = mesh_axes(mesh)
    if axes is None:
        return ""
    return ".".join(f"{n}{s}" for n, s in axes)


def _mesh_tag(mesh: MeshArg) -> str:
    sig = mesh_signature(mesh)
    return f"|mesh:{sig}" if sig else ""


def mesh_device_count(mesh: MeshArg) -> int:
    axes = mesh_axes(mesh)
    return 1 if axes is None else math.prod(s for _, s in axes)


def _engine_methods(engine: Engine) -> Optional[tuple]:
    if engine is None:
        return None
    if isinstance(engine, str):
        return (engine,)
    return tuple(engine)


def _engine_tag(engine: Engine) -> str:
    methods = _engine_methods(engine)
    return "" if methods is None else "|" + "+".join(methods)


# policy argument: None, or a repro.core.precision.MmaPolicy.
PolicyArg = Optional[object]


def _prec_tag(policy: PolicyArg) -> str:
    return "" if policy is None else f"|prec:{policy.signature()}"


@dataclasses.dataclass(frozen=True)
class LatencyObjective:
    """A per-call latency target the auto sweep selects under.

    ``latency_slo_ms`` is the step budget one reduction may spend
    (wall-clock ms when the sweep measures; nominal model-unit ms —
    1 model unit ~= 1 µs — in analytical mode).  Selection flips the
    budget-sweep's dual: instead of *fastest within the error budget*,
    the winner is the **most accurate candidate whose latency meets
    the SLO** (a serving stack buys all the accuracy its deadline
    affords), falling back to the fastest eligible candidate when
    nothing meets it — a decode step must not fail because the SLO was
    set tighter than the hardware.  The recorded ``latency_ms`` on the
    plan makes any shortfall visible, mirroring ``error_pct``.

    The signature is the plan key's ``|lat:`` component (between
    ``|prec:`` and ``|mesh:`` — see ``plan_key``), so prefill
    (B×S×V) and decode (B×1×V) shapes tuned under one SLO resolve
    *distinct, objective-keyed* plans by their n-buckets.
    """
    latency_slo_ms: float

    def __post_init__(self):
        if not self.latency_slo_ms > 0.0:
            raise ValueError(
                f"latency_slo_ms must be positive, got "
                f"{self.latency_slo_ms!r}")

    def signature(self) -> str:
        return f"slo{self.latency_slo_ms:g}ms"

    @classmethod
    def from_signature(cls, sig: str) -> "LatencyObjective":
        got = re.fullmatch(r"slo(.+)ms", sig)
        if got is None:
            raise ValueError(
                f"bad latency-objective signature {sig!r} "
                f"(expected 'slo<ms>ms', e.g. 'slo0.25ms')")
        return cls(latency_slo_ms=float(got.group(1)))


# objective argument: None, a LatencyObjective, a bare number of
# milliseconds, or a signature string ("slo0.25ms").
ObjectiveArg = Optional[object]


def as_objective(obj: ObjectiveArg) -> Optional[LatencyObjective]:
    """Normalise an ``objective`` argument to a LatencyObjective."""
    if obj is None or isinstance(obj, LatencyObjective):
        return obj
    if isinstance(obj, str):
        return LatencyObjective.from_signature(obj)
    if isinstance(obj, (int, float)):
        return LatencyObjective(latency_slo_ms=float(obj))
    raise TypeError(
        f"objective must be None, a LatencyObjective, a number of "
        f"milliseconds, or an 'slo<ms>ms' signature; got {obj!r}")


def _lat_tag(objective: ObjectiveArg) -> str:
    obj = as_objective(objective)
    return "" if obj is None else f"|lat:{obj.signature()}"


def plan_key(op: str, n: int, dtype, backend: Optional[str] = None,
             engine: Engine = None, mesh: MeshArg = None,
             policy: PolicyArg = None,
             objective: ObjectiveArg = None,
             bucket: BucketArg = DEFAULT_BUCKET) -> str:
    """Registry key: op|n-bucket|dtype|backend[|engine][|prec:sig]
    [|lat:sig][|mesh:sig] (a flat string so the registry
    JSON-serialises as a plain object).

    The second field is the **bucket cap** ``bucket_cap(n, bucket)``:
    the size the plan was tuned at, which serves every n in its bucket.
    The bucket policy changes only this field — suffix grammar and
    ordering (engine < ``|prec:`` < ``|lat:`` < ``|mesh:``) are
    policy-independent — so two policies mapping a shape to the same
    cap share one tuned plan (by design: the plan depends only on the
    size it was tuned at), and ``bucket=None`` writes the exact n
    (which for a cap-aligned n is bit-for-bit the default pow-2 key).

    The engine suffix appears only for engine-restricted tunes (e.g.
    the tc_reduce / mma_reduce 'auto' spellings), so a per-engine
    geometry plan never collides with the unrestricted cross-engine
    winner.  The precision suffix (``|prec:any.float32.w2.b0.001`` —
    ``repro.core.precision.MmaPolicy.signature``) appears whenever the
    call carried a policy: plans tuned under different input dtypes,
    split-word pins, or error budgets live under their own keys.  The
    latency suffix (``|lat:slo0.25ms`` —
    ``LatencyObjective.signature``) appears whenever the call carried
    a latency objective: plans selected under different SLOs — or
    under an SLO vs none — never collide.  The mesh suffix
    (``|mesh:data4.model2`` — see ``mesh_signature``) appears only
    under a live >1-device mesh: a mesh-keyed plan describes the
    *local per-device* chain geometry of a size-n global problem, so
    it never collides with the single-device plan for the same n."""
    if backend is None:
        backend = jax.default_backend()
    return (f"{op}|{bucket_cap(n, bucket)}"
            f"|{jax.numpy.dtype(dtype).name}|{backend}"
            f"{_engine_tag(engine)}{_prec_tag(policy)}"
            f"{_lat_tag(objective)}{_mesh_tag(mesh)}")


# VMEM feasibility for Pallas tiles: input tile + f32 working copy,
# double-buffered, must fit on-chip.
_VMEM_BUDGET = 16 * 2**20


# The split-word counts the compensated engines sweep when no policy
# pins one: hi+lo (~16-bit multiplicands) and hi+mid+lo (exact f32).
SPLIT_WORDS = (2, 3)


def candidate_plans(n: int, dtype, *, chains=CHAINS, blocks=BLOCK_ROWS,
                    m: int = DEFAULT_M, engine: Engine = None,
                    op: str = "reduce_sum",
                    policy: PolicyArg = None) -> Iterator[ReductionPlan]:
    """Enumerate the sweep space for one problem, off the op registry.

    The op's ``repro.core.dispatch.OpSpec`` declares the engines; each
    engine's ``sweep`` declares its knobs: geometry-free engines (the
    'mma' ones-contraction, the 'vpu' baseline) contribute one
    candidate, ``('chain',)`` engines sweep the paper's R,
    ``('chain', 'block_rows')`` engines sweep the full R x B grid, and
    the compensated family additionally sweeps ``split_words`` over
    ``SPLIT_WORDS`` — unless ``policy`` pins a word count, in which
    case only that count is enumerated.  ``engine`` narrows the space
    to one engine (or a tuple) — how the per-engine 'auto' geometry
    spellings get a plan actually tuned for the engine they run.
    VMEM-tiled (block_rows-swept) plans are pruned when the tile would
    not fit on-chip (dtype-dependent) or would be strictly more
    padding than a smaller config.
    """
    from repro.core import dispatch
    spec = dispatch.op_spec(op)
    methods = _engine_methods(engine)
    itemsize = jax.numpy.dtype(dtype).itemsize
    for eng in spec.engines:
        if methods is not None and eng.name not in methods:
            continue
        if policy is None and methods is None:
            # No policy = the default f32 scalar contract (the dispatch
            # ``_policy_reason`` rule): engines that cannot accumulate
            # in float32 — the dd family, whose result is a (hi, lo)
            # pair — never enter an *unrestricted* sweep.  An explicit
            # ``engine=`` restriction naming them (the per-engine
            # 'auto' geometry spellings) still enumerates.
            if "float32" not in eng.accum_dtypes:
                continue
        if policy is not None:
            # Policy capability facts prune the sweep itself, so every
            # enumeration path (dispatch auto, local_plan, direct
            # get_plan) can only ever tune a plan the policy's
            # execute-time predicates will accept.
            if policy.split_words > eng.max_split_words:
                continue
            if jax.numpy.dtype(policy.accum_dtype).name \
                    not in eng.accum_dtypes:
                continue
        if "split_words" not in eng.sweep:
            words_opts = (ReductionPlan.split_words,)
        elif policy is not None and policy.split_words > 1:
            words_opts = (int(policy.split_words),)
        else:
            words_opts = SPLIT_WORDS
        if not eng.sweep:
            yield ReductionPlan(method=eng.name)
            continue
        eng_chains = chains if "chain" in eng.sweep else (1,)
        if "block_rows" not in eng.sweep:
            for chain in eng_chains:
                for words in words_opts:
                    yield ReductionPlan(method=eng.name, chain=chain,
                                        m=m, split_words=words)
            continue
        for words in words_opts:
            prev_tile = 0
            for chain in eng_chains:
                for block_rows in blocks:
                    tile = chain * block_rows * m
                    if 2 * tile * (itemsize + 4) > _VMEM_BUDGET:
                        continue  # double-buffered tile exceeds VMEM
                    if tile > max(n, 1) and prev_tile > max(n, 1):
                        continue  # strictly more padding than smaller
                    prev_tile = tile
                    yield ReductionPlan(method=eng.name, chain=chain,
                                        block_rows=block_rows, m=m,
                                        split_words=words)


# --------------------------------------------------------------- cost


def _cost_vpu(family: str, plan: ReductionPlan, n: int,
              itemsize: int) -> float:
    # classic parallel reduction/scan: log-depth + vectorised work (a
    # Hillis-Steele scan does log2 n full-width passes, hence the
    # extra work term for scans).
    work = n / (_VPU_THROUGHPUT * _PARALLELISM)
    if family == "scan":
        work *= max(math.log2(max(n, 2.0)) / 4.0, 1.0)
    return theory.t_classic(n) + work


def _cost_mma(family: str, plan: ReductionPlan, n: int,
              itemsize: int) -> float:
    # one big contraction: two-MMA depth, full-MXU work (for the
    # segment family the one-hot mask build adds a VPU compare pass).
    extra = n / (_VPU_THROUGHPUT * _PARALLELISM) \
        if family == "segment" else 0.0
    return theory.t_tc(n, plan.m) + n / (_MXU_THROUGHPUT *
                                         _PARALLELISM) + extra


def _cost_chained(family: str, plan: ReductionPlan, n: int,
                  itemsize: int, *, grid_walk: bool = False) -> float:
    # chained engines: PRAM depth + MMA work + grid overheads.
    if family == "scan":
        tile = plan.chain * plan.block_rows * plan.m \
            if grid_walk else plan.chain * plan.m
        groups = max(1, math.ceil(n / tile))
        padded = groups * tile
        depth = theory.t_tc_scan(n, plan.m, plan.chain)
        oc = theory.op_count_scan(padded, m=plan.m, chain=plan.chain,
                                  variant=plan.variant)
    else:
        tile = plan.chain * plan.block_rows * plan.m
        groups = max(1, math.ceil(n / tile))
        padded = groups * tile
        depth = theory.t_tc_chained(n, plan.m, plan.chain)
        oc = theory.op_count(padded, m=plan.m, chain=plan.chain,
                             variant=plan.variant)
    work = oc.mma_ops / _PARALLELISM
    grid = 0.0
    waste = (padded - n) / (_MXU_THROUGHPUT * _PARALLELISM)
    if grid_walk:
        # sequential grid walk: one VMEM tile fill + accumulate per step
        grid = _GRID_STEP_OVERHEAD * groups / _PARALLELISM
    if family == "segment":
        grid += n / (_VPU_THROUGHPUT * _PARALLELISM)  # mask build
    return depth + work + grid + waste


def _cost_ec(family: str, plan: ReductionPlan, n: int,
             itemsize: int, *, grid_walk: bool = False) -> float:
    # Compensated split-bf16 engines: one MMA chain per word, plus the
    # split's elementwise passes (one cast + one subtract per extra
    # word) and the TwoSum combine tree — the tree touches every one
    # of the w * n / (chain * m) lane partials once (vectorised,
    # halving), plus a per-level overhead.
    w = max(int(plan.split_words), 1)
    base = _cost_chained(family, plan, n, itemsize, grid_walk=grid_walk)
    split = (2 * w - 1) * n / (_VPU_THROUGHPUT * _PARALLELISM)
    lanes = w * n / max(plan.chain * plan.m, 1)
    combine = 2.0 * lanes / (_VPU_THROUGHPUT * _PARALLELISM) \
        + 6.0 * math.log2(max(lanes, 2.0))
    return w * base + split + combine


def _cost_dd(family: str, plan: ReductionPlan, n: int,
             itemsize: int, *, grid_walk: bool = False) -> float:
    # Double-double engines: the pairwise dd merge tree does ~n pair
    # merges total (halving levels), each one pair ones-MMA plus ~10
    # VPU ops (TwoSum residual, low-word fold, FastTwoSum
    # renormalise) — about two chained passes of MMA work plus a dense
    # VPU carry stream.
    base = _cost_chained(family, plan, n, itemsize, grid_walk=grid_walk)
    carry = 10.0 * n / (_VPU_THROUGHPUT * _PARALLELISM)
    return 2.0 * base + carry


# Per-engine scoring — keyed, not branched, so the only place engine
# names select behaviour stays the dispatch registry.
_ENGINE_COSTS = {
    "vpu": _cost_vpu,
    "mma": _cost_mma,
    "mma_chained": _cost_chained,
    "mma_ec": _cost_ec,
    "pallas": functools.partial(_cost_chained, grid_walk=True),
    "pallas_ec": functools.partial(_cost_ec, grid_walk=True),
    "mma_dd": _cost_dd,
    "pallas_dd": functools.partial(_cost_dd, grid_walk=True),
}


# ------------------------------------------------------- error model

_EPS32 = 2.0 ** -24     # f32 unit roundoff
_BF16_BITS = 8          # bf16 significand bits (incl. implicit)
_F32_BITS = 24


# The TwoSum-compensated engine family (keyed, like _ENGINE_COSTS, so
# engine-name selection stays out of branch ladders) and the per-engine
# multiplicand widths: the VPU baseline keeps full f32; None marks the
# split family, whose width is 8 bits per word; every other
# matrix-unit engine truncates f32 multiplicands to bf16 (TF32/bf16
# MXU semantics).
_COMPENSATED = frozenset({"mma_ec", "pallas_ec"})
# The double-double family: unevaluated (hi, lo) f32 pairs carried via
# TwoSum/TwoProd — no multiplicand truncation, O(eps32^2) per merge.
_DOUBLE_DOUBLE = frozenset({"mma_dd", "pallas_dd"})
_ENGINE_BITS = {"vpu": _F32_BITS, "mma_ec": None, "pallas_ec": None}


def _multiplicand_bits(plan: ReductionPlan, dtype,
                       op: str = "reduce_sum") -> int:
    """Effective significand bits the engine's multiplicands carry.
    A bf16 *input* caps everything at 8.  An op whose registry entry
    declares ``engine_bits`` overrides the shared table per engine
    (e.g. norm_matmul's ``unfused_mma`` runs at full f32 width)."""
    from repro.core import dispatch
    in_bits = _BF16_BITS if jax.numpy.dtype(dtype).name == "bfloat16" \
        else _F32_BITS
    over = dispatch.op_spec(op).engine_bits or {}
    eng_bits = over.get(plan.method,
                        _ENGINE_BITS.get(plan.method, _BF16_BITS))
    if eng_bits is None:
        eng_bits = min(_BF16_BITS * max(int(plan.split_words), 1),
                       _F32_BITS)
    return min(in_bits, eng_bits)


def model_percent_error(plan: ReductionPlan, n: int, dtype,
                        op: str = "reduce_sum") -> float:
    """Modelled % error vs the fp64 oracle — the budget-aware sweep's
    hardware-free score (the analytical analogue of
    ``repro.core.precision.percent_error``).

    Two terms: a **representation** term 2^-(bits+1) from the
    effective multiplicand width (see ``_multiplicand_bits`` — this is
    where bf16-truncating MMAs pay and the split-bf16 words earn their
    keep), and an **accumulation** term — ~eps32 * sqrt(n) of random-
    walk rounding for the uncompensated engines, ~eps32^2 * n +
    one final rounding for the TwoSum-compensated family.  The model
    ranks engines for budget filtering; ``measure=True`` sweeps
    replace it with the measured harness
    (``measured_percent_error``).
    """
    n = max(int(n), 1)
    if plan.method in _DOUBLE_DOUBLE:
        # dd: no multiplicand truncation (full f32 words, f64 inputs
        # split exactly on entry) and every pair merge is error-free
        # to O(eps32^2) — what remains is ~log2(n) second-order
        # renormalisation terms.  ~1e-11 % at 2^22: only this family
        # fits under an f64-equivalent budget (~1e-10 %), while the
        # compensated family floors at its 2^-25 final rounding.
        return 100.0 * (2.0 ** -48) * (4.0 + math.log2(n))
    rep = 2.0 ** -(_multiplicand_bits(plan, dtype, op) + 1)
    if plan.method in _COMPENSATED:
        acc = _EPS32 * _EPS32 * n + 2.0 ** -25
    else:
        acc = _EPS32 * math.sqrt(n)
    return 100.0 * (rep + acc)


def measured_percent_error(plan: ReductionPlan, n: int, dtype, *,
                           op: str = "reduce_sum", seed: int = 0,
                           policy: PolicyArg = None) -> float:
    """Measured % error vs the fp64 oracle for one plan (the paper's
    harness, §5.4): a uniform-[0,1] problem — the paper's hard case —
    of the bucket size is executed under ``plan`` and compared against
    the double-precision CPU sum.  Reduce-family only; other families
    fall back to the analytical model.  ``policy`` rides into the
    executor so policy-gated plans (the dd family) pass their
    capability check, and results collapse through
    ``precision.dd_value`` — exact for scalars, hi+lo in f64 for the
    dd pair.  The probe is capped at 2^22 elements so a measured
    budget sweep stays interactive."""
    import numpy as np
    from repro.core import dispatch, precision
    spec = dispatch.op_spec(op)
    if spec.family != "reduce" or spec.measure is not None:
        return model_percent_error(plan, n, dtype, op=op)
    probe_n = min(max(int(n), 1), 1 << 22)
    x64 = precision.uniform_input(probe_n, seed=seed)
    x = jax.numpy.asarray(x64.astype(np.float32)).astype(dtype)
    kw = {} if policy is None else {"policy": policy}
    got = precision.dd_value(execute_plan(x, plan, op=op, **kw))
    if op == "squared_sum":
        x64 = np.asarray(x, np.float64) ** 2
    else:
        x64 = np.asarray(x, np.float64)
    return precision.percent_error(got, x64)


def model_cost(plan: ReductionPlan, n: int, dtype,
               op: str = "reduce_sum") -> float:
    """Analytical score: Brent-style T = depth + work/P + overheads.

    For the reduce family, depth is the paper's chained PRAM bound
    T^R(n) = (2R+3) log_{Rm^2} n (Eq. 24); for the scan family it is
    the triangular-MMA analogue T^R_scan(n) = (2R+4) log_{Rm} n
    (``theory.t_tc_scan``) with op counts from
    ``theory.op_count_scan``.  Work/P and the per-grid-step overhead are
    the finite-hardware corrections the paper observes experimentally
    (which is why the model here does NOT always answer R=1 like the
    pure PRAM model does).  Padding waste penalises tiles much larger
    than n.  The op's family comes from its registry entry
    (``repro.core.dispatch.OpSpec.family``); an op with a registered
    ``cost`` hook overrides this model entirely.
    """
    from repro.core import dispatch
    spec = dispatch.op_spec(op)
    if spec.cost is not None:
        return spec.cost(plan, n, dtype)
    n = max(int(n), 1)
    itemsize = jax.numpy.dtype(dtype).itemsize
    mem = n * itemsize / (4.0 * _VPU_THROUGHPUT)  # streaming traffic
    return _ENGINE_COSTS[plan.method](spec.family, plan, n,
                                      itemsize) + mem


# Segment count used when timing segment_sum candidates (the plan key
# does not carry it; 128 segments = one MXU lane tile).
_MEASURE_SEGMENTS = 128

# Cross-mesh combine model: one f32-scalar psum per mesh axis, tree
# depth log2(axis size), in the same arbitrary PRAM-step units as the
# local terms.  Which axes are the slow DCI hops comes from the
# combine layer itself (``repro.distributed.collectives.SLOW_AXES``);
# every other axis rides the fast ICI.
_PSUM_STEP_FAST = 24.0
_PSUM_STEP_SLOW = 512.0


def combine_model_cost(mesh: MeshArg) -> float:
    """Model cost of the cross-device scalar tree combine — constant
    across candidates (it ranks nothing within one sweep) but part of
    the honest total a mesh-keyed plan records in ``cost``."""
    from repro.distributed.collectives import SLOW_AXES
    axes = mesh_axes(mesh)
    if axes is None:
        return 0.0
    total = 0.0
    for name, size in axes:
        if size <= 1:
            continue
        step = _PSUM_STEP_SLOW if name in SLOW_AXES \
            else _PSUM_STEP_FAST
        total += step * math.log2(size)
    return total


def _measure_problem(op: str, n: int, dtype, seed: int):
    """The op-representative timed problem (input + op kwargs)."""
    import numpy as np
    from repro.core import dispatch
    spec = dispatch.op_spec(op)
    rng = np.random.default_rng(seed)
    if spec.measure is not None:
        return spec.measure(n, dtype, rng)
    x = jax.numpy.asarray(
        rng.standard_normal(n).astype(np.float32)).astype(dtype)
    kwargs = {}
    if spec.family == "segment":
        kwargs = {
            "segment_ids": jax.numpy.asarray(
                rng.integers(0, _MEASURE_SEGMENTS, n)
                .astype(np.int32)),
            "num_segments": _MEASURE_SEGMENTS,
        }
    return x, kwargs


def _sharded_executor(plan: ReductionPlan, op: str, axes: tuple, x,
                      kwargs: dict):
    """The timed callable for a mesh-keyed measured sweep.

    Builds a live mesh matching ``axes`` (raising when this host cannot
    — measuring a mesh plan on absent hardware would time the wrong
    thing, exactly like measuring for a foreign backend), shards every
    same-leading-dim array operand over all mesh axes, and runs
    per-device ``execute_plan`` + the hierarchical scalar combine under
    ``shard_map`` — the same local-partial/tree-combine structure
    ``repro.distributed.tc_collectives`` executes in production.
    """
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.distributed import collectives as coll
    names = tuple(a for a, _ in axes)
    sizes = tuple(s for _, s in axes)
    need = math.prod(sizes)
    if need > len(jax.devices()):
        raise ValueError(
            f"cannot measure mesh {mesh_signature(axes)!r} plans on a "
            f"{len(jax.devices())}-device host; use the analytical "
            f"model (measure=False) or tune on the target mesh")
    if x.shape[0] % need:
        raise ValueError(
            f"measured-sweep problem of leading dim {x.shape[0]} does "
            f"not shard over {need} devices")
    hw_mesh = compat.make_mesh(sizes, names)
    lead = x.shape[0]
    arr_keys = tuple(
        k for k, v in kwargs.items()
        if hasattr(v, "ndim") and v.ndim >= 1 and v.shape[0] == lead)
    static = {k: v for k, v in kwargs.items() if k not in arr_keys}

    def spec_of(v):
        return P(names, *([None] * (v.ndim - 1)))

    def body(xl, *arrs):
        kw = dict(static, **dict(zip(arr_keys, arrs)))
        partial = execute_plan(xl, plan, op=op, **kw)
        return coll.mesh_psum(partial, names)

    f = compat.shard_map(
        body, mesh=hw_mesh,
        in_specs=(spec_of(x),) + tuple(spec_of(kwargs[k])
                                       for k in arr_keys),
        out_specs=P())
    extras = tuple(kwargs[k] for k in arr_keys)
    return lambda v: f(v, *extras)


def measure_cost(plan: ReductionPlan, n: int, dtype, *, iters: int = 5,
                 warmup: int = 2, seed: int = 0,
                 op: str = "reduce_sum", mesh: MeshArg = None,
                 policy: PolicyArg = None) -> float:
    """Wall-clock microseconds for one plan on this host's backend.

    The timed problem comes from the op's registry entry: an op with a
    ``measure`` hook builds its own representative input (masked_mean's
    mask, expert_counts' one-hot matrix); otherwise the family default
    is a size-n 1D stream (plus random segment ids for the segment
    family).  With ``mesh`` the size-n problem is *global*: it is
    sharded over a live mesh of that shape and the timed region is the
    per-device local execute plus the hierarchical scalar combine
    under ``shard_map``.
    """
    axes = mesh_axes(mesh)
    x, kwargs = _measure_problem(op, n, dtype, seed)
    if policy is not None:
        # Policy-gated plans (the dd family) need their policy at
        # execute time or the capability check refuses them.
        kwargs = dict(kwargs, policy=policy)
    if axes is None:
        fn = lambda v: execute_plan(v, plan, op=op, **kwargs)
    else:
        fn = _sharded_executor(plan, op, axes, x, kwargs)
    out = None
    for _ in range(warmup):
        out = fn(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def execute_plan(x, plan: ReductionPlan, *, op: str = "reduce_sum",
                 **op_kwargs):
    """Run one problem under ``plan`` — the subsystem's ONE executor.

    Every op family goes through here: the auto path of every
    ``integration`` hook, the measured sweep, and the benchmark
    drivers, so no call site carries hardcoded chain/block_rows.  The
    op's engine runner comes from the TC-op registry
    (``repro.core.dispatch.execute``); op-specific operands (a scan's
    ``axis``/``inclusive``, a segmented sum's ``segment_ids`` /
    ``num_segments``, masked_mean's ``mask``) ride ``op_kwargs``.
    """
    from repro.core import dispatch
    return dispatch.execute(op, x, plan, **op_kwargs)


# ----------------------------------------------------------- registry

# On-disk schema version.  Version 1 wraps the plan table as
# {"version": 1, "plans": {key: plan-dict}}; the legacy (pre-version)
# form was the bare plan table and still loads.  A file written by a
# FUTURE schema is refused with a clear error instead of being
# half-parsed: a fleet rolls registry schema forward with its code.
SCHEMA_VERSION = 1


@contextlib.contextmanager
def _store_lock(path: str, shared: bool = False):
    """Advisory file lock on ``<path>.lock`` serialising cross-process
    store writes (no-op where ``fcntl`` is unavailable).  A sidecar
    lock file keeps the store itself atomically replaceable."""
    if fcntl is None:  # pragma: no cover - non-POSIX host
        yield
        return
    fd = os.open(path + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_SH if shared else fcntl.LOCK_EX)
        yield
    finally:
        fcntl.flock(fd, fcntl.LOCK_UN)
        os.close(fd)


def _atomic_write(path: str, text: str) -> None:
    """Write-to-temp + ``os.replace``: readers only ever see a complete
    store, even if a writer dies mid-write."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    tmp = tempfile.NamedTemporaryFile(
        "w", dir=d, prefix=os.path.basename(path) + ".",
        suffix=".tmp", delete=False)
    try:
        with tmp:
            tmp.write(text)
            tmp.flush()
            os.fsync(tmp.fileno())
        os.replace(tmp.name, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp.name)
        raise


def _prefer_incoming(ours: ReductionPlan,
                     theirs: ReductionPlan) -> bool:
    """Merge rule: measured evidence beats the analytical model; among
    equals, a cheaper plan (better tuned winner) beats a dearer one."""
    rank = {"model": 0, "measured": 1}
    ro, rt = rank.get(ours.source, 0), rank.get(theirs.source, 0)
    if rt != ro:
        return rt > ro
    return theirs.cost < ours.cost


class PlanRegistry:
    """Thread-safe in-memory plan cache over a shareable on-disk store.

    The JSON form is ``{"version": 1, "plans": {key: plan-dict}}``
    (see ``plan_key`` for the key grammar) so tuned tables can be
    shipped with a model config or diffed in review; the legacy bare
    ``{key: plan-dict}`` form still loads.  ``save`` is crash- and
    concurrency-safe: an advisory file lock serialises writers, the
    on-disk table is merged in before writing (two processes tuning
    disjoint shapes both survive), and the write itself is
    write-to-temp + ``os.replace`` so readers never see a torn file.
    ``sweep_worker`` optionally holds a ``SweepWorker`` that
    ``get_plan`` hands model-cost resolutions to for background
    measured upgrade.
    """

    def __init__(self, path: Optional[str] = None):
        self._plans: dict[str, ReductionPlan] = {}
        self._mu = threading.Lock()
        self.path = path
        self.sweep_worker: Optional["SweepWorker"] = None

    def get(self, key: str) -> Optional[ReductionPlan]:
        return self._plans.get(key)

    def put(self, key: str, plan: ReductionPlan) -> None:
        with self._mu:
            self._plans[key] = plan

    def items(self):
        with self._mu:
            return sorted(self._plans.items())

    def clear(self) -> None:
        with self._mu:
            self._plans.clear()

    def __len__(self) -> int:
        return len(self._plans)

    def merge(self, other: "PlanRegistry") -> int:
        """Adopt ``other``'s entries: absent keys always, conflicting
        keys per the merge rule (measured beats model, then lower
        cost).  Returns the number of entries adopted."""
        adopted = 0
        for key, theirs in other.items():
            with self._mu:
                ours = self._plans.get(key)
                if ours is None or _prefer_incoming(ours, theirs):
                    self._plans[key] = theirs
                    adopted += 1
        return adopted

    def mesh_signatures(self) -> tuple:
        """Every distinct ``|mesh:`` signature keyed in the registry,
        sorted — what an elastic-remesh invalidation scans."""
        sigs = set()
        for key, _ in self.items():
            if "|mesh:" in key:
                sigs.add(key.rsplit("|mesh:", 1)[1])
        return tuple(sorted(sigs))

    def invalidate_mesh(self, mesh: MeshArg) -> tuple:
        """Drop every plan keyed to mesh signature ``mesh`` (a
        signature string, or anything ``mesh_signature`` accepts).
        Plans tuned for a dead mesh geometry must not serve the new
        mesh — the next ``method='auto'`` call under the new mesh
        resolves (and tunes) a fresh ``|mesh:`` key.  Returns the
        removed keys, sorted."""
        sig = mesh if isinstance(mesh, str) else mesh_signature(mesh)
        if not sig:
            return ()
        suffix = f"|mesh:{sig}"
        with self._mu:
            dead = sorted(k for k in self._plans
                          if k.endswith(suffix))
            for k in dead:
                del self._plans[k]
        return tuple(dead)

    def to_json(self) -> str:
        return json.dumps(
            {"version": SCHEMA_VERSION,
             "plans": {k: p.to_dict() for k, p in self.items()}},
            indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "PlanRegistry":
        data = json.loads(text)
        if "version" in data or "plans" in data:
            version = data.get("version")
            if not isinstance(version, int):
                raise ValueError(
                    f"plan-store schema: 'plans' present but "
                    f"'version' is {version!r} (expected an int)")
            if version > SCHEMA_VERSION:
                raise ValueError(
                    f"plan store was written by schema version "
                    f"{version}, but this build reads at most "
                    f"{SCHEMA_VERSION} — upgrade the code or "
                    f"regenerate the store with this build")
            table = data["plans"]
        else:
            table = data  # legacy bare {key: plan-dict} form
        reg = cls()
        for k, d in table.items():
            reg.put(k, ReductionPlan.from_dict(d))
        return reg

    def save(self, path: Optional[str] = None) -> None:
        """Atomically persist, merging the current on-disk table in
        first so concurrent writers lose nothing."""
        path = path if path is not None else self.path
        if not path:
            raise ValueError(
                "PlanRegistry.save: no path given and none bound "
                "(pass path= or construct with PlanRegistry(path))")
        with _store_lock(path):
            if os.path.exists(path):
                self.merge(PlanRegistry.load(path))
            self._atomic_save(path)
        self.path = self.path or path

    def _atomic_save(self, path: str) -> None:
        _atomic_write(path, self.to_json())

    @classmethod
    def load(cls, path: str) -> "PlanRegistry":
        with open(path) as f:
            reg = cls.from_json(f.read())
        reg.path = path
        return reg

    def reload(self) -> int:
        """Merge the bound store file back into memory — how a serving
        process picks up plans tuned by its fleet peers.  Returns the
        number of entries adopted (0 when unbound or absent)."""
        if not self.path or not os.path.exists(self.path):
            return 0
        with _store_lock(self.path, shared=True):
            disk = PlanRegistry.load(self.path)
        return self.merge(disk)


_default_registry: Optional[PlanRegistry] = None


def default_registry() -> PlanRegistry:
    """Process-wide registry; pre-seeded from $REPRO_AUTOTUNE_CACHE if
    that file exists (ship a tuned table, skip the sweep)."""
    global _default_registry
    if _default_registry is None:
        path = os.environ.get("REPRO_AUTOTUNE_CACHE", "")
        if path and os.path.exists(path):
            _default_registry = PlanRegistry.load(path)
        else:
            _default_registry = PlanRegistry()
    return _default_registry


def bind_default_registry(path: str) -> PlanRegistry:
    """Bind the process-wide registry to a shared store file: merge the
    file in if it exists (plans tuned by fleet peers), and make
    ``save()`` / ``reload()`` default to it.  Returns the registry."""
    reg = default_registry()
    reg.path = path
    reg.reload()
    return reg


def reset_default_registry() -> None:
    """Drop the process-wide cache (tests / re-tuning), closing any
    attached background sweep worker first."""
    global _default_registry
    if _default_registry is not None and \
            _default_registry.sweep_worker is not None:
        _default_registry.sweep_worker.close()
    _default_registry = None


# ----------------------------------------------------------- autotune


class SweepCancelled(RuntimeError):
    """Raised by ``autotune`` when its ``cancel`` predicate fires —
    how a background sweep worker abandons an in-flight measured
    sweep at a candidate boundary during shutdown."""


def autotune(n: int, dtype, *, op: str = "reduce_sum",
             measure: bool = False, chains=CHAINS, blocks=BLOCK_ROWS,
             m: int = DEFAULT_M, engine: Engine = None,
             mesh: MeshArg = None, policy: PolicyArg = None,
             objective: ObjectiveArg = None,
             bucket: BucketArg = DEFAULT_BUCKET,
             cancel=None) -> ReductionPlan:
    """Sweep the candidate space for one problem and return the winner.

    ``measure=False`` (default, and the only mode that is deterministic
    and hardware-free) scores with the analytical model; ``measure=True``
    times each candidate on the live backend.  ``engine`` restricts the
    sweep (per-engine geometry tuning).  The sweep is bucketed — score
    at ``bucket_cap(n, bucket)`` so every n in the bucket gets the same
    plan, and the cap's error score bounds the whole bucket (the error
    model's accumulation term grows with n); ``bucket=None`` tunes at
    the exact n.

    With ``mesh`` the sweep tunes the **local per-device chain
    geometry** of a size-n *global* problem: candidates are enumerated
    and model-scored at the per-device shard size n / device-count
    (plus the constant cross-mesh combine term), or wall-clock timed
    under ``shard_map`` over a live mesh of that shape — so a 1-device
    and a sharded run of the same global n resolve different R /
    block_rows.  Inside a ``shard_map`` body every engine is structurally
    legal (the shard is local), so the mesh sweep is *not* restricted to
    the distribution-safe engines the way the pjit auto path is.

    With a ``policy`` carrying an ``error_budget_pct`` the sweep is
    **error-budget-aware**: every candidate is additionally scored by
    percent error vs the fp64 oracle (``model_percent_error``, or the
    measured harness ``measured_percent_error`` when
    ``measure=True``), and the winner is the *fastest candidate whose
    error meets the budget* — the paper's accuracy contract made a
    selection constraint.  When no candidate meets the budget the
    most accurate one wins (best effort — a training step must not
    fail because a ceiling was set too tight; the plan's recorded
    ``error_pct`` makes the shortfall visible).

    With an ``objective`` carrying a ``latency_slo_ms`` the selection
    flips to the budget rule's dual: among the budget-eligible
    candidates, the **most accurate one whose latency estimate meets
    the SLO** wins (``cost`` in µs when measured, model units at the
    nominal 1-unit-~=-1-µs anchor otherwise).  When nothing meets the
    SLO the fastest eligible candidate wins — best effort again, with
    the shortfall visible in the plan's recorded ``latency_ms``.  Both
    constraints compose: the error budget filters eligibility first,
    the SLO then picks within it.
    """
    axes = mesh_axes(mesh)
    objective = as_objective(objective)
    nb = bucket_cap(n, bucket)
    # Local per-device shard of the bucketed global problem.  The
    # measured size is the bucket rounded UP to a device-count
    # multiple, so non-power-of-two meshes (data=3, ...) shard evenly
    # and the timed shard matches the enumerated geometry.
    need = 1 if axes is None else math.prod(s for _, s in axes)
    local = max(math.ceil(nb / need), 1)
    local_nb = nb if axes is None else bucket_cap(local, bucket)
    measure_nb = nb if axes is None else local * need
    combine = combine_model_cost(axes)
    budget = None if policy is None else policy.error_budget_pct
    # The SLO rule ranks by accuracy, so an objective forces error
    # scoring even without a budget.
    want_err = budget is not None or objective is not None
    best: Optional[ReductionPlan] = None      # meets budget (+ SLO)
    fastest: Optional[ReductionPlan] = None   # fastest within budget
    fallback: Optional[ReductionPlan] = None  # most accurate seen
    for cand in candidate_plans(local_nb, dtype, chains=chains,
                                blocks=blocks, m=m, engine=engine,
                                op=op, policy=policy):
        if cancel is not None and cancel():
            # Bail at a candidate boundary (``cancel`` is how the
            # background SweepWorker abandons a sweep on shutdown —
            # a wedged measured sweep must not outlive close()).
            raise SweepCancelled(
                f"autotune sweep for op={op!r} n={n} cancelled")
        if measure:
            cost = measure_cost(cand, measure_nb, dtype, op=op,
                                mesh=axes, policy=policy)
            cand = dataclasses.replace(cand, source="measured", cost=cost)
        else:
            cost = model_cost(cand, local_nb, dtype, op=op) + combine
            cand = dataclasses.replace(cand, source="model", cost=cost)
        if objective is not None:
            lat_us = cost if measure else cost * _MODEL_UNIT_US
            cand = dataclasses.replace(cand, latency_ms=lat_us / 1e3)
        if want_err:
            err = (measured_percent_error(cand, local_nb, dtype, op=op,
                                          policy=policy)
                   if measure else
                   model_percent_error(cand, local_nb, dtype, op=op))
            cand = dataclasses.replace(cand, error_pct=err)
            if fallback is None or err < fallback.error_pct:
                fallback = cand
            if budget is not None and err > budget:
                continue
        if fastest is None or cand.cost < fastest.cost:
            fastest = cand
        if objective is None:
            continue                 # objective-free: fastest wins
        if cand.latency_ms <= objective.latency_slo_ms and \
                (best is None or cand.error_pct < best.error_pct):
            best = cand
    if best is None:
        best = fastest      # no objective, or nothing met the SLO
    if best is None:
        best = fallback     # nothing met the budget: most accurate
    if best is None:
        raise ValueError(f"no reduction candidates for engine={engine!r}")
    return best


def get_plan(n: int, dtype, *, op: str = "reduce_sum",
             backend: Optional[str] = None,
             registry: Optional[PlanRegistry] = None,
             measure: bool = False, engine: Engine = None,
             mesh: MeshArg = None, policy: PolicyArg = None,
             objective: ObjectiveArg = None,
             bucket: BucketArg = DEFAULT_BUCKET) -> ReductionPlan:
    """Cached plan lookup — the entry point of ``method='auto'``.

    Registry hit: return it (a model-mode entry is re-tuned and
    replaced when ``measure=True`` asks for wall-clock evidence).
    Miss: run ``autotune`` once for the (op, n-bucket, dtype, backend
    [, engine][, prec][, lat][, mesh]) key and cache the winner — the
    n-bucket is ``bucket_cap(n, bucket)``, so under the default pow-2
    policy one tuned plan serves every n in its octave and an exact
    tune is an explicit ``bucket=None`` opt-out.  A cold miss NEVER
    blocks on a measured sweep: the model-cost winner is returned
    immediately, and when the registry has a ``sweep_worker`` attached
    the key is queued for a background measured sweep that swaps in
    the wall-clock winner off the hot path.
    ``mesh`` keys (and tunes) the plan for the local shard of a size-n
    global problem under that mesh shape — the mesh-collective path
    (``repro.distributed.tc_collectives``) and the auto path under a
    live mesh both resolve here, so a sharded run never silently
    reuses the single-device geometry.  ``policy`` keys the plan by
    the precision signature and makes the sweep error-budget-aware
    (see ``autotune``) — two calls differing only in budget resolve
    independent plans.  ``objective`` keys the plan by the latency
    signature and makes the selection SLO-aware — a serving stack's
    prefill (B×S×V) and decode (B×1×V) reductions land in different
    n-buckets and so resolve distinct, independently-selected plans
    under one SLO.  Measuring for a backend other than the live one is
    refused rather than silently timed on the wrong hardware.
    """
    reg = registry if registry is not None else default_registry()
    key = plan_key(op, n, dtype, backend, engine, mesh, policy,
                   objective, bucket)
    plan = reg.get(key)
    if plan is None or (measure and plan.source != "measured"):
        if measure and backend is not None \
                and backend != jax.default_backend():
            raise ValueError(
                f"cannot measure for backend {backend!r} on a "
                f"{jax.default_backend()!r} host; use the analytical "
                f"model (measure=False) or tune on the target hardware")
        plan = autotune(n, dtype, op=op, measure=measure, engine=engine,
                        mesh=mesh, policy=policy, objective=objective,
                        bucket=bucket)
        reg.put(key, plan)
    if plan.source != "measured" and reg.sweep_worker is not None \
            and backend in (None, jax.default_backend()):
        reg.sweep_worker.submit(
            key, dict(n=n, dtype=dtype, op=op, engine=engine,
                      mesh=mesh, policy=policy, objective=objective,
                      bucket=bucket))
    return plan


# ------------------------------------------- warmup & background sweeps


def warmup(ops, shapes, *, dtype=None, registry=None, measure=False,
           backend=None, engine=None, mesh=None, policy=None,
           objective=None, bucket=DEFAULT_BUCKET) -> dict:
    """Pre-resolve the serving hot set so live traffic never tunes.

    ``ops`` is an op name or an iterable of them; ``shapes`` an
    iterable of sizes (or ``(n, dtype)`` pairs — the bare ``dtype``
    argument, default float32, covers the rest).  Every (op, shape)
    pair is resolved through ``get_plan`` under the given bucket
    policy, so shapes collapsing onto one bucket cap tune at most
    once.  Returns ``{"resolved", "tuned", "keys"}`` — ``tuned``
    counts the actual tuning events (registry misses), the number the
    fleet-scale story wants near the bucket count, not the shape
    count.
    """
    reg = registry if registry is not None else default_registry()
    base_dtype = jax.numpy.float32 if dtype is None else dtype
    if isinstance(ops, str):
        ops = (ops,)
    tuned = 0
    keys: dict[str, None] = {}
    for op in ops:
        for shape in shapes:
            n, dt = shape if isinstance(shape, tuple) \
                else (shape, base_dtype)
            key = plan_key(op, n, dt, backend, engine, mesh, policy,
                           objective, bucket)
            if reg.get(key) is None:
                tuned += 1
            get_plan(n, dt, op=op, backend=backend, registry=reg,
                     measure=measure, engine=engine, mesh=mesh,
                     policy=policy, objective=objective, bucket=bucket)
            keys[key] = None
    return {"resolved": len(keys), "tuned": tuned,
            "keys": tuple(keys)}


class SweepWorker:
    """Background measured-sweep upgrader for model-cost plans.

    ``get_plan`` serves a cold miss from the analytical model
    immediately and — when a worker is attached to the registry
    (``registry.sweep_worker = worker``) — submits the key here; the
    worker re-tunes it with ``measure=True`` off the hot path and
    swaps the wall-clock winner into the registry.  The lifecycle
    follows the ``data/pipeline.py`` prefetch pattern: the worker loop
    uses timed queue gets that re-check the stop event, submissions
    are non-blocking (a full queue drops the upgrade — it will be
    resubmitted on the next model-plan serve), and ``close()`` sets
    the stop flag, drains the queue, and joins with a timeout, so a
    server shutdown can never deadlock on an in-flight sweep.
    ``upgraded`` and ``failed`` count finished and failed sweeps; each
    failure is also logged with its traceback.
    """

    def __init__(self, registry=None, *, max_pending: int = 256,
                 iters: int = 3, poll_s: float = 0.1):
        self._registry = registry
        self._iters = iters
        self._poll_s = poll_s
        self._q: queue.Queue = queue.Queue(maxsize=max_pending)
        self._stop = threading.Event()
        self._inflight: set[str] = set()
        self._mu = threading.Lock()
        self.upgraded = 0
        self.failed = 0
        self._thread = threading.Thread(
            target=self._run, name="autotune-sweep", daemon=True)
        self._thread.start()

    def _reg(self) -> PlanRegistry:
        return self._registry if self._registry is not None \
            else default_registry()

    def submit(self, key: str, spec: dict) -> bool:
        """Queue ``key`` for a measured upgrade (non-blocking; dedupes
        in-flight keys).  ``spec`` holds the ``autotune`` kwargs that
        produced the model plan.  Returns whether the key was queued."""
        if self._stop.is_set():
            return False
        with self._mu:
            if key in self._inflight:
                return False
            self._inflight.add(key)
        try:
            self._q.put_nowait((key, spec))
            return True
        except queue.Full:
            with self._mu:
                self._inflight.discard(key)
            return False

    def pending(self) -> int:
        with self._mu:
            return len(self._inflight)

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Block (tests / warmup barriers) until every submitted key
        has been swept or ``timeout_s`` passes."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if not self.pending():
                return True
            time.sleep(self._poll_s / 2)
        return not self.pending()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                key, spec = self._q.get(timeout=self._poll_s)
            except queue.Empty:
                continue
            try:
                reg = self._reg()
                current = reg.get(key)
                if current is not None and current.source == "measured":
                    continue  # a peer already upgraded it
                spec = dict(spec)
                n, dtype = spec.pop("n"), spec.pop("dtype")
                plan = autotune(n, dtype, measure=True,
                                cancel=self._stop.is_set, **spec)
                reg.put(key, plan)
                self.upgraded += 1
            except SweepCancelled:
                pass  # shutdown raced the sweep; model plan keeps serving
            except Exception:
                # A failed sweep (e.g. a mesh plan on a host without
                # that mesh, or a kernel the backend refuses) keeps the
                # model plan serving, but is counted in ``failed`` and
                # logged with its traceback, never swallowed.
                self.failed += 1
                log.exception("autotune sweep for %s failed; the model "
                              "plan keeps serving", key)
            finally:
                with self._mu:
                    self._inflight.discard(key)

    def close(self, timeout_s: float = 5.0) -> None:
        """Idempotent shutdown: stop, drain the queue, join."""
        self._stop.set()
        while True:
            try:
                key, _ = self._q.get_nowait()
            except queue.Empty:
                break
            with self._mu:
                self._inflight.discard(key)
        self._thread.join(timeout=timeout_s)

    def __enter__(self) -> "SweepWorker":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

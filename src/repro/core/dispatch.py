"""The TC-op registry: one declarative dispatch layer for every
tensor-core op family.

The paper's chained-MMA encoding powers three op families in this repo
(arithmetic reductions, prefix scans, segmented sums), and Dakkak et
al. ("Accelerating Reduction and Scan Using Tensor Core Units") show
they share one TCU algorithm skeleton.  This module is the single
place that knowledge lives: each op (``reduce_sum``, ``squared_sum``,
``masked_mean``, ``expert_counts``, ``scan``, ``masked_cumsum``,
``segment_sum``) is registered as an :class:`OpSpec` declaring

  * its execution engines (:class:`EngineSpec`): the ones-contraction
    ``'mma'``, the explicitly chained ``'mma_chained'`` core, the
    compensated split-bf16 ``'mma_ec'`` family (and its Pallas twin
    ``'pallas_ec'``), the double-double ``'mma_dd'`` family (and its
    twin ``'pallas_dd'`` — f64-equivalent (hi, lo) pairs, reachable
    only under an explicit ``accum_dtype=float64`` policy), the
    hand-tiled ``'pallas'`` kernel, and the classic ``'vpu'`` baseline
    — each with a ``run(x, plan, **op_kwargs)`` callable;
  * per-engine **capability predicates** — multi-device safety, axis /
    ndim / layout support, dtype restrictions, and the
    precision-policy facts (which accumulator dtypes the engine
    honours, how many split-bf16 words it can run) — evaluated
    against a :class:`DispatchContext` built from the call (the
    context carries the caller's ``repro.core.precision.MmaPolicy``);
  * a pure-jnp **reference oracle** (what the tests compare every
    engine against);
  * the autotuner hooks: which knobs each engine sweeps
    (``EngineSpec.sweep``) and an optional per-op cost-model override
    (``OpSpec.cost``).

``dispatch(op, x, method=..., **op_kwargs)`` is the one entry point
the framework hooks (``repro.core.integration``) call: explicit
methods are capability-checked (an illegal engine raises ``ValueError``
with the reason — no hook can silently misroute again), and
``method='auto'`` restricts the autotuner's sweep to the engines that
are *legal for this call* before executing the winning plan.  Both
paths are routed by ``_route`` and run the engine in one place, where
the call is counted and traced (``repro.obs``).  The autotuner
(``repro.core.autotune``) enumerates its candidate space off the same
registry, so adding an op or an engine is one ``register()`` call —
not another dispatch ladder.

This module is deliberately the only place in ``src/`` where engine
names are compared (``scripts/check.sh`` greps for ``method ==``
ladders outside it).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.precision import MmaPolicy, as_policy

# ------------------------------------------------------------- context


@dataclasses.dataclass(frozen=True)
class DispatchContext:
    """Trace-time facts one dispatch decision is made from.

    Everything here is static shape/dtype/mesh/policy information, so
    building a context (and therefore the whole auto path) is
    jit-safe.
    """
    op: str
    shape: tuple
    dtype: str
    multi_device: bool
    axis: Optional[tuple] = None    # reduce family: reduced-axis subset
    scan_axis: Optional[int] = None  # scan family: the scanned axis
    mesh_axes: Optional[tuple] = None  # ((name, size), ...) of the live
    #                                    multi-device mesh, mesh order;
    #                                    None on a single device
    policy: Optional[MmaPolicy] = None  # the call's precision policy
    extras: Optional[tuple] = None  # op-family static facts as a
    #                                 ((key, value), ...) tuple (hashable
    #                                 — the attention family records its
    #                                 mask/layout structure here)

    def extra(self, key: str, default=None):
        """Look up one op-family fact recorded in ``extras``."""
        for k, v in self.extras or ():
            if k == key:
                return v
        return default

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def axis_subset(self) -> bool:
        """True when only *some* axes are reduced (batched reduction)."""
        return self.axis is not None and len(self.axis) < self.ndim

    @property
    def flat(self) -> bool:
        """Effectively 1D: the op's axis walk IS the flattened order."""
        if self.ndim <= 1:
            return True
        if self.scan_axis is None:
            return False
        return (self.scan_axis == self.ndim - 1
                and all(d == 1 for d in self.shape[:-1]))


def _live_mesh_axes() -> Optional[tuple]:
    """((name, size), ...) of the ambient >1-device mesh, or None.

    The mesh comes from the sharding context
    (``repro.distributed.sharding.current_mesh``); a mesh whose device
    product is 1 is indistinguishable from no mesh for dispatch
    purposes (every engine is legal, plans carry no mesh signature)."""
    from repro.distributed import sharding as shd
    mesh = shd.current_mesh()
    if mesh is None or math.prod(mesh.devices.shape) <= 1:
        return None
    return tuple((str(name), int(size))
                 for name, size in mesh.shape.items())


# -------------------------------------------------------------- engines


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One execution engine of an op, with declarative capabilities.

    ``run(x, plan, **op_kwargs)`` executes the op under a
    ``repro.core.autotune.ReductionPlan`` whose geometry fields
    (variant / chain / block_rows / m) it honours.  ``sweep`` names the
    plan knobs the autotuner enumerates for this engine (``()`` =
    geometry-free, one candidate).  The capability flags are evaluated
    by :func:`capability_reason`; ``dtypes`` is ``None`` for
    any-input-dtype (every engine accumulates in f32 regardless — the
    precision contract) or a tuple of allowed input dtype names.
    """
    name: str
    run: Callable
    multi_device_safe: bool = False
    axis_subsets: bool = False      # batched reductions (axis=...)
    needs_flat: bool = False        # requires effectively-1D layout
    ndim: Optional[int] = None      # exact input rank, None = any
    dtypes: Optional[tuple] = None  # allowed input dtype names
    sweep: tuple = ()               # of 'chain'/'block_rows'/'split_words'
    max_split_words: int = 1        # split-bf16 words the engine runs
    accum_dtypes: tuple = ("float32",)  # accumulators it can honour
    predicate: Optional[Callable] = None  # (ctx) -> reason-or-None;
    #                                       op-family structural checks
    #                                       beyond the shared flags
    #                                       (reads ``ctx.extra(...)``)


def capability_reason(eng: EngineSpec, ctx: DispatchContext, *,
                      env: bool = True) -> Optional[str]:
    """Why ``eng`` cannot serve ``ctx`` — or None when it can.

    ``env=False`` skips the environment predicate (multi-device mesh)
    and checks only structural shape/axis/dtype facts; the executor
    uses that mode so an already-chosen plan is still validated against
    the input it is applied to.
    """
    if env and ctx.multi_device and not eng.multi_device_safe:
        return ("not distribution-safe: flatten-and-pad forces a "
                "re-layout of sharded operands under a live "
                "multi-device mesh")
    if ctx.axis_subset and not eng.axis_subsets:
        return "flatten-only engine: no axis-subset (batched) support"
    if eng.needs_flat and not ctx.flat:
        return ("operates on the flattened input; use a batched engine "
                "for multi-axis inputs")
    if eng.ndim is not None and ctx.ndim != eng.ndim:
        return f"requires an ndim == {eng.ndim} input"
    if eng.dtypes is not None and ctx.dtype not in eng.dtypes:
        return f"dtype {ctx.dtype} not in {eng.dtypes}"
    reason = _policy_reason(eng, ctx.policy)
    if reason is not None:
        return reason
    if eng.predicate is not None:
        return eng.predicate(ctx)
    return None


def _policy_reason(eng: EngineSpec,
                   policy: Optional[MmaPolicy]) -> Optional[str]:
    """Why ``eng`` cannot honour ``policy`` — or None when it can.
    The policy-only slice of the capability predicates, shared by the
    full context check and plan resolvers that have no input array
    (``local_plan``)."""
    if policy is None:
        # No policy means the default f32 *scalar* contract: an engine
        # that cannot accumulate in float32 (the dd family, whose
        # result is an unevaluated (hi, lo) pair, not a scalar) is
        # only reachable through an explicit accum_dtype policy.
        if "float32" not in eng.accum_dtypes:
            return ("double-word engine: returns a (hi, lo) dd pair, "
                    "not the default f32 scalar — request it with an "
                    "explicit MmaPolicy(accum_dtype=jnp.float64)")
        return None
    acc = jnp.dtype(policy.accum_dtype).name
    if acc not in eng.accum_dtypes:
        return (f"cannot honour accum_dtype={acc} (engine "
                f"accumulates in {eng.accum_dtypes})")
    if policy.split_words > eng.max_split_words:
        return (f"cannot honour split_words={policy.split_words}: "
                f"the engine runs at most {eng.max_split_words} "
                f"multiplicand word(s) — use the mma_ec family")
    return None


# ------------------------------------------------------------------ ops


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One registered TC-op.

    ``engines`` is the ordered tuple of concrete engines (order is the
    enumeration — and engine-restriction key — order); ``aliases`` maps
    accepted method spellings onto concrete engines (e.g. the scan
    family's ``'mma'`` *is* its chained triangular core).
    ``reference`` is the pure-jnp oracle with the op's exact keyword
    surface; ``size_of`` extracts the problem size the plan registry
    keys on; ``family`` picks the default analytical cost model and
    ``cost`` optionally overrides it per-op.
    """
    name: str
    family: str                     # 'reduce' | 'scan' | 'segment'
    engines: tuple                  # tuple[EngineSpec, ...]
    reference: Callable
    aliases: Optional[dict] = None
    size_of: Optional[Callable] = None   # (x, op_kwargs) -> int
    cost: Optional[Callable] = None      # (plan, n, dtype) -> float
    measure: Optional[Callable] = None   # (n, dtype, rng) -> (x, kw)
    # Per-op override of the autotuner's engine -> multiplicand-bits
    # table (autotune._ENGINE_BITS): e.g. norm_matmul's unfused_mma
    # runs the statistic through the f32 reduce engines, not bf16 MMAs.
    engine_bits: Optional[dict] = None   # {engine name: bits}

    def engine(self, name: str) -> Optional[EngineSpec]:
        name = (self.aliases or {}).get(name, name)
        for eng in self.engines:
            if eng.name == name:
                return eng
        return None

    def engine_names(self) -> tuple:
        return tuple(e.name for e in self.engines)

    def problem_size(self, x, op_kwargs: dict) -> int:
        if self.size_of is not None:
            return self.size_of(x, op_kwargs)
        return x.size


_REGISTRY: dict[str, OpSpec] = {}


def register(spec: OpSpec) -> OpSpec:
    """Add (or replace) one op in the registry."""
    _REGISTRY[spec.name] = spec
    return spec


def ops() -> tuple:
    """Registered op names, sorted."""
    return tuple(sorted(_REGISTRY))


def op_spec(name: str) -> OpSpec:
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(
            f"unknown TC-op {name!r}; registered: {', '.join(ops())}")
    return spec


def build_context(op: str, x, *, axis=None, scan_axis=None,
                  multi_device: Optional[bool] = None,
                  mesh_axes: Optional[tuple] = None,
                  policy: Optional[MmaPolicy] = None,
                  extras: Optional[tuple] = None) -> DispatchContext:
    if multi_device is None:
        if mesh_axes is None:
            mesh_axes = _live_mesh_axes()
        multi_device = mesh_axes is not None
    return DispatchContext(
        op=op, shape=tuple(x.shape), dtype=jnp.dtype(x.dtype).name,
        multi_device=multi_device, axis=axis, scan_axis=scan_axis,
        mesh_axes=mesh_axes, policy=policy, extras=extras)


def legal_engines(spec: OpSpec, ctx: DispatchContext) -> tuple:
    """Engine names (registration order) whose capabilities cover ctx."""
    return tuple(e.name for e in spec.engines
                 if capability_reason(e, ctx) is None)


def _unknown_method(spec: OpSpec, method: str) -> ValueError:
    accepted = spec.engine_names() + tuple(spec.aliases or ())
    return ValueError(
        f"unknown {spec.name} method: {method!r} (accepted: 'auto', "
        + ", ".join(repr(a) for a in sorted(accepted)) + ")")


def known_method(op: str, method: str) -> bool:
    """Does ``method`` spell an engine (or alias, or ``'auto'``) the op
    declares — regardless of capability?  Unknown spellings must raise
    at every API surface; only *capability* rejections may resolve
    through a fallback policy (``resolve_method``)."""
    return method == "auto" or op_spec(op).engine(method) is not None


def local_plan(op: str, n: int, dtype, method: str = "auto", *,
               mesh=None, chain: int = 4, precision=None,
               objective=None, bucket: str = "pow2"):
    """Resolve a method spelling to an executable plan for a size-n
    problem WITHOUT running it — how the mesh-collective layer
    (``repro.distributed.tc_collectives``) picks the per-device
    partial engine before entering ``shard_map``.

    ``'auto'`` consults the plan registry (mesh-keyed when ``mesh`` is
    given — the plan is tuned for the local shard of the size-n global
    problem; precision-keyed and error-budget-constrained when
    ``precision`` carries a policy; latency-keyed and SLO-selected
    when ``objective`` carries one; keyed at the ``bucket`` policy's
    cap — ``repro.core.autotune.bucket_cap`` — with ``bucket=None``
    the exact-key opt-out); an explicit spelling resolves
    through the op's aliases to a one-engine plan with the hooks'
    default ``chain`` geometry (and the policy's ``split_words``); an
    engine the op does not declare raises exactly like ``dispatch``.
    Capability checking happens at execution (``execute`` validates
    structurally) — inside a ``shard_map`` body the shard is local, so
    the environment predicate deliberately does not apply.
    """
    from repro.core import autotune
    spec = op_spec(op)
    policy = as_policy(precision)
    if method == "auto":
        # The autotuner's sweep prunes engines the policy forbids
        # (candidate_plans), so the resolved plan is always one the
        # execute-time predicates will accept.
        return autotune.get_plan(n, dtype, op=op, mesh=mesh,
                                 policy=policy, objective=objective,
                                 bucket=bucket)
    eng = spec.engine(method)
    if eng is None:
        raise _unknown_method(spec, method)
    reason = _policy_reason(eng, policy)
    if reason is not None:
        raise ValueError(
            f"engine {eng.name!r} cannot serve op {op!r} under this "
            f"precision policy: {reason}")
    return autotune.ReductionPlan(method=eng.name, chain=chain,
                                  **_plan_words(policy))


def _plan_words(policy: Optional[MmaPolicy]) -> dict:
    """Plan-field overrides an explicit policy pins (split words)."""
    if policy is None or policy.split_words == 1:
        return {}
    return {"split_words": int(policy.split_words)}


def supported_method(op: str, x, method: str, *, precision=None,
                     **op_kwargs) -> bool:
    """Would ``dispatch(op, x, method=...)`` accept this call?

    True when ``method`` is ``'auto'`` or resolves (through the op's
    aliases) to an engine whose capability predicates cover the call
    (including the precision policy, when one is given).  Callers with
    their own fallback policy (e.g. a hot path that maps an
    inapplicable ablation engine to the classic baseline instead of
    failing the whole forward pass) probe with this before
    dispatching.
    """
    if method == "auto":
        return True
    spec = op_spec(op)
    eng = spec.engine(method)
    if eng is None:
        return False
    ctx = _context_for(spec, x, op_kwargs, policy=as_policy(precision))
    return capability_reason(eng, ctx) is None


def resolve_method(op: str, x, method: str, *, fallback: str = "vpu",
                   precision=None, **op_kwargs) -> str:
    """``method`` when ``dispatch`` would accept it, else ``fallback``.

    The stay-trainable policy for the model/launch layers: a forward
    pass must survive every ``reduce_method`` ablation spelling, so
    consumers whose op cannot serve an engine (a flatten-only engine
    asked for a per-row statistic, a non-distribution-safe engine
    under a live mesh, an unknown string) map the knob onto a legal
    engine here instead of failing at trace time.  The hooks
    themselves stay strict — misrouting is only ever explicit, in one
    place, with the policy named by the ``fallback`` argument.

    A precision policy is never silently dropped: when the fallback
    itself cannot honour it (e.g. a split-word policy on a per-row
    statistic no split-capable engine serves), this raises
    ``ValueError`` naming the conflict here — at the resolve point —
    instead of deep inside the dispatch the doomed fallback would hit.
    """
    if supported_method(op, x, method, precision=precision,
                        **op_kwargs):
        return method
    if not supported_method(op, x, fallback, precision=precision,
                            **op_kwargs):
        pol = as_policy(precision)
        raise ValueError(
            f"no engine of op {op!r} serves this call: {method!r} and "
            f"the fallback {fallback!r} both fail the capability "
            f"predicates"
            + (f" under precision policy {pol.signature()!r}"
               if pol is not None else ""))
    obs.count("dispatch.fallbacks", (op, method, fallback))
    return fallback


# -------------------------------------------------------- entry points


def dispatch(op: str, x, *, method: str = "auto", chain=None,
             precision=None, objective=None, bucket: str = "pow2",
             **op_kwargs):
    """THE dispatch path: every framework hook lands here.

    Explicit ``method`` spellings are resolved through the op's alias
    map and capability-checked — an engine the op does not declare, or
    one whose predicates reject this input/mesh/policy, raises
    ``ValueError`` naming the reason.  ``method='auto'`` consults the
    autotuner's plan registry under the *legal* engine subset for this
    call and executes the winner.  ``chain`` (when not None) overrides
    the plan's chain length on the explicit path, preserving the
    hooks' R knob — an int is the paper's explicit R, and the string
    ``'auto'`` resolves the engine-restricted tuned plan (chain AND
    block geometry) from the registry, exactly like the kernels'
    per-engine 'auto' spellings.  The auto *method* ignores ``chain``
    (the plan's tuned geometry wins).

    ``precision`` carries the call's ``repro.core.precision.MmaPolicy``
    (or a bare ``jax.lax.Precision`` for back-compat): it narrows the
    legal engine set (accumulator dtype, split-word support), keys —
    and error-budget-constrains — the auto plan, casts the plain
    engines' multiplicands to ``policy.input_dtype``, and reaches the
    engine runners (the scan family's MMA einsum precision, the
    ``mma_ec`` family's split-word count).

    ``objective`` carries a latency target
    (``repro.core.autotune.LatencyObjective``, or a bare number of
    milliseconds): it keys — and SLO-constrains — the auto plan (see
    ``autotune.autotune``); explicit methods ignore it (the caller
    already chose the engine).

    ``bucket`` names the shape-bucketing policy the auto plan is keyed
    under (``repro.core.autotune.bucket_cap`` — default pow-2 caps;
    ``'geom'`` for the paper-geometry m²-aligned caps; ``None`` opts
    out to exact-n keys).  One plan tuned at the bucket cap serves
    every shape in the bucket; explicit methods ignore it.

    An eager call counts once in ``dispatch.calls`` and
    ``dispatch.bytes`` under ``(op, engine)``, the engine that served
    it, and while a JAX profile records it is span ``repro.dispatch``
    (the registry's routing and the engine run) with child span
    ``repro.engine`` (the engine run; ``repro.obs``).  Under a jit
    trace a host span would time tracing and a count would count
    traces, so neither is recorded there: the engine runs inside
    ``jax.named_scope("<op>.<engine>")`` instead, so the compiled ops
    say which registry op they came from.
    """
    if isinstance(x, jax.core.Tracer):
        eng, x, plan, op_kwargs = _route(op, x, method, chain, precision,
                                         objective, bucket, op_kwargs)
        with jax.named_scope(f"{op}.{eng.name}"):
            return eng.run(x, plan, **op_kwargs)
    with obs.span("repro.dispatch") as sp:
        eng, x, plan, op_kwargs = _route(op, x, method, chain, precision,
                                         objective, bucket, op_kwargs)
        key, nbytes = (op, eng.name), int(x.size) * x.dtype.itemsize
        obs.count("dispatch.calls", key)
        obs.count("dispatch.bytes", key, nbytes)
        sp.set(op=op, method=method, engine=eng.name, n=x.size,
               bytes=nbytes)
        with obs.span("repro.engine", engine=eng.name):
            return eng.run(x, plan, **op_kwargs)


def _route(op: str, x, method: str, chain, precision, objective,
           bucket, op_kwargs: dict) -> tuple:
    """The registry's decision for one ``dispatch`` call:
    ``(engine, input, plan, op_kwargs)``, with the input cast as the
    policy asks and the engine checked against it."""
    from repro.core import autotune
    spec = op_spec(op)
    policy = as_policy(precision)
    ctx = _context_for(spec, x, op_kwargs, policy=policy)
    if policy is not None:
        op_kwargs = dict(op_kwargs, policy=policy)
    if method == "auto":
        legal = legal_engines(spec, ctx)
        if not legal:
            raise ValueError(f"no engine of op {op!r} supports this "
                             f"input: shape={ctx.shape}")
        # The engine tag marks restrictions *beyond* what the policy
        # itself prunes from the sweep (``autotune.candidate_plans``
        # applies ``_policy_reason`` too, and the policy is already in
        # the key via ``|prec:``) — so a policy that merely gates the
        # engine family (f32 vs the dd family) resolves under the
        # untagged key, while mesh/axis/shape restrictions still tag.
        sweepable = tuple(e.name for e in spec.engines
                          if _policy_reason(e, policy) is None)
        restrict = None if legal == sweepable else legal
        plan = autotune.get_plan(spec.problem_size(x, op_kwargs),
                                 x.dtype, op=op, engine=restrict,
                                 mesh=ctx.mesh_axes, policy=policy,
                                 objective=objective, bucket=bucket)
        x = _cast_in(x, policy, spec, plan.method)
        eng = _plan_engine(spec, x, plan, op_kwargs)
        return eng, x, plan, op_kwargs
    eng = spec.engine(method)
    if eng is None:
        raise _unknown_method(spec, method)
    reason = capability_reason(eng, ctx)
    if reason is not None:
        raise ValueError(
            f"engine {eng.name!r} cannot run op {op!r} here: {reason}")
    x = _cast_in(x, policy, spec, eng.name)
    if chain == "auto":
        plan = autotune.get_plan(spec.problem_size(x, op_kwargs),
                                 x.dtype, op=op, engine=(eng.name,),
                                 mesh=ctx.mesh_axes, policy=policy,
                                 objective=objective, bucket=bucket)
        eng = _plan_engine(spec, x, plan, op_kwargs)
        return eng, x, plan, op_kwargs
    overrides = {} if chain is None else {"chain": int(chain)}
    overrides.update(_plan_words(policy))
    plan = autotune.ReductionPlan(method=eng.name, **overrides)
    return eng, x, plan, op_kwargs


def _cast_in(x, policy: Optional[MmaPolicy], spec: "OpSpec",
             engine_name: str):
    """Apply the policy's multiplicand cast for the plain engines.

    The ``mma_ec`` family performs its own split-bf16 decomposition of
    the full-precision input, so casting first would destroy exactly
    the bits the split exists to preserve — split-capable engines are
    exempt."""
    if policy is None or policy.input_dtype is None:
        return x
    eng = spec.engine(engine_name)
    if eng is not None and eng.max_split_words > 1:
        return x
    return policy.cast_in(x)


def execute(op: str, x, plan, **op_kwargs):
    """Run ``x`` under an already-chosen plan — the single executor.

    The autotuner's measured sweep, the mesh collectives and the
    benchmark drivers land here.  The plan's engine is validated
    against the op's structural capabilities (axis/layout/ndim — not
    the mesh, so candidate plans can be timed on a single host).  It
    counts and traces nothing: a sweep's candidate runs are not calls
    the registry served.
    """
    spec = op_spec(op)
    return _plan_engine(spec, x, plan, op_kwargs).run(x, plan, **op_kwargs)


def _plan_engine(spec: OpSpec, x, plan, op_kwargs: dict) -> EngineSpec:
    """The plan's engine, checked against the op's structural
    capabilities for ``x``."""
    eng = spec.engine(plan.method)
    if eng is None:
        raise ValueError(f"unknown plan method {plan.method!r} for op "
                         f"{spec.name!r} (engines: "
                         f"{spec.engine_names()})")
    reason = capability_reason(eng, _context_for(spec, x, op_kwargs),
                               env=False)
    if reason is not None:
        raise ValueError(f"engine {eng.name!r} cannot run op "
                         f"{spec.name!r} here: {reason}")
    return eng


def _context_for(spec: OpSpec, x, op_kwargs: dict, *,
                 policy: Optional[MmaPolicy] = None) -> DispatchContext:
    if policy is None:
        policy = op_kwargs.get("policy")
    if spec.family == "scan":
        axis = op_kwargs.get("axis", -1)
        scan_axis = axis % max(x.ndim, 1)
        return build_context(spec.name, x, scan_axis=scan_axis,
                             policy=policy)
    if spec.family == "attention":
        return build_context(spec.name, x, policy=policy,
                             extras=_attention_extras(x, op_kwargs))
    if spec.family == "norm_matmul":
        return build_context(spec.name, x, policy=policy,
                             extras=_norm_matmul_extras(x, op_kwargs))
    return build_context(spec.name, x, axis=op_kwargs.get("axis"),
                         policy=policy)


def _attention_extras(qg, op_kwargs: dict) -> tuple:
    """The attention family's static context facts.

    Everything recorded here is trace-time shape/flag information —
    never an operand array — so the context stays hashable and the
    predicates stay jit-safe.  ``has_kv_len`` is True only for a
    *dynamic* valid-length mask (the decode ring-buffer case); a static
    ``kv_len == Sk`` is the dense no-op every engine handles.
    """
    k = op_kwargs.get("k")
    v = op_kwargs.get("v")
    qpos = op_kwargs.get("qpos")
    kv_len = op_kwargs.get("kv_len")
    window = op_kwargs.get("window")
    kv_seq = int(k.shape[1]) if k is not None else 0
    return (
        ("causal", bool(op_kwargs.get("causal", False))),
        ("window", int(window) if window is not None else None),
        ("has_kv_len",
         kv_len is not None
         and not (isinstance(kv_len, int) and kv_len == kv_seq)),
        ("per_row", qpos is not None and getattr(qpos, "ndim", 1) == 2),
        ("head_dim", int(qg.shape[-1])),
        ("v_head_dim",
         int(v.shape[-1]) if v is not None else int(qg.shape[-1])),
        ("kv_seq", kv_seq),
    )


def _norm_matmul_extras(x, op_kwargs: dict) -> tuple:
    """The norm_matmul family's static context facts (trace-time
    shape/flag information only, so the context stays hashable)."""
    w = op_kwargs.get("w")
    return (
        ("d_model", int(x.shape[-1])),
        ("d_out", int(w.shape[-1]) if w is not None else 0),
        ("has_gate", op_kwargs.get("w_gate") is not None),
        ("has_bias", op_kwargs.get("bias") is not None),
    )


# ===================================================== engine runners
#
# Lazy imports throughout: the registry must import without pulling the
# Pallas kernels (or the scan core) until an engine actually runs.


def _f32(x):
    return x.astype(jnp.float32)


# ---- reduce family


def _reduce_mma(x, plan, *, axis=None, **_):
    from repro.core import reduction as R
    return R.tc_sum(x, axis)


def _reduce_chained(x, plan, **_):
    from repro.core import reduction as R
    return R.tc_reduce(x, variant=plan.variant, chain=plan.chain,
                       m=plan.m, mma_fraction=plan.mma_fraction)


def _reduce_pallas(x, plan, **_):
    from repro.kernels import mma_reduce
    return mma_reduce(x, variant=plan.variant, chain=plan.chain,
                      block_rows=plan.block_rows)


def _reduce_vpu(x, plan, *, axis=None, **_):
    return jnp.sum(_f32(x), axis=axis)


def _reduce_ec(x, plan, **_):
    from repro.core import reduction as R
    return R.tc_reduce_ec(x, split_words=plan.split_words,
                          chain=plan.chain, m=plan.m)


def _reduce_pallas_ec(x, plan, **_):
    from repro.kernels import mma_ec_reduce
    return mma_ec_reduce(x, split_words=plan.split_words,
                         chain=plan.chain, block_rows=plan.block_rows)


def _sq_mma(x, plan, *, axis=None, **_):
    from repro.core import reduction as R
    return R.tc_squared_sum(x, axis)


def _sq_chained(x, plan, **_):
    xf = _f32(x)
    return _reduce_chained(xf * xf, plan)


def _sq_pallas(x, plan, **_):
    from repro.kernels import mma_squared_sum
    return mma_squared_sum(x, chain=plan.chain,
                           block_rows=plan.block_rows)


def _sq_vpu(x, plan, *, axis=None, **_):
    xf = _f32(x)
    return jnp.sum(xf * xf, axis=axis)


def _sq_ec(x, plan, **_):
    # Square in f32 on the VPU, then compensated split-bf16 reduce —
    # the squaring rounds once per element (same as every engine); the
    # accumulation contributes no first-order error.
    from repro.core import reduction as R
    xf = _f32(x)
    return R.tc_reduce_ec(xf * xf, split_words=plan.split_words,
                          chain=plan.chain, m=plan.m)


def _sq_pallas_ec(x, plan, **_):
    from repro.kernels import mma_ec_squared_sum
    return mma_ec_squared_sum(x, split_words=plan.split_words,
                              chain=plan.chain,
                              block_rows=plan.block_rows)


def _reduce_dd(x, plan, **_):
    from repro.core import reduction as R
    return R.tc_reduce_dd(x)


def _reduce_pallas_dd(x, plan, **_):
    from repro.kernels import mma_dd_reduce
    return mma_dd_reduce(x, chain=plan.chain,
                         block_rows=plan.block_rows)


def _sq_dd(x, plan, **_):
    from repro.core import reduction as R
    return R.tc_reduce_dd(x, square=True)


def _sq_pallas_dd(x, plan, **_):
    from repro.kernels import mma_dd_squared_sum
    return mma_dd_squared_sum(x, chain=plan.chain,
                              block_rows=plan.block_rows)


def _masked_mean_with(reduce_run):
    """Lift one reduce engine into the masked-mean op: numerator and
    denominator both ride that engine; the all-masked denominator is
    floored at 1 (so an empty mask yields 0, not NaN)."""
    def run(values, plan, *, mask, **_):
        num = reduce_run(values * mask, plan)
        den = reduce_run(mask, plan)
        return num / jnp.maximum(den, 1.0)
    return run


def _masked_mean_mma(values, plan, *, mask, **_):
    # Fused form: the mask itself plays the ones-matrix role, so the
    # numerator is a *single* contraction <values, mask>.
    from repro.core import reduction as R
    num = R.tc_contract(values, mask)
    den = R.tc_contract(mask, jnp.ones_like(mask))
    return num / jnp.maximum(den, 1.0)


def _counts_mma(x, plan, **_):
    from repro.core import reduction as R
    return R.tc_reduce_rows(x.T)            # (E,) f32


def _counts_vpu(x, plan, **_):
    return jnp.sum(_f32(x), axis=0)


# ---- scan family


def _scan_chained(x, plan, *, axis=-1, inclusive=True, policy=None,
                  **_):
    from repro.core import scan as S
    lax_prec = None if policy is None else policy.lax_precision()
    return S.tc_scan(x, axis=axis, inclusive=inclusive,
                     variant=plan.variant, chain=plan.chain, m=plan.m,
                     precision=lax_prec)


def _scan_ec(x, plan, *, axis=-1, inclusive=True, **_):
    from repro.core import scan as S
    return S.tc_scan_ec(x, axis=axis, inclusive=inclusive,
                        split_words=plan.split_words,
                        chain=plan.chain, m=plan.m)


def _scan_pallas(x, plan, *, inclusive=True, **_):
    from repro.kernels import mma_scan
    return mma_scan(x, inclusive=inclusive, chain=plan.chain,
                    block_rows=plan.block_rows)


def _scan_vpu(x, plan, *, axis=-1, inclusive=True, **_):
    from repro.core import scan as S
    out = jnp.cumsum(_f32(x), axis=axis)
    if not inclusive:
        out = jnp.moveaxis(
            S._shift_exclusive(jnp.moveaxis(out, axis, -1)), -1, axis)
    return out


# ---- segment family


def _segment_mma(values, plan, *, segment_ids, num_segments, **_):
    from repro.core import scan as S
    return S.tc_segment_reduce(values, segment_ids, num_segments,
                               m=plan.m)


def _segment_pallas(values, plan, *, segment_ids, num_segments, **_):
    from repro.kernels import mma_segment_sum
    return mma_segment_sum(values, segment_ids, num_segments,
                           block_rows=plan.block_rows)


def _segment_vpu(values, plan, *, segment_ids, num_segments, **_):
    import jax.ops
    return jax.ops.segment_sum(
        jnp.ravel(_f32(values)), jnp.ravel(segment_ids),
        num_segments=num_segments)


# ---- attention family
#
# Operand surface (every runner): qg (B, Sq, KV, G, hd) grouped
# queries; k (B, Sk, KV, hd); v (B, Sk, KV, hd_v — MLA's value width
# may differ); qpos (Sq,) or per-row (B, Sq) absolute positions;
# key positions are always 0..Sk-1 (the ring-buffer slot order).
# Returns (B, Sq, KV, G, hd_v) in v.dtype.


def _attn_scale(qg, scale):
    return 1.0 / math.sqrt(qg.shape[-1]) if scale is None else scale


def _attn_vpu(qg, plan, *, k, v, qpos, causal=False, window=None,
              kv_len=None, scale=None, cap=None, **_):
    from repro.models.attention import _direct_attn
    kpos = jnp.arange(k.shape[1], dtype=jnp.int32)
    return _direct_attn(qg, k, v, qpos=qpos, kpos=kpos, causal=causal,
                        window=window, kv_len=kv_len,
                        scale=_attn_scale(qg, scale), cap=cap)


def _attn_unfused(qg, plan, *, k, v, qpos, causal=False, window=None,
                  kv_len=None, scale=None, cap=None, chunk=None, **_):
    # kv_len is None or statically the full Sk here (the capability
    # predicate refuses the dynamic ring-buffer form), so the dense
    # chunked scan's built-in kv_len == Sk bound is exact.
    from repro.models.attention import _chunked_attn
    chunk = int(chunk) if chunk else plan.chain * plan.block_rows
    return _chunked_attn(qg, k, v, qpos=qpos, causal=causal,
                         window=window, scale=_attn_scale(qg, scale),
                         cap=cap, chunk=chunk)


def _attn_fused(qg, plan, *, k, v, qpos, causal=False, window=None,
                kv_len=None, scale=None, cap=None, **_):
    from repro.kernels import mma_attention
    return mma_attention(qg, k, v, qpos=qpos, causal=causal,
                         window=window, kv_len=kv_len,
                         scale=_attn_scale(qg, scale), cap=cap,
                         chain=plan.chain, block_rows=plan.block_rows)


# The fused kernel tiles one (padded) head dim across VMEM lanes; past
# this width the f32 working set (scores + accumulator + row stats,
# double-buffered) no longer fits the 16 MB budget.
_FUSED_MAX_HEAD = 512


def _attn_fused_predicate(ctx: DispatchContext) -> Optional[str]:
    pad = max(int(ctx.extra("head_dim", 0)),
              int(ctx.extra("v_head_dim", 0)))
    pad = -(-max(pad, 1) // 128) * 128
    if pad > _FUSED_MAX_HEAD:
        return (f"padded head dim {pad} exceeds the fused kernel's "
                f"{_FUSED_MAX_HEAD}-lane VMEM head tiling; use the "
                f"unfused engines")
    return None


def _attn_unfused_predicate(ctx: DispatchContext) -> Optional[str]:
    if ctx.extra("has_kv_len"):
        return ("dense-prefill engine: the KV-chunked scan has no "
                "dynamic valid-length (ring-buffer kv_len) mask; "
                "decode needs the fused kernel or the vpu oracle")
    return None


# ---- norm_matmul family: rmsnorm(x) @ W without the HBM round trip
#
# Op surface (all engines): x (..., d), scale (d,) with gemma
# (1 + scale) weighting, w (d, dout) or None for the norm-only form
# (output = normalized activations — the legacy kernels/mma_rmsnorm.py
# path folded behind the registry), optional bias (dout,), optional
# w_gate (d, dout) + act for the MLP up/gate pair
# act(xh @ w_gate) * (xh @ w [+ bias]).  Output in x.dtype.


def _nm_apply_act(g, act):
    if act is None:
        return g
    if act == "silu":
        return jax.nn.silu(g)
    if act == "gelu":
        return jax.nn.gelu(g, approximate=True)
    raise ValueError(f"unknown norm_matmul act: {act!r}")


def _nm_weight(w, policy):
    # policy.cast_in on the WEIGHT operand: the dispatch-level _cast_in
    # already handles x, but the weight never passes through it.
    return w if policy is None else policy.cast_in(w)


def _nm_vpu(x, plan, *, w, scale, w_gate=None, bias=None, act=None,
            eps=1e-6, policy=None, **_):
    xf = _f32(x)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    xh = xf * rstd * (1.0 + _f32(jnp.asarray(scale)))
    if w is None:
        return xh.astype(x.dtype)
    up = xh @ _f32(_nm_weight(w, policy))
    if bias is not None:
        up = up + _f32(jnp.asarray(bias))
    if w_gate is not None:
        g = xh @ _f32(_nm_weight(w_gate, policy))
        up = _nm_apply_act(g, act) * up
    return up.astype(x.dtype)


def _nm_unfused(x, plan, *, w, scale, w_gate=None, bias=None, act=None,
                eps=1e-6, policy=None, **_):
    # Today's two-op path, spelled to stay BIT-identical to
    # layers.rmsnorm(method='mma') followed by the layers.mlp-style
    # matmul in x.dtype: same reduction primitive (tc_reduce_axes on
    # the last dim), same multiply association, same casts.
    from repro.core import reduction as R
    xf = _f32(x)
    ms = R.tc_reduce_axes(xf * xf, (x.ndim - 1,))[..., None] \
        / x.shape[-1]
    rstd = jax.lax.rsqrt(ms + eps)
    xh = (xf * rstd * (1.0 + _f32(jnp.asarray(scale)))).astype(x.dtype)
    if w is None:
        return xh
    up = xh @ _nm_weight(w, policy).astype(x.dtype)
    if bias is not None:
        up = up + jnp.asarray(bias).astype(x.dtype)
    if w_gate is not None:
        g = xh @ _nm_weight(w_gate, policy).astype(x.dtype)
        up = _nm_apply_act(g, act) * up
    return up


def _nm_fused(x, plan, *, w, scale, w_gate=None, bias=None, act=None,
              eps=1e-6, policy=None, **_):
    if w is None:
        # Norm-only spelling: the original fused rmsnorm kernel, now
        # reachable only through this registry entry.
        from repro.kernels import mma_rmsnorm
        return mma_rmsnorm(x, jnp.asarray(scale), eps=eps,
                           weight_offset=1.0)
    from repro.kernels import mma_norm_matmul
    wg = None if w_gate is None else _nm_weight(w_gate, policy)
    return mma_norm_matmul(x, scale, _nm_weight(w, policy), w_gate=wg,
                           bias=bias, act=act, eps=eps,
                           chain=plan.chain,
                           block_rows=plan.block_rows)


# The fused kernel tiles d and dout, so VMEM does not bound d_model;
# the engine still serves only models up to this padded width until a
# chip measurement shows it pays at published widths.
_NM_FUSED_MAX_D = 512


def _nm_fused_predicate(ctx: DispatchContext) -> Optional[str]:
    pad = -(-max(int(ctx.extra("d_model", 0)), 1) // 128) * 128
    if pad > _NM_FUSED_MAX_D:
        return (f"padded d_model {pad} exceeds the fused norm->matmul "
                f"engine's {_NM_FUSED_MAX_D}-lane limit; use the unfused "
                f"engines")
    return None


# ================================================= reference oracles
#
# The classic baseline IS each op's semantic reference (the paper
# compares against it, and its engine runner is already pure jnp), so
# the oracles are the vpu runners with the plan argument dropped — one
# definition, no copy to drift out of sync.


def _ref_reduce_sum(x, **kw):
    return _reduce_vpu(x, None, **kw)


def _ref_squared_sum(x, **kw):
    return _sq_vpu(x, None, **kw)


def _ref_masked_mean(values, *, mask, **_):
    vm = _f32(values) * _f32(mask)
    return jnp.sum(vm) / jnp.maximum(jnp.sum(_f32(mask)), 1.0)


def _ref_expert_counts(x, **kw):
    return _counts_vpu(x, None, **kw)


def _ref_scan(x, **kw):
    return _scan_vpu(x, None, **kw)


def _ref_segment_sum(values, **kw):
    return _segment_vpu(values, None, **kw)


def _ref_attention(qg, **kw):
    kw.pop("chunk", None)
    return _attn_vpu(qg, None, **kw)


def _ref_norm_matmul(x, **kw):
    return _nm_vpu(x, None, **kw)


# ----------------------------------------------- measurement inputs
#
# Ops whose runners need more than one 1D operand declare how the
# autotuner's measured sweep builds a representative problem of size n.


def _measure_masked_mean(n, dtype, rng):
    x = jnp.asarray(rng.standard_normal(n), dtype=jnp.float32)
    mask = jnp.asarray(rng.random(n) > 0.5, dtype=jnp.float32)
    return x.astype(dtype), {"mask": mask.astype(dtype)}


def _measure_expert_counts(n, dtype, rng):
    e = 128                                   # one MXU lane tile
    t = max(n // e, 1)
    onehot = jnp.eye(e, dtype=jnp.float32)[
        jnp.asarray(rng.integers(0, e, t))]
    return onehot.astype(dtype), {}


def _measure_attention(n, dtype, rng):
    # A representative causal self-attention problem with ~n score
    # elements (Sq == Sk == sqrt(n)): B = KV = G = 1 is enough — every
    # engine batches the leading dims trivially.
    hd = 64
    s = max(int(math.isqrt(max(int(n), 1))), 8)
    qg = jnp.asarray(rng.standard_normal((1, s, 1, 1, hd)),
                     dtype=jnp.float32).astype(dtype)
    k = jnp.asarray(rng.standard_normal((1, s, 1, hd)),
                    dtype=jnp.float32).astype(dtype)
    v = jnp.asarray(rng.standard_normal((1, s, 1, hd)),
                    dtype=jnp.float32).astype(dtype)
    return qg, {"k": k, "v": v,
                "qpos": jnp.arange(s, dtype=jnp.int32),
                "causal": True, "scale": 1.0 / math.sqrt(hd)}


def _measure_norm_matmul(n, dtype, rng):
    # A representative rmsnorm -> square projection with ~n input
    # elements (rows = n / d at one k-block of d = 128).
    d = 128
    rows = max(int(n) // d, 1)
    x = jnp.asarray(rng.standard_normal((rows, d)),
                    dtype=jnp.float32).astype(dtype)
    w = jnp.asarray(rng.standard_normal((d, d)) / math.sqrt(d),
                    dtype=jnp.float32).astype(dtype)
    scale = jnp.asarray(0.1 * rng.standard_normal(d),
                        dtype=jnp.float32)
    return x, {"w": w, "scale": scale}


def _attention_cost(plan, n, dtype):
    """Analytical score for the attention engines, in the autotuner's
    model units (``n`` = score elements B*Sq*KV*G*Sk).

    Every engine pays the same two MXU contractions per score element
    (QK^T and PV); they differ in VPU passes over the score matrix and
    grid overhead: the oracle materialises scores + a full softmax
    (~5 passes + the HBM round-trip), the KV-chunked scan streams with
    ~3 passes per chunk, and the fused kernel keeps the row statistics
    in registers — one exp pass plus a max/sum fold that amortises
    with the MMA chain, which is the whole point of the fusion
    (ROADMAP open item 1).
    """
    from repro.core import autotune as at
    n = max(int(n), 1)
    par = at._PARALLELISM
    mma = 2.0 * n / (at._MXU_THROUGHPUT * par)
    vpass = n / (at._VPU_THROUGHPUT * par)
    mem = n * jnp.dtype(dtype).itemsize / (4.0 * at._VPU_THROUGHPUT)
    tile = max(plan.block_rows * plan.m, 1)
    if plan.method == "vpu":
        return mma + 5.0 * vpass + mem
    if plan.method == "unfused_mma":
        steps = max(math.ceil(n / tile), 1)
        return mma + 3.0 * vpass \
            + at._GRID_STEP_OVERHEAD * steps / par
    # fused_pallas
    steps = max(math.ceil(n / (max(plan.chain, 1) * tile)), 1)
    return mma + (1.0 + 1.0 / max(plan.chain, 1)) * vpass \
        + at._GRID_STEP_OVERHEAD * steps / par


# ==================================================== registrations
#
# Engine capability summary (the table docs/ARCHITECTURE.md renders):
#   mma          geometry-free single contraction — distribution-safe,
#                axis-aware (batched) for the reduce family.
#   mma_chained  pure-JAX chained core.  Flatten-and-pad for reductions
#                (single-device only, no axis subsets); reshapes ONLY
#                the scan axis for scans (distribution-safe, batched).
#   mma_ec       compensated split-bf16 chains (pure JAX): 2-3 bf16
#                words per f32 multiplicand, TwoSum-combined f32
#                partials.  Single-device, flatten-only (reduce) /
#                scan-axis-only (scan); the only family honouring
#                policy split_words > 1.
#   pallas       hand-tiled kernel: single-device, flatten-only.
#   pallas_ec    hand-tiled twin of mma_ec (Kahan VMEM accumulators).
#   mma_dd       double-double family (pure JAX): every partial an
#                unevaluated (hi, lo) f32 pair via TwoSum/TwoProd,
#                pair-granular ones-MMAs — f64-equivalent shape-(2,)
#                result.  Declares accum_dtypes=('float64',): refused
#                without an explicit f64 policy (and refuses f32
#                policies with the reason).  Single-device,
#                flatten-only.
#   pallas_dd    hand-tiled twin of mma_dd (per-word TwoSum VMEM
#                accumulator rows, (2, 1) output).
#   vpu          classic baseline: safe everywhere.

_REDUCE_ENGINES = (
    EngineSpec("mma", _reduce_mma, multi_device_safe=True,
               axis_subsets=True),
    EngineSpec("mma_chained", _reduce_chained, sweep=("chain",)),
    EngineSpec("mma_ec", _reduce_ec, max_split_words=3,
               sweep=("chain", "split_words")),
    EngineSpec("pallas", _reduce_pallas, sweep=("chain", "block_rows")),
    EngineSpec("pallas_ec", _reduce_pallas_ec, max_split_words=3,
               sweep=("chain", "block_rows", "split_words")),
    EngineSpec("mma_dd", _reduce_dd, max_split_words=2,
               accum_dtypes=("float64",)),
    EngineSpec("pallas_dd", _reduce_pallas_dd, max_split_words=2,
               accum_dtypes=("float64",),
               sweep=("chain", "block_rows")),
    EngineSpec("vpu", _reduce_vpu, multi_device_safe=True,
               axis_subsets=True),
)

register(OpSpec(
    name="reduce_sum", family="reduce", engines=_REDUCE_ENGINES,
    reference=_ref_reduce_sum))

register(OpSpec(
    name="squared_sum", family="reduce",
    engines=(
        EngineSpec("mma", _sq_mma, multi_device_safe=True,
                   axis_subsets=True),
        EngineSpec("mma_chained", _sq_chained, sweep=("chain",)),
        EngineSpec("mma_ec", _sq_ec, max_split_words=3,
                   sweep=("chain", "split_words")),
        EngineSpec("pallas", _sq_pallas, sweep=("chain", "block_rows")),
        EngineSpec("pallas_ec", _sq_pallas_ec, max_split_words=3,
                   sweep=("chain", "block_rows", "split_words")),
        EngineSpec("mma_dd", _sq_dd, max_split_words=2,
                   accum_dtypes=("float64",)),
        EngineSpec("pallas_dd", _sq_pallas_dd, max_split_words=2,
                   accum_dtypes=("float64",),
                   sweep=("chain", "block_rows")),
        EngineSpec("vpu", _sq_vpu, multi_device_safe=True,
                   axis_subsets=True),
    ),
    reference=_ref_squared_sum))

register(OpSpec(
    name="masked_mean", family="reduce",
    engines=(
        EngineSpec("mma", _masked_mean_mma, multi_device_safe=True),
        EngineSpec("mma_chained", _masked_mean_with(_reduce_chained),
                   sweep=("chain",)),
        EngineSpec("pallas", _masked_mean_with(_reduce_pallas),
                   sweep=("chain", "block_rows")),
        EngineSpec("vpu", _masked_mean_with(_reduce_vpu),
                   multi_device_safe=True),
    ),
    reference=_ref_masked_mean, measure=_measure_masked_mean))

register(OpSpec(
    name="expert_counts", family="reduce",
    engines=(
        EngineSpec("mma", _counts_mma, multi_device_safe=True, ndim=2),
        EngineSpec("vpu", _counts_vpu, multi_device_safe=True, ndim=2),
    ),
    reference=_ref_expert_counts, measure=_measure_expert_counts))

_SCAN_ENGINES = (
    EngineSpec("mma_chained", _scan_chained, multi_device_safe=True,
               sweep=("chain",)),
    EngineSpec("mma_ec", _scan_ec, max_split_words=3,
               sweep=("chain", "split_words")),
    EngineSpec("pallas", _scan_pallas, needs_flat=True,
               sweep=("chain", "block_rows")),
    EngineSpec("vpu", _scan_vpu, multi_device_safe=True),
)

register(OpSpec(
    name="scan", family="scan", engines=_SCAN_ENGINES,
    aliases={"mma": "mma_chained"}, reference=_ref_scan,
    size_of=lambda x, kw: x.shape[kw.get("axis", -1)]))

register(OpSpec(
    name="masked_cumsum", family="scan", engines=_SCAN_ENGINES,
    aliases={"mma": "mma_chained"}, reference=_ref_scan,
    size_of=lambda x, kw: x.shape[kw.get("axis", -1)]))

register(OpSpec(
    name="segment_sum", family="segment",
    engines=(
        EngineSpec("mma", _segment_mma, multi_device_safe=True),
        EngineSpec("pallas", _segment_pallas,
                   sweep=("block_rows",)),
        EngineSpec("vpu", _segment_vpu, multi_device_safe=True),
    ),
    aliases={"mma_chained": "mma"}, reference=_ref_segment_sum))

# Attention engine capability summary:
#   fused_pallas  flash-style Pallas kernel (kernels/mma_attention.py):
#                 online-softmax row stats in-kernel via chained-MMA
#                 max/sum folds with Kahan-carried f32 normalisers.
#                 Handles causal/window/GQA, per-row decode positions
#                 and the ring-buffer kv_len mask; head dims tile up to
#                 _FUSED_MAX_HEAD lanes; f32/bf16 inputs only.
#   unfused_mma   today's KV-chunked online-softmax scan
#                 (models/attention._chunked_attn): dense prefill only
#                 (no dynamic kv_len), any dtype, distribution-safe.
#   vpu           the unchunked oracle (models/attention._direct_attn):
#                 safe everywhere; materialises the score matrix.

_ATTENTION_ENGINES = (
    EngineSpec("fused_pallas", _attn_fused, ndim=5,
               dtypes=("float32", "bfloat16"),
               sweep=("chain", "block_rows"),
               predicate=_attn_fused_predicate),
    EngineSpec("unfused_mma", _attn_unfused, ndim=5,
               multi_device_safe=True, sweep=("block_rows",),
               predicate=_attn_unfused_predicate),
    EngineSpec("vpu", _attn_vpu, ndim=5, multi_device_safe=True),
)

register(OpSpec(
    name="attention", family="attention", engines=_ATTENTION_ENGINES,
    aliases={"pallas": "fused_pallas", "mma": "unfused_mma"},
    reference=_ref_attention,
    # plan keys bucket on score elements, so prefill (Sq*Sk) and
    # decode (1*Sk) land in different n-buckets and resolve distinct
    # plans under one SLO — the PR-6 latency-objective contract.
    size_of=lambda qg, kw: (qg.shape[0] * qg.shape[1] * qg.shape[2]
                            * qg.shape[3] * kw["k"].shape[1]),
    cost=_attention_cost, measure=_measure_attention))


def _norm_matmul_cost(plan, n, dtype):
    """Analytical score for the norm_matmul engines, in the
    autotuner's model units (``n`` = input elements rows * d).

    Every engine pays the same MXU contractions (the projection plus
    the statistic's ones-MMA); they differ in VPU passes and — the
    point of the fusion — HBM traffic and launches: the two-op paths
    round-trip the normalized activations through HBM between two
    kernel launches (2x mem + 2 launches), while the fused kernel
    reads x once, keeps the row statistic and the matmul partial in
    VMEM, and pays one launch per grid step.  At decode sizes
    (rows = num_slots, S = 1) the launch + round-trip terms dominate,
    which is exactly where the fused plan must win (ROADMAP item 1).
    """
    from repro.core import autotune as at
    n = max(int(n), 1)
    par = at._PARALLELISM
    mma = 8.0 * n / (at._MXU_THROUGHPUT * par)
    vpass = n / (at._VPU_THROUGHPUT * par)
    mem = n * jnp.dtype(dtype).itemsize / (4.0 * at._VPU_THROUGHPUT)
    launch = at._GRID_STEP_OVERHEAD / par
    if plan.method == "vpu":
        return mma + 5.0 * vpass + 2.0 * mem + 2.0 * launch
    if plan.method == "unfused_mma":
        return mma + 2.0 * vpass + 2.0 * mem + 2.0 * launch
    # fused_pallas: one read of x, no intermediate HBM round trip
    tile = max(plan.chain * plan.block_rows * plan.m, 1)
    steps = max(math.ceil(n / (max(plan.chain, 1) * tile)), 1)
    return mma + (1.0 + 1.0 / max(plan.chain, 1)) * vpass + mem \
        + launch * steps


# norm_matmul engine capability summary:
#   fused_pallas  kernels/mma_norm_matmul.py: one k-walk accumulates
#                 the chained ones-MMA sum of squares (Kahan carry)
#                 AND the unnormalized matmul partials in VMEM; the
#                 normalized activations never reach HBM.  d_model
#                 pads up to _NM_FUSED_MAX_D lanes; f32/bf16 only.
#   unfused_mma   today's two-op path (rmsnorm statistic via
#                 tc_reduce_axes + XLA matmul in x.dtype) — the
#                 current-behavior reference, distribution-safe.
#   vpu           classic all-f32 baseline: safe everywhere.

_NORM_MATMUL_ENGINES = (
    EngineSpec("fused_pallas", _nm_fused,
               dtypes=("float32", "bfloat16"),
               sweep=("chain", "block_rows"),
               predicate=_nm_fused_predicate),
    EngineSpec("unfused_mma", _nm_unfused, multi_device_safe=True),
    EngineSpec("vpu", _nm_vpu, multi_device_safe=True),
)

register(OpSpec(
    name="norm_matmul", family="norm_matmul",
    engines=_NORM_MATMUL_ENGINES,
    aliases={"pallas": "fused_pallas", "mma": "unfused_mma"},
    reference=_ref_norm_matmul,
    # default size_of (x.size = rows * d): decode (num_slots rows) and
    # prefill (B * S rows) land in different n-buckets and resolve
    # distinct plans under one SLO, as with the attention op.
    cost=_norm_matmul_cost, measure=_measure_norm_matmul,
    # The unfused statistic runs on the f32 reduce engines and the
    # matmul in x.dtype — full f32 multiplicand bits, unlike the
    # bf16-multiplicand default the autotuner assumes for MMA engines.
    engine_bits={"unfused_mma": 24}))

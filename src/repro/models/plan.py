"""Frozen serving plans (DESIGN.md §10).

A :class:`ModelPlan` is the once-per-model resolution of everything the
serving path would otherwise redo on every call: each launch's tiles
(``kernels/core.py``'s rule, frozen per layer), epilogue wiring, and the
compressed/quantized weight buffers themselves. Each layer's serving
step is staged into a closure with its parameters *frozen in*, and the
whole chain is jit-compiled once — weights become trace-time constants,
so XLA folds the per-call weight relayout (reshape / index expand /
dtype cast) at compile time and steady-state serving is a single
dispatch with zero per-call tile resolution, re-layout, or retracing.

Plans are immutable (frozen dataclasses) and *pinned to the exact
parameters they were built from*: :func:`params_fingerprint` hashes every
leaf (shapes, dtypes, bytes) plus the tree structure, and
``SparseCNN.apply(params, x, plan=plan)`` raises :class:`StalePlanError`
when the fingerprint no longer matches — e.g. after a re-``quantize()``
with fresh calibration. The hot path (``plan.serve(x)`` / ``plan(x)``)
skips the check; the checked ``apply(..., plan=)`` form is for callers
that still carry params and want the safety net.

A :class:`PlanSet` (DESIGN.md §11) lifts one plan to a serving *bucket
ladder*: each batch-size bucket maps to its own pre-compiled plan, and
``serve(x)`` pads any ragged batch up to the nearest bucket, dispatches
that bucket's frozen plan, and slices the padding back off — so variable
load never retraces and padded serving stays bit-identical to
per-request serving (batch rows are independent through conv/GEMM/GAP;
zero rows contribute nothing to anyone else's output). Every plan counts
its (re)traces, which is what lets the serving tier *prove* the
zero-retrace-after-warmup contract rather than assume it.
"""
from __future__ import annotations

import dataclasses
import hashlib
from types import MappingProxyType
from typing import Any, Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


class StalePlanError(RuntimeError):
    """A frozen plan was used with params it was not built from."""


def params_fingerprint(params) -> str:
    """Content hash of a param tree: tree structure (incl. static aux data
    like ``DBBFormat``), every leaf's shape/dtype, and its bytes. Computed
    once at plan build; any later re-quantize / re-compress / re-calibrate
    changes it."""
    h = hashlib.sha1()
    leaves, treedef = jax.tree_util.tree_flatten(params)
    h.update(repr(treedef).encode())
    for leaf in leaves:
        arr = np.asarray(leaf)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One staged serving stage: a name, the resolved tile config (sorted
    (key, value) pairs; empty for reference/XLA paths and the pooling
    stage), and the ``x -> y`` closure with weight buffers frozen in."""

    name: str
    kind: str  # 'conv' | 'linear' | 'pool'
    tiles: Tuple[Tuple[str, int], ...]
    run: Callable[[Any], Any]


@dataclasses.dataclass(frozen=True)
class ModelPlan:
    """Immutable per-model serving plan — build with ``SparseCNN.plan()``.

    ``serve(x)`` (also ``plan(x)``) runs the whole staged chain as one
    jit-compiled program. ``check(params)`` raises :class:`StalePlanError`
    on a fingerprint mismatch.
    """

    model: str
    fingerprint: str
    layers: Tuple[LayerPlan, ...]
    batch: Optional[int] = None  # the batch the plan was staged for
    # One sample's (shape-sans-batch, dtype-name) the plan was staged for,
    # e.g. ((32, 32, 3), 'float32') for a CNN or ((128,), 'int32') for LM
    # prefill. The serving tier validates every request against this at
    # admission (DESIGN.md §14) so malformed requests are rejected alone
    # instead of poisoning a co-batch. None for plans built before the
    # spec was known (validation is then skipped).
    sample_spec: Optional[Tuple[Tuple[int, ...], str]] = None
    # (mesh, PartitionSpec of the batch axis) for data-parallel serving:
    # the chain then runs per device on its own rows (see PlanSet.shard)
    shard: Optional[Tuple[Any, Any]] = None

    def __post_init__(self):
        stages = tuple((l.name, l.run) for l in self.layers)
        traces = {"count": 0}

        def chain(x):
            traces["count"] += 1  # runs at trace time only, not per dispatch
            for name, run in stages:
                # names the stage's device ops in a profile (op metadata
                # only: the compiled program is the same)
                with jax.named_scope(name):
                    x = run(x)
            return x

        fn = chain
        if self.shard is not None:
            mesh, spec = self.shard
            fn = jax.shard_map(chain, mesh=mesh, in_specs=spec,
                               out_specs=spec, check_vma=False)
        object.__setattr__(self, "_serve", jax.jit(fn))
        object.__setattr__(self, "_traces", traces)

    def serve(self, x):
        """Steady-state serving: one dispatch, no checks, no params."""
        return self._serve(x)

    def lower(self, x):
        """``jax.jit(...).lower`` of the staged chain at ``x``'s shape:
        ``.compile()`` it to read the program the device runs (e.g. its
        Pallas ``tpu_custom_call`` ops). Counts as a trace."""
        return self._serve.lower(x)

    @property
    def trace_count(self) -> int:
        """How many times the staged chain has been (re)traced — one per
        distinct (shape, dtype, sharding) this plan has served. The
        serving tier snapshots this after warmup to enforce its
        zero-retrace contract (DESIGN.md §11)."""
        return self._traces["count"]

    def __call__(self, x):
        return self.serve(x)

    def check(self, params) -> None:
        if params_fingerprint(params) != self.fingerprint:
            raise StalePlanError(
                f"plan for {self.model!r} was built from different params "
                "(weights were re-quantized/re-compressed/re-calibrated "
                "after the plan was frozen) — rebuild with model.plan()"
            )

    @property
    def tiles(self) -> dict:
        """Per-layer resolved tile configs (introspection/bench)."""
        return {l.name: dict(l.tiles) for l in self.layers if l.tiles}


# ------------------------------------------------------------------ §11
def make_buckets(max_batch: int, *, dp: int = 1) -> Tuple[int, ...]:
    """The serving bucket ladder: ``dp``-multiple powers of two up to the
    first bucket ≥ ``max_batch`` (e.g. ``make_buckets(8) == (1, 2, 4, 8)``,
    ``make_buckets(6, dp=2) == (2, 4, 8)``). Every bucket is divisible by
    ``dp`` so a padded batch always shards evenly over the data axis of a
    device mesh."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if dp < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    out = [dp]
    while out[-1] < max_batch:
        out.append(out[-1] * 2)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class Launched:
    """What :meth:`PlanSet.launch` enqueued: each bucket chunk's pending
    result with its real rows and bucket, ``(y, n_real, bucket)``, in
    order, and whether the input was numpy (results come back as numpy)."""

    host: bool
    parts: Tuple[Tuple[Any, int, int], ...]


@dataclasses.dataclass(frozen=True)
class PlanSet:
    """A bucket ladder of frozen plans for one model (DESIGN.md §11).

    ``buckets`` is ascending and ``plans[b]`` is the :class:`ModelPlan`
    staged for batch ``b``. ``serve(x)`` handles any leading batch size:
    the batch is chunked at the largest bucket, each chunk is zero-padded
    up to the smallest bucket that fits, the bucket's pre-compiled plan
    runs, and the padding is sliced back off — bit-identical to serving
    each request alone (batch rows are independent end to end), with
    zero retraces once every bucket has been warmed.

    Build with ``SparseCNN.plan_set()``. The set shares its parent
    plans' immutability and params pin (one fingerprint for all
    buckets).
    """

    model: str
    fingerprint: str
    buckets: Tuple[int, ...]
    plans: Mapping[int, "ModelPlan"]
    # shared per-sample admission spec (see ModelPlan.sample_spec);
    # build_plan_set inherits it from the bucket plans.
    sample_spec: Optional[Tuple[Tuple[int, ...], str]] = None

    def __post_init__(self):
        if not self.buckets:
            raise ValueError("PlanSet needs at least one bucket")
        if list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError(f"buckets must be ascending+unique: {self.buckets}")
        if set(self.plans) != set(self.buckets):
            raise ValueError(
                f"plans keyed {sorted(self.plans)} != buckets {self.buckets}"
            )
        object.__setattr__(self, "plans", MappingProxyType(dict(self.plans)))

    # ------------------------------------------------------------ serve
    def bucket_for(self, n: int) -> Optional[int]:
        """Smallest bucket ≥ n, or None when n exceeds the largest bucket
        (``serve`` then chunks at the largest bucket)."""
        for b in self.buckets:
            if b >= n:
                return b
        return None

    def launch(self, x, *, put=None, on_dispatch=None,
               dispatch=None) -> "Launched":
        """Enqueue the bucketed plans for any batch size; returns at once.

        The batch is chunked at the largest bucket and each chunk is
        zero-padded up to the smallest bucket that fits and enqueued on its
        bucket's plan (for a numpy chunk the enqueue includes the
        synchronous host→device input copy). :meth:`fetch` of the returned
        handle waits for the results.

        ``put`` (optional) maps each padded chunk onto devices — the
        serving tier injects ``device_put`` to a mesh's data-axis
        ``NamedSharding`` here. ``on_dispatch(bucket, n_real)`` (optional)
        observes each underlying plan dispatch (stats/bench hook).
        ``dispatch(bucket, xb)`` (optional) replaces the per-bucket plan
        dispatch itself — the §15 degradation path routes a demoted
        bucket to its ref fallback closure here while chunk/pad/slice
        stay identical.
        """
        n = x.shape[0]
        if n < 1:
            raise ValueError(f"empty batch: {x.shape}")
        host = isinstance(x, np.ndarray)
        xp = np if host else jnp
        cap = self.buckets[-1]
        parts = []
        i = 0
        while i < n:
            take = min(cap, n - i)
            b = self.bucket_for(take)
            with TraceAnnotation("plan.dispatch", bucket=b, n_real=take):
                xb = x[i : i + take]
                if take < b:
                    pad = [(0, b - take)] + [(0, 0)] * (x.ndim - 1)
                    xb = xp.pad(xb, pad)
                if put is not None:
                    xb = put(xb)
                if on_dispatch is not None:
                    on_dispatch(b, take)
                # enqueue: includes the synchronous host->device input copy
                with TraceAnnotation("plan.launch"):
                    y = (self.plans[b].serve(xb) if dispatch is None
                         else dispatch(b, xb))
            parts.append((y, take, b))
            i += take
        return Launched(host, tuple(parts))

    @staticmethod
    def fetch(launched: "Launched"):
        """Wait for a :meth:`launch`'s results and slice the padding off:
        numpy for a numpy input (each chunk blocked on and gathered once,
        sliced on the host), jax, still on the device, for a jax input."""
        outs = []
        for y, take, b in launched.parts:
            if launched.host:
                with TraceAnnotation("plan.fetch", bucket=b):
                    y = np.asarray(y)
            outs.append(y if take == b else y[:take])
        if len(outs) == 1:
            return outs[0]
        return (np if launched.host else jnp).concatenate(outs, axis=0)

    def serve(self, x, *, put=None, on_dispatch=None, dispatch=None):
        """Bucketed serving of any batch size: ``fetch(launch(x))``.

        A numpy ``x`` takes the **host-assembly fast path**: chunk/pad/
        slice run as numpy on the host and the result comes back as
        numpy — only the pre-warmed bucket-shaped plan dispatch ever
        touches the device, so no glue op (pad, slice, concat) can
        trigger a first-occurrence XLA compile mid-traffic. This is the
        path the serving tier dispatches on. A jax ``x`` stays on-device
        end to end and returns jax. The keywords are :meth:`launch`'s.
        """
        return self.fetch(self.launch(x, put=put, on_dispatch=on_dispatch,
                                      dispatch=dispatch))

    def __call__(self, x):
        return self.serve(x)

    def shard(self, mesh, spec) -> "PlanSet":
        """This ladder served data-parallel on ``mesh``: every bucket's
        chain runs under ``shard_map`` with its batch axis split per
        ``spec``, so each device serves its own rows. XLA's partitioner
        cannot split a Pallas kernel — left to it, every device would run
        the whole batch. Each bucket must be a multiple of the data-axis
        size (``make_buckets(dp=)``)."""
        plans = {b: dataclasses.replace(p, shard=(mesh, spec))
                 for b, p in self.plans.items()}
        return dataclasses.replace(self, plans=plans)

    @property
    def sharded(self) -> bool:
        return any(p.shard is not None for p in self.plans.values())

    def warmup(self, sample_shape: Optional[Tuple[int, ...]] = None,
               dtype=jnp.float32, *, put=None) -> int:
        """Trace+compile every bucket once (``sample_shape`` is one
        sample, no batch dim — e.g. ``(H, W, C)``; defaults to the set's
        own :attr:`sample_spec`). Warms the same host→device transfer +
        dispatch signature the host-assembly ``serve`` path uses. Returns
        :attr:`trace_count` afterwards; serving any batch size through
        the same ``put`` after this retraces nothing."""
        if sample_shape is None:
            if self.sample_spec is None:
                raise ValueError(
                    "warmup() needs sample_shape: this plan set carries no "
                    "sample_spec")
            sample_shape, dtype = self.sample_spec
        for b in self.buckets:
            with TraceAnnotation("plan.warmup", bucket=b):
                xb = np.zeros((b,) + tuple(sample_shape), dtype)
                self.serve(xb, put=put)
        return self.trace_count

    # ------------------------------------------------------- introspection
    @property
    def trace_count(self) -> int:
        """Total (re)traces across all buckets (zero-retrace contract)."""
        return sum(p.trace_count for p in self.plans.values())

    @property
    def tiles(self) -> dict:
        """Per-bucket per-layer resolved tile configs."""
        return {b: self.plans[b].tiles for b in self.buckets}

    def check(self, params) -> None:
        """Raise :class:`StalePlanError` unless ``params`` still matches
        the params every bucket's plan was frozen from."""
        if params_fingerprint(params) != self.fingerprint:
            raise StalePlanError(
                f"plan set for {self.model!r} was built from different "
                "params (weights were re-quantized/re-compressed/"
                "re-calibrated) — rebuild with model.plan_set()"
            )


# ----------------------------------------------------------------- §15
def fallback_closures(primary: "PlanSet", fallback: "PlanSet", *,
                      verify: bool = True, rtol: float = 0.0) -> dict:
    """Per-bucket degradation closures for the self-healing serving tier
    (DESIGN.md §15): ``{bucket: serve_callable}`` built from a second
    :class:`PlanSet` staged on the reference (gather/interpreter) kernel
    path. When a bucket's compiled (pallas) dispatch persistently fails,
    the server demotes exactly that bucket to its closure here; every
    other bucket keeps the compiled path.

    Bit-compat is **asserted at build time** (``verify=True``): the two
    sets must share the params fingerprint, buckets, and sample spec, and
    every bucket is served a deterministic batch through both paths —
    outputs must match exactly (``rtol=0``, the int8 datapath's integer
    accumulation is bit-identical between ref and pallas) or within
    ``rtol``. The verification pass doubles as the fallback's warmup, so
    a later demotion dispatches an already-compiled closure and adds
    zero mid-traffic traces.
    """
    if primary.fingerprint != fallback.fingerprint:
        raise StalePlanError(
            "fallback plan set was built from different params than the "
            "primary — rebuild both from the same quantized weights")
    if tuple(primary.buckets) != tuple(fallback.buckets):
        raise ValueError(
            f"fallback buckets {fallback.buckets} != primary "
            f"{primary.buckets} — a demoted bucket must keep its ladder")
    if (primary.sample_spec is not None
            and fallback.sample_spec != primary.sample_spec):
        raise ValueError(
            f"fallback sample spec {fallback.sample_spec} != primary "
            f"{primary.sample_spec}")
    if verify:
        if primary.sample_spec is None:
            raise ValueError("bit-compat verification needs a sample_spec")
        shape, dtype = primary.sample_spec
        rng = np.random.default_rng(0)
        for b in primary.buckets:
            xb = rng.standard_normal((b,) + tuple(shape)).astype(dtype)
            yp = np.asarray(primary.plans[b].serve(xb))
            yf = np.asarray(fallback.plans[b].serve(xb))
            if rtol == 0.0:
                np.testing.assert_array_equal(
                    yf, yp,
                    err_msg=f"fallback bucket {b} is not bit-compatible "
                            "with the compiled path")
            else:
                np.testing.assert_allclose(
                    yf, yp, rtol=rtol,
                    err_msg=f"fallback bucket {b} diverges beyond "
                            f"rtol={rtol} from the compiled path")
    return {b: fallback.plans[b].serve for b in fallback.buckets}


# ----------------------------------------------------------------- §13
# Model-agnostic plan staging. SparseCNN.plan/plan_set and LM.plan are
# thin compositions over these — any model family stages per-layer
# closures through a PlanBuilder and inherits fingerprint pinning, the
# tiles each layer's make_plan froze, and bucketed PlanSets.


class PlanBuilder:
    """Collects staged serving layers into an immutable :class:`ModelPlan`.

    One builder per (model, params, batch): the params fingerprint is
    taken at construction, and every :meth:`stage` call records the tiles
    the layer's ``make_plan`` froze. Layers that stage plain closures
    without tile resolution (pooling, norms, whole transformer blocks)
    use :meth:`raw`.
    """

    def __init__(self, model: str, params, *, batch: Optional[int] = None,
                 sample_spec: Optional[Tuple[Tuple[int, ...], str]] = None):
        self.model = model
        self.batch = batch
        self.sample_spec = sample_spec
        self.fingerprint = params_fingerprint(params)
        self._stages: list = []

    def stage(self, name: str, kind: str, make_plan: Callable, *args, **kw):
        """Stage one layer via its ``make_plan(*args, **kw)`` → ``(run,
        tiles)`` contract. Returns self (chainable)."""
        run, tiles = make_plan(*args, **kw)
        self._stages.append(
            LayerPlan(name, kind, tuple(sorted(tiles.items())), run)
        )
        return self

    def raw(self, name: str, kind: str, run: Callable):
        """Stage a tile-free closure (weights already frozen in)."""
        self._stages.append(LayerPlan(name, kind, (), run))
        return self

    def build(self) -> ModelPlan:
        if not self._stages:
            raise ValueError("PlanBuilder has no stages")
        return ModelPlan(self.model, self.fingerprint, tuple(self._stages),
                         self.batch, self.sample_spec)


def build_plan_set(model: str, params, plan_for_batch: Callable[[int], ModelPlan],
                   *, max_batch: Optional[int] = None, buckets=None,
                   dp: int = 1) -> PlanSet:
    """Bucket-ladder :class:`PlanSet` from a per-batch plan factory.

    Derives/validates the ladder (``make_buckets`` powers of two when
    ``buckets`` is None; every bucket a positive multiple of ``dp``),
    builds one plan per bucket via ``plan_for_batch(b)``, and pins the
    set to ``params``. Model families supply only the factory.
    """
    if buckets is None:
        if max_batch is None:
            raise ValueError("plan set needs max_batch or explicit buckets")
        buckets = make_buckets(max_batch, dp=dp)
    buckets = tuple(sorted({int(b) for b in buckets}))
    bad = [b for b in buckets if b < 1 or b % dp]
    if bad:
        raise ValueError(f"buckets {bad} not positive multiples of dp={dp}")
    plans = {b: plan_for_batch(b) for b in buckets}
    # every bucket stages the same per-sample signature — inherit the
    # admission spec (DESIGN.md §14) from the first plan that carries one
    spec = next(
        (p.sample_spec for p in plans.values() if p.sample_spec is not None),
        None,
    )
    return PlanSet(model, params_fingerprint(params), buckets, plans, spec)

"""DQGAN (paper Algorithm 2) as a composable distributed train-step builder.

The builder turns any "field" function F (gradient oracle — for GANs the
concatenated field [∇θ L_G, ∇φ L_D], for plain minimization just grad(loss))
into a jit-compilable SPMD step:

    worker m:  w_{t-1/2}^m = w_{t-1} - [η F(w_{t-3/2}^m; ξ_{t-1}^m) + e_{t-1}^m]
               g_t^m       = F(w_{t-1/2}^m; ξ_t^m)
               p_t^m       = η g_t^m + e_{t-1}^m
               p̂_t^m      = Q(p_t^m);   e_t^m = p_t^m - p̂_t^m
    server:    q̂_t = (1/M) Σ_m p̂_t^m          (core.exchange strategies)
    workers:   w_t = w_{t-1} - q̂_t

SPMD mapping: one `jax.shard_map`, manual over DQConfig.worker_axes (the
paper's M machines), auto over everything else ('model' tensor parallelism,
and — when worker_axes == ('pod',) — FSDP over 'data' inside each pod).
Per-worker state (prev grad, EF residuals) is carried with a leading
worker axis sharded over the worker mesh axes.

Baselines from the paper fall out as configurations:
    CPOAdam      = optimizer='oadam', compressor='identity'
    CPOAdam-GQ   = optimizer='oadam', compressor=..., error_feedback=False
    DQGAN        = optimizer='omd',   compressor=..., error_feedback=True

`extrapolation='global'` replaces the paper's per-worker lookahead
η F(w^m_prev) + e^m with the previous *applied* update q̂_{t-1} (identical
across workers, hence FSDP-safe at 100B scale) — a deliberate beyond-paper
variant, see DESIGN.md §2.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import DQConfig
from repro.strategy import Strategy
from repro import obs as OBS
from . import compressors as C
from . import exchange as X


class DQState(NamedTuple):
    """Full optimizer state. Per-worker leaves have a leading axis of size
    M (the worker count) sharded over the worker mesh axes; replicated
    leaves (params, moments) have no worker axis."""
    step: jax.Array
    params: Any
    prev_grad: Any       # per-worker F(w^m_{t-3/2}; ξ_{t-1}) (omd/local) | None
    prev_update: Any     # q̂_{t-1} (global extrapolation) or Adam prev dir | None
    ef: Any              # per-worker exchange EF state dicts | None
    m: Any               # Adam first moment | None
    v: Any               # Adam second moment | None
    # repro.sched per-worker buffers (DESIGN.md §5, §8) | None for every_step:
    #   {"accum": tree}   local_k — message accumulated since last round
    #   {"pending": tree, "versions": (W,) int32}   delayed(τ) —
    #       pending: the in-flight message(s) awaiting exchange. τ=1 keeps
    #       PR 2's single-slot layout (leaf (W, *shape)); τ>1 is a ring
    #       buffer (leaf (W, τ, *shape), index 0 = oldest = next on the
    #       wire). versions: per-worker step index of the last message
    #       this worker had applied at the server (the parameter-server
    #       push/pull version vector; staleness at step t = t − version).
    sched: Any = None
    # fsdp (exchange.parallelism='fsdp', DESIGN.md §15) per-bucket shard
    # state, {str(bid): {...}} with every leaf (W, bucket_size/W) f32
    # sharded over the worker axes — worker m's row is its owned flat
    # shard. Slots: "m"/"v" Adam moments (adam/oadam), "dir" previous
    # Adam direction (oadam), "w" the authoritative parameter shard
    # (zero_stage=3), "age" the owner-side all-gather EF residual
    # (arXiv 2004.14180). None outside fsdp mode; replaces the
    # replicated m/v/prev_update slots, which stay None.
    fsdp: Any = None


class StepOutput(NamedTuple):
    state: DQState
    metrics: Any


def _tree_zeros(tree, dtype=None):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree)


def _is_plan(x):
    return isinstance(x, dict) and "strategy" in x


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


@dataclasses.dataclass(frozen=True)
class DQGAN:
    """Builder. Construct once per (model, mesh, Strategy/DQConfig); then
    use `.init(params)` and `.step` (jit the latter).

    The blessed spelling passes a `repro.strategy.Strategy` (optimizer
    knobs via `dq=DQConfig.from_strategy(...)` when they matter); the
    legacy flat `dq=DQConfig(...)` flag bag keeps working through the
    shim. Either way `self.strategy` is the single validated dispatch
    surface both SPMD paths consume."""

    field_fn: Callable  # (params, batch, rng) -> (grad_tree, metrics_dict)
    dq: Optional[DQConfig] = None
    mesh: Any = None                      # jax.sharding.Mesh | None (single proc)
    param_specs: Any = None               # pytree of PartitionSpec (model axes only)
    batch_spec: Any = None                # PartitionSpec for batch leaves
    strategy: Optional[Strategy] = None   # distribution strategy (DESIGN.md §9)
    # (layout, plan) memo keyed by leaf shapes — _comm is hit several times
    # per trace (plans, EF init, exchange) and is pure host-side planning.
    _comm_cache: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False)

    def __post_init__(self):
        if self.dq is None:
            dq = DQConfig.from_strategy(self.strategy or Strategy())
            object.__setattr__(self, "dq", dq)
        elif self.strategy is not None and self.strategy != self.dq.strategy:
            raise ValueError(
                "DQGAN: dq and strategy disagree:\n  "
                + "\n  ".join(self.dq.strategy.diff(self.strategy)))
        object.__setattr__(self, "strategy", self.dq.strategy)

    # ------------------------------------------------------------------ #
    @property
    def n_workers(self) -> int:
        if not self.strategy.exchange.worker_axes or self.mesh is None:
            return 1
        return math.prod(self.mesh.shape[a]
                         for a in self.strategy.exchange.worker_axes)

    @property
    def compressor(self) -> C.Compressor:
        return self.strategy.compression.get()

    @property
    def uses_adam(self) -> bool:
        return self.dq.optimizer in ("adam", "oadam")

    @property
    def bucketed(self) -> bool:
        """True when the repro.comm flat-bucket exchange path is active."""
        return self.strategy.compression.bucketing

    @property
    def fsdp(self) -> bool:
        """True when exchange.parallelism='fsdp': optimizer state shards
        across the workers, gradients ride a (compressed) reduce-scatter
        and updates/params a quantized all-gather (DESIGN.md §15)."""
        return self.strategy.exchange.fsdp

    @property
    def adaptive(self) -> bool:
        """True when a round-adaptive PlanFamily drives the bucket
        compressors (DESIGN.md §10)."""
        return self.strategy.compression.adaptive

    def _comm_full(self, tree):
        """(BucketLayout, CommPlan, PlanFamily | None) — static, derived
        from leaf shapes. For an adaptive strategy the CommPlan is the
        family's full-participation member, so every consumer of the
        static plan (EF init, ledger, skipped-leaf bookkeeping) sees the
        same layout whether the family is in play or not."""
        shapes = jax.tree.map(lambda x: tuple(x.shape), tree)
        cache_key = (jax.tree.structure(shapes, is_leaf=_is_shape),
                     tuple(jax.tree.leaves(shapes, is_leaf=_is_shape)))
        hit = self._comm_cache.get(cache_key)
        if hit is not None:
            return hit
        # mesh axis sizes let the layout see degenerate (size-1) mesh
        # axes as replication instead of sharding, so e.g. a model_n=1
        # mesh doesn't push 'model'-spec'd leaves off the bucket path
        axis_sizes = (dict(self.mesh.shape) if self.mesh is not None
                      else None)
        if self.adaptive:
            layout, family = self.strategy.compression.build_family(
                shapes, self.param_specs, self.n_workers)
            entry = (layout, family.full, family)
        else:
            layout, plan = self.strategy.compression.build(
                shapes, self.param_specs, self.n_workers,
                axis_sizes=axis_sizes)
            entry = (layout, plan, None)
        self._comm_cache[cache_key] = entry
        return entry

    def _comm(self, tree):
        """(BucketLayout, CommPlan) — the static (full-participation)
        view."""
        layout, plan, _ = self._comm_full(tree)
        return layout, plan

    def _family(self, tree):
        """The PlanFamily, or None for non-adaptive strategies."""
        return self._comm_full(tree)[2]

    # ------------------------------------------------------------------ #
    # repro.obs wiring (DESIGN.md §11) — all jit-static
    # ------------------------------------------------------------------ #
    @property
    def obs_spec(self):
        """The resolved `repro.obs.MetricSpec` for this trainer."""
        return self.strategy.observability.spec()

    @property
    def _obs_spans(self) -> bool:
        return self.strategy.observability.spans

    def _obs_bins(self) -> int:
        """Staleness-histogram bins: 0..τ plus one overflow bin (partial
        participation lets a sitting worker's staleness exceed τ)."""
        return self.strategy.schedule.staleness + 2

    def _obs_n_buckets(self, tree) -> int:
        return len(self._comm(tree)[0].buckets) if self.bucketed else 0

    def _obs_collector(self, tree):
        """A live `Collector` when metrics are on, else the no-op
        `NullCollector` (whose record calls leave the trace untouched —
        the metrics="off" bit-exactness contract)."""
        spec = self.obs_spec
        if not spec.on:
            return OBS.NullCollector()
        return OBS.Collector(spec, self._obs_n_buckets(tree))

    def comm_ledger(self, params) -> "Any":
        """CommLedger describing this trainer's per-step wire cost (used by
        launch.train logs and benchmarks.run)."""
        from repro.comm import CommLedger

        strat = self.strategy
        shapes = jax.tree.map(lambda x: tuple(x.shape), params)
        if self.bucketed:
            layout, cplan, family = self._comm_full(params)
            flat_plans = jax.tree.leaves(self._plans(params), is_leaf=_is_plan)
            leaf_plans = [flat_plans[s.index] for s in layout.skipped]
            budget = (int(strat.compression.budget_mb * (1 << 20))
                      if strat.compression.plan == "delta_budget" else 0)
            return CommLedger.from_plan(
                layout, cplan, strat.exchange.kind, self.n_workers,
                strat.compression.compressor, leaf_plans=leaf_plans,
                family=family, budget_bytes=budget,
                moment_compressor=(strat.moments.compressor
                                   if self.fsdp else None))
        return CommLedger.from_tree(
            strat.exchange.kind, strat.compression.compressor, shapes,
            self.param_specs, self.n_workers)

    def _plans(self, params):
        shapes = jax.tree.map(lambda x: tuple(x.shape), params)
        specs = self.param_specs
        if specs is None:
            specs = jax.tree.map(lambda x: P(), params)
        plans = self.strategy.exchange.leaf_plans(shapes, specs,
                                                  self.n_workers)
        if not self.bucketed:
            return plans
        # bucketed leaves leave the per-tensor machinery entirely; only the
        # skipped (sharded) leaves keep their per-tensor plan (which may
        # still legitimately fall back to sim).
        layout, _ = self._comm(params)
        in_bucket = {s.index for b in layout.buckets for s in b.slots}
        flat, treedef = jax.tree.flatten(plans, is_leaf=_is_plan)
        flat = [
            {"strategy": "bucketed", "chunk_axis": None, "fallback": False}
            if i in in_bucket else p
            for i, p in enumerate(flat)
        ]
        return jax.tree.unflatten(treedef, flat)

    def _scale_groups(self, tree):
        """Apply DQConfig.lr_mults by top-level pytree key (TTUR)."""
        if not self.dq.lr_mults:
            return tree
        mults = dict(self.dq.lr_mults)

        def one(path, leaf):
            key = getattr(path[0], "key", None) if path else None
            return leaf * mults.get(str(key), 1.0)

        return jax.tree_util.tree_map_with_path(one, tree)

    # ------------------------------------------------------------------ #
    # state construction
    # ------------------------------------------------------------------ #
    def init(self, params) -> DQState:
        """Concrete zero state, every leaf placed with its `init_abstract`
        sharding: the first step then sees the input shardings that later
        steps get back from it (one trace, one compile), and each
        per-worker slot lives on its own worker's devices."""
        sched_c = self.strategy.schedule
        abstract = self.init_abstract(params)
        st = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype) if hasattr(s, "shape") else s,
            abstract,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )._replace(params=params)
        if sched_c.kind == "delayed":
            # nothing applied yet: version −τ makes the staleness metric
            # (step − version) read exactly τ from the first exchange on
            st = st._replace(sched={
                **st.sched,
                "versions": jnp.full((max(self.n_workers, 1),),
                                     -sched_c.tau, jnp.int32),
            })
        if self.fsdp and self.strategy.exchange.zero_stage == 3:
            # zero-3: the shard owner's parameter copy is authoritative —
            # seed it from the packed initial params so round 0's
            # all-gather reconstructs exactly w_0 under an exact
            # compressor (and EF-corrects otherwise).
            from repro.comm import buckets as B

            layout, _ = self._comm(params)
            flats = B.pack(layout, [l.astype(jnp.float32)
                                    for l in jax.tree.leaves(params)])
            W = max(self.n_workers, 1)
            fb = {k: dict(v) for k, v in st.fsdp.items()}
            for b in layout.buckets:
                fb[str(b.bid)]["w"] = flats[b.bid].reshape(W, b.size // W)
            st = st._replace(fsdp=fb)
        if self.mesh is None:
            return st
        return jax.tree.map(lambda x, s: jax.device_put(x, s.sharding),
                            st, abstract)

    def _validate_lr_mults(self, params):
        """DQConfig.lr_mults names top-level param groups (TTUR); a typo'd
        group (e.g. "disc_" for "disc") was silently ignored — fail fast
        against the actual tree instead."""
        if not self.dq.lr_mults:
            return
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        groups = {str(p[0].key) for p, _ in flat
                  if p and hasattr(p[0], "key")}
        unknown = sorted(k for k, _ in self.dq.lr_mults if k not in groups)
        if unknown:
            raise ValueError(
                f"lr_mults group(s) {unknown} not found in the top-level "
                f"param groups {sorted(groups)}")

    def init_abstract(self, params) -> DQState:
        """ShapeDtypeStruct state with correct shardings (dry-run path).

        Strategy composition is validated at DQConfig/Strategy
        construction, so no flag checks remain here."""
        W = self.n_workers
        dq = self.dq
        strat = self.strategy
        self._validate_lr_mults(params)
        tv = strat.schedule.tau_vector
        if tv and len(tv) != max(W, 1):
            raise ValueError(
                f"schedule.tau_vector has {len(tv)} entries but this mesh "
                f"runs {max(W, 1)} workers — one τ_m per worker")
        plans = self._plans(params)
        ef_dtype = jnp.dtype(strat.compression.ef_dtype)

        def sds(shape, dtype, spec):
            # trailing Nones dropped: the spelling jit gives its outputs, so
            # `init`'s state keys the same compiled step as the step's own
            # output (P(None,) and P() are one layout but two cache keys)
            entries = list(spec)
            while entries and entries[-1] is None:
                entries.pop()
            sharding = (NamedSharding(self.mesh, P(*entries))
                        if self.mesh is not None else None)
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        def pspec(x):
            # params' own sharding if it is an array/SDS with sharding
            sh = getattr(x, "sharding", None)
            if isinstance(sh, NamedSharding):
                return sh.spec
            return P()

        axes = strat.exchange.worker_axes

        def worker_spec(spec):
            return P(axes, *spec)

        def param_like(x):
            return sds(x.shape, x.dtype, pspec(x))

        def per_worker_like(x, dtype=None):
            return sds((W,) + tuple(x.shape), dtype or x.dtype,
                       worker_spec(pspec(x)))

        params_s = jax.tree.map(param_like, params)

        prev_grad = None
        if dq.optimizer == "omd" and dq.extrapolation == "local":
            prev_grad = jax.tree.map(per_worker_like, params)

        prev_update = None
        if ((dq.optimizer == "omd" and dq.extrapolation == "global")
                or dq.optimizer == "oadam") and not self.fsdp:
            # fsdp: oadam's previous direction shards into the per-bucket
            # "dir" slot; omd 'global' extrapolation is rejected below
            # (the applied-update tree never materializes at any worker).
            prev_update = jax.tree.map(param_like, params)

        def ef_leaf(x, plan):
            st = {}
            if dq.error_feedback:
                st["e1"] = sds((W,) + tuple(x.shape), ef_dtype,
                               worker_spec(pspec(x)))
            if X.plan_has_owner_ef(plan):
                ax = plan["chunk_axis"]
                cs = list(x.shape)
                cs[ax] //= W
                spec = pspec(x)
                st["e2"] = sds((W,) + tuple(cs), ef_dtype, worker_spec(spec))
            return st if st else None

        ef = jax.tree.map(ef_leaf, params, plans)
        if self.bucketed:
            # bucket-level state rides beside the per-leaf residuals: e1
            # stays per-tensor (the local-extrapolation lookahead needs leaf
            # views of it), phase-2 owner error is per-bucket.
            layout, _ = self._comm(params)
            bucket_ef = {}
            # fsdp has no phase-2 owner requantization — the return leg's
            # owner residual is the per-bucket "age" slot instead of e2.
            if strat.exchange.owner_ef and not self.fsdp:
                for b in layout.buckets:
                    bucket_ef[str(b.bid)] = {
                        "e2": sds((W, b.size // max(W, 1)), ef_dtype,
                                  worker_spec(P()))
                    }
            ef = {"leaf": ef, "bucket": bucket_ef}

        fsdp = None
        if self.fsdp:
            layout, _ = self._comm(params)
            if layout.skipped:
                skipped_ix = sorted(s.index for s in layout.skipped)
                raise ValueError(
                    "exchange.parallelism='fsdp' needs every leaf in a "
                    "flat bucket, but the comm planner skipped leaf "
                    f"index(es) {skipped_ix} (sharded over axes outside "
                    "the fsdp worker axes). Shard those leaves over the "
                    "fsdp axis (shard-aware bucketing, DESIGN.md §15.1), "
                    "unshard them, or use parallelism='replicated'.")
            if dq.lr_mults:
                raise ValueError(
                    "lr_mults groups params by top-level key, which is "
                    "undefined on fsdp's flat shard buckets — drop "
                    "lr_mults or use parallelism='replicated'")
            if dq.optimizer == "omd" and dq.extrapolation == "global":
                raise ValueError(
                    "extrapolation='global' needs the full applied-update "
                    "tree, which fsdp never materializes at a single "
                    "worker — use extrapolation='local' or "
                    "parallelism='replicated'")
            fsdp = {}
            for b in layout.buckets:
                c = b.size // max(W, 1)

                def shard_like():
                    return sds((W, c), jnp.float32, worker_spec(P()))

                ent = {"age": shard_like()}
                if self.uses_adam:
                    ent["m"] = shard_like()
                    ent["v"] = shard_like()
                if dq.optimizer == "oadam":
                    ent["dir"] = shard_like()
                if strat.exchange.zero_stage == 3:
                    ent["w"] = shard_like()
                fsdp[str(b.bid)] = ent

        m = v = None
        if self.uses_adam and not self.fsdp:
            m = jax.tree.map(param_like, params)
            v = jax.tree.map(param_like, params)

        # repro.sched buffers carry the (float32) exchange message, one per
        # worker, same sharding discipline as the EF residuals. The
        # schedule component owns WHICH slots exist (accum / pending ring /
        # versions); the closures own shape+sharding.
        sched = strat.schedule.init_slots(
            params,
            worker_like=lambda x: per_worker_like(x, jnp.float32),
            # (W, τ, *shape): τ in-flight messages per worker, oldest
            # first. τ=1 keeps PR 2's (W, *shape) single-slot layout
            # (and its compiled graph) bit-exactly.
            ring_like=lambda x: sds(
                (W, strat.schedule.tau) + tuple(x.shape), jnp.float32,
                P(axes, None, *pspec(x))),
            versions_like=lambda: sds((W,), jnp.int32, P(axes)),
        )

        return DQState(
            step=sds((), jnp.int32, P()),
            params=params_s,
            prev_grad=prev_grad,
            prev_update=prev_update,
            ef=ef,
            m=m,
            v=v,
            sched=sched,
            fsdp=fsdp,
        )

    def state_specs(self, params) -> DQState:
        """PartitionSpec tree matching init_abstract (for jit in_shardings)."""
        abstract = self.init_abstract(params)

        def spec_of(x):
            sh = getattr(x, "sharding", None)
            if isinstance(sh, NamedSharding):
                return sh.spec
            return P()

        return jax.tree.map(spec_of, abstract,
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    # ------------------------------------------------------------------ #
    # the step
    # ------------------------------------------------------------------ #
    def step(self, state: DQState, batch, key,
             do_exchange: bool = True) -> StepOutput:
        """One Algorithm-2 iteration. jit me (donate state for in-place).

        ``do_exchange`` is only consulted by the ``local_k`` schedule; it
        must be a static Python bool (jit it via ``static_argnums=(3,)``)
        — the host decides the cadence with
        ``sched.ExchangeSchedule.is_exchange_step(step)``. ``every_step``
        and ``delayed`` run their collective every call and ignore it.
        """
        dq = self.dq
        strat = self.strategy
        if strat.schedule.kind == "local_k":
            if not isinstance(do_exchange, bool):
                raise TypeError(
                    "schedule='local_k' needs a static Python bool "
                    "do_exchange (jit with static_argnums=(3,)); got "
                    f"{type(do_exchange).__name__}")
        else:
            do_exchange = True
        plans = self._plans(state.params)
        axes = tuple(strat.exchange.worker_axes)
        W = self.n_workers

        if not axes or self.mesh is None or W == 1:
            # single worker: per-worker leaves still carry their leading
            # worker axis (of size 1), so squeeze stays on.
            return self._worker_body(
                state, batch, key, None, plans, axes=(), squeeze=True,
                do_exchange=do_exchange,
            )

        if strat.exchange.spmd == "vmap":
            return self._step_vmap(state, batch, key, W,
                                   do_exchange=do_exchange)

        body = partial(self._worker_body, plans=plans, axes=axes,
                       squeeze=True, do_exchange=do_exchange)

        # ---- build shard_map specs (manual axes only) -------------------- #
        rep = P()
        wlead = P(axes)

        def st_spec(name):
            sub = getattr(state, name)
            if sub is None:
                return None
            lead = (wlead if name in ("prev_grad", "ef", "sched", "fsdp")
                    else rep)
            return jax.tree.map(lambda _: lead, sub)

        state_specs = DQState(
            step=rep,
            params=jax.tree.map(lambda _: rep, state.params),
            prev_grad=st_spec("prev_grad"),
            prev_update=st_spec("prev_update"),
            ef=st_spec("ef"),
            m=st_spec("m"),
            v=st_spec("v"),
            sched=st_spec("sched"),
            fsdp=st_spec("fsdp"),
        )
        bspec = self.batch_spec
        if bspec is None:
            bspec = P(axes)
        batch_specs = jax.tree.map(lambda _: bspec, batch)

        metric_specs = {"loss": rep, "grad_norm": rep, "error_norm": rep,
                        "staleness_max": rep, "staleness_mean": rep}
        obs_spec = self.obs_spec
        if obs_spec.on:
            # obs metrics ride out replicated; the key set is the static
            # `metric_keys` contract shared with metrics.finalize
            metric_specs["obs"] = {
                k: rep for k in OBS.metric_keys(
                    obs_spec, self._obs_n_buckets(state.params))}
        out_specs = StepOutput(state=state_specs, metrics=metric_specs)
        # manual over the worker axes, auto over the rest; the replication
        # checker is off because the worker bodies mix collectives with
        # auto-sharded compute, which it cannot type. The worker index
        # arrives as a sharded input: with `lax.axis_index` inside a
        # partial-auto shard_map (an auto 'model' axis) the XLA:CPU
        # collectives of a (pod, data, model) mesh deadlock.
        widx_arr = jnp.arange(W, dtype=jnp.int32)
        fn = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(state_specs, batch_specs, rep, wlead),
            out_specs=out_specs,
            axis_names=set(axes),
            check_vma=False,
        )
        return fn(state, batch, key, widx_arr)

    # ------------------------------------------------------------------ #
    def _step_vmap(self, state, batch, key, W, do_exchange=True):
        """Workers as a vmapped leading axis (paper semantics of Algorithm 2,
        exchange = mean over the worker axis, compression via per-worker
        roundtrip — the 'sim' strategy). Pure auto-sharding: the worker axis
        is sharded over dq.worker_axes, everything inside (FSDP 'data',
        tensor 'model') is compiler-managed. Used for the 100B-scale FSDP
        layout where shard_map-over-pod hits an XLA partitioner CHECK.

        Schedule dataflow (repro.sched) mirrors `_worker_body`: local_k
        accumulates the message and only compresses at round ends; delayed
        compresses the previous step's message with the staleness
        correction folded into the OMD lookahead; partial participation
        masks messages/residuals and rescales the mean."""
        from .error_feedback import compress_with_ef

        dq = self.dq
        sched_c = self.strategy.schedule
        comp = self.compressor
        eta = dq.lr
        schedule = sched_c.kind

        batch_w = jax.tree.map(
            lambda x: x.reshape((W, x.shape[0] // W) + x.shape[1:]), batch
        )
        widx = jnp.arange(W)
        part_setup = self.strategy.participation.round_setup(
            key, state.step, W, sched_c.period)
        has_part = part_setup is not None
        mask_vec = part_setup[0] if has_part else jnp.ones((W,), jnp.float32)
        n_part = part_setup[1] if has_part else W
        exchanging = not (schedule == "local_k" and not do_exchange)
        obs_spec = self.obs_spec
        spans = self._obs_spans
        # vmap forbids bucketing (Strategy validation), so the collector
        # runs aggregate-only; its per-worker sums ride out of the vmap
        # stacked and are summed over axis 0 below.
        col = (OBS.Collector(obs_spec, 0) if obs_spec.on
               else OBS.NullCollector())

        def worker(prev_g, ef, sw, b, i, mask):
            kw = jax.random.fold_in(jax.random.fold_in(key, i), state.step)
            kf, kq = jax.random.split(kw)
            pending_buf, pending = sched_c.wire_head(sw, i)
            stale = sched_c.staleness_correction(pending_buf, dq.message,
                                                 eta, i)
            if dq.optimizer == "omd" and dq.extrapolation == "local":
                def extrap(w, g_prev, e, s):
                    upd = eta * g_prev
                    if e is not None:
                        upd = upd + e["e1"].astype(upd.dtype)
                    if s is not None:
                        upd = upd + s.astype(upd.dtype)
                    return w - upd.astype(w.dtype)
                leaves_p, tdp = jax.tree.flatten(state.params)
                gl = tdp.flatten_up_to(prev_g)
                el = (tdp.flatten_up_to(ef) if dq.error_feedback and ef
                      is not None else [None] * len(leaves_p))
                sl = (tdp.flatten_up_to(stale) if stale is not None
                      else [None] * len(leaves_p))
                w_half = jax.tree.unflatten(
                    tdp, [extrap(w, g, e, s)
                          for w, g, e, s in zip(leaves_p, gl, el, sl)])
            elif dq.optimizer == "omd":
                upd_tree = state.prev_update
                if stale is not None:
                    upd_tree = jax.tree.map(
                        lambda u, s: u + s.astype(u.dtype), upd_tree, stale)
                w_half = jax.tree.map(lambda w, u: w - u.astype(w.dtype),
                                      state.params, upd_tree)
            else:
                w_half = state.params
            with OBS.device_span("field", spans):
                grads, metrics = self.field_fn(w_half, b, kf)
            if dq.message == "update" and dq.optimizer == "omd":
                msg = jax.tree.map(lambda g: (eta * g).astype(jnp.float32),
                                   grads)
            else:
                msg = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

            # schedule dataflow — one component method shared with the
            # shard_map path (accumulate / ring-shift / version advance)
            exch, new_sw = sched_c.fold(sw, msg, pending, do_exchange,
                                        state.step, mask, _tree_zeros, i)

            phat = enew = None
            if exch is not None:
                leaves, treedef = jax.tree.flatten(exch)
                ef_leaves = (treedef.flatten_up_to(ef) if ef is not None
                             else [None] * len(leaves))
                phats, enews = [], []
                for j, (m, e) in enumerate(zip(leaves, ef_leaves)):
                    e1 = (e["e1"] if e
                          else jnp.zeros_like(m)).astype(jnp.float32)
                    m_in = m * mask if has_part else m
                    e_in = e1 * mask if has_part else e1
                    _, p_hat, e_new = compress_with_ef(
                        comp, m_in, e_in, jax.random.fold_in(kq, j),
                        use_ef=dq.error_feedback, allow_fused=False)  # vmapped
                    if col.enabled:
                        # the wire stream (masked under participation, as
                        # in the shard_map path) and the pre-merge
                        # residual: exactly m_in + e_in − Q(·)
                        col.leaf(m_in, m_in + e_in, e_new)
                    if has_part and dq.error_feedback:
                        e_new = mask * e_new + (1.0 - mask) * (e1 + m)
                    phats.append(p_hat)
                    enews.append({"e1": e_new.astype(jnp.dtype(dq.ef_dtype))}
                                 if dq.error_feedback else None)
                phat = jax.tree.unflatten(treedef, phats)
                enew = (jax.tree.unflatten(treedef, enews)
                        if dq.error_feedback else None)
            return (phat, enew, new_sw, grads,
                    metrics.get("loss", jnp.zeros(())), col.sums())

        prev_g = state.prev_grad
        ef = state.ef if dq.error_feedback else None
        phat_w, ef_w, sched_w, grads_w, loss_w, obs_sums_w = jax.vmap(
            worker,
            in_axes=(0, 0 if ef is not None else None, 0, 0, 0, 0),
        )(prev_g, ef, state.sched, batch_w, widx, mask_vec)

        new_m, new_v, new_prev_update = state.m, state.v, state.prev_update
        new_ef = state.ef
        if exchanging:
            with OBS.device_span("exchange", spans):
                qhat = jax.tree.map(lambda x: jnp.mean(x, axis=0), phat_w)
                if has_part:
                    scale = W / n_part
                    qhat = jax.tree.map(
                        lambda q: (q * scale).astype(q.dtype), qhat)
            with OBS.device_span("apply", spans):
                new_params, new_m, new_v, new_prev_update = (
                    self._server_update(state, qhat))
            if dq.error_feedback and ef_w is not None:
                new_ef = jax.tree.map(
                    lambda o, n: n.astype(o.dtype), state.ef, ef_w)
        else:
            new_params = state.params

        new_prev_grad = state.prev_grad
        if state.prev_grad is not None:
            new_prev_grad = jax.tree.map(lambda o, g: g.astype(o.dtype),
                                         state.prev_grad, grads_w)
        new_sched = state.sched
        if sched_w is not None:
            new_sched = jax.tree.map(lambda o, n: n.astype(o.dtype),
                                     state.sched, sched_w)

        new_state = DQState(
            step=state.step + 1, params=new_params, prev_grad=new_prev_grad,
            prev_update=new_prev_update, ef=new_ef, m=new_m, v=new_v,
            sched=new_sched)
        gn = _global_norm(grads_w)
        en = _global_norm(new_ef) if new_ef is not None else jnp.zeros(())
        if schedule == "delayed":
            st_now = sched_c.staleness_now(state.step, new_sched)
            st_max, st_mean = jnp.max(st_now), jnp.mean(st_now)
        else:
            st_max = st_mean = jnp.zeros(())
        out_metrics = {"loss": jnp.mean(loss_w),
                       "grad_norm": gn, "error_norm": en,
                       "staleness_max": st_max,
                       "staleness_mean": st_mean}
        if obs_spec.on:
            # per-worker sums come out of the vmap stacked — the axis-0
            # sum is the fleet reduction (the shard_map path's psum)
            sums = jax.tree.map(lambda x: jnp.sum(x, axis=0), obs_sums_w)
            if obs_spec.ef_norms:
                sums["e1_sq"], sums["e2_sq"] = OBS.ef_norms_sq(new_ef)
            if obs_spec.staleness:
                st_vec = (sched_c.staleness_now(state.step, new_sched)
                          if schedule == "delayed"
                          else jnp.zeros((W,), jnp.float32))
                sums["staleness_hist"] = OBS.staleness_hist(
                    st_vec, self._obs_bins())
            out_metrics["obs"] = OBS.finalize(obs_spec, sums, col.counts(),
                                              W, 0)
        return StepOutput(state=new_state, metrics=out_metrics)

    # ------------------------------------------------------------------ #
    def _worker_body(self, state, batch, key, widx_arr, plans, axes, squeeze,
                     do_exchange=True):
        """Per-worker computation. When `squeeze`, per-worker leaves arrive
        with a leading axis of local size 1 (their worker shard).
        `widx_arr` is the (local size 1) slice of arange(W) sharded over
        the worker axes, or None outside shard_map."""
        dq = self.dq
        sched_c = self.strategy.schedule
        W = self.n_workers
        eta = dq.lr
        schedule = sched_c.kind

        def takew(tree):
            if tree is None or not squeeze:
                return tree
            return jax.tree.map(lambda x: x[0], tree)

        def putw(tree):
            if tree is None or not squeeze:
                return tree
            return jax.tree.map(lambda x: x[None], tree)

        # participation mask from the shared (pre-worker-fold) key so every
        # worker draws the same round permutation.
        part_setup = self.strategy.participation.round_setup(
            key, state.step, W, sched_c.period)

        widx = None
        if axes:
            widx = widx_arr[0]
            key = jax.random.fold_in(key, widx)
        kfield, kq = jax.random.split(jax.random.fold_in(key, state.step))

        params = state.params
        prev_grad = takew(state.prev_grad)
        ef = takew(state.ef)
        sched_st = takew(state.sched)
        fsdp_st = takew(state.fsdp)
        # pending_buf: the raw delayed-schedule buffer (ring for τ>1);
        # pending: the message on the wire THIS step (its oldest slot, or
        # this worker's τ_m pull slot under a heterogeneous tau_vector)
        pending_buf, pending = sched_c.wire_head(sched_st, widx)
        part = None
        plan_sel = None
        if part_setup is not None and widx is not None:
            part = (part_setup[0][widx], part_setup[1])
            if self.adaptive:
                # the round's participant count, as DATA: the PlanFamily
                # member is a gather on this index, so a different round
                # size is a different table row, never a retrace.
                from repro.sched.participation import round_count
                plan_sel = round_count(part_setup[0]) - 1

        # ---------- overlapped exchange start (delayed × overlap) --------- #
        # The delayed wire head is pure carried state (ring slot, EF
        # residuals, kq, participation mask) — none of it depends on this
        # round's field output — so with exchange.overlap the compress +
        # wire collectives are ISSUED here, before the field compute, and
        # only their local post-processing is emitted at consumption time
        # below. XLA's latency-hiding scheduler can then run the wire ops
        # concurrently with generator/discriminator work (DESIGN.md §13).
        # Identical per-op operands → numerically bit-exact with the
        # blocking (overlap=False) lowering.
        col = self._obs_collector(state.params)
        finish_xchg = None
        if (self.strategy.exchange.overlap and sched_c.overlappable
                and pending is not None):
            with OBS.device_span("exchange", self._obs_spans):
                if self.fsdp:
                    # fsdp overlap: only the gradient reduce-scatter is
                    # issued here — the optimizer + all-gather + unpack
                    # depend on the reduced shard and wait in the thunk.
                    finish_xchg = self._start_fsdp(
                        pending, ef, fsdp_st, params, state.step, kq,
                        axes, col=col)
                else:
                    finish_xchg = self._start_exchange_tree(
                        pending, ef, plans, kq, axes, part=part,
                        plan_sel=plan_sel, col=col, eager=False)

        # ---------- extrapolation to w_{t-1/2} ---------------------------- #
        # delayed schedule: w_{t-1} is τ applied updates stale, so the OMD
        # lookahead additionally subtracts the SUM of the worker's pending
        # (in-flight) messages as the staleness-correction proxy for the
        # τ outstanding q̂'s (DESIGN.md §8).
        stale = sched_c.staleness_correction(pending_buf, dq.message, eta,
                                             widx)
        ef_leaf_tree = ef["leaf"] if (self.bucketed and ef is not None) else ef
        if dq.optimizer == "omd":
            if dq.extrapolation == "local":
                e_term = ef_leaf_tree if dq.error_feedback else None

                def extrap(w, g_prev, e_leaf, s):
                    upd = eta * g_prev
                    if e_leaf is not None and "e1" in e_leaf:
                        upd = upd + e_leaf["e1"].astype(w.dtype)
                    if s is not None:
                        upd = upd + s.astype(w.dtype)
                    return w - upd.astype(w.dtype)

                leaves_p, tdp = jax.tree.flatten(params)
                gl = tdp.flatten_up_to(prev_grad)
                el = (tdp.flatten_up_to(e_term) if e_term is not None
                      else [None] * len(leaves_p))
                sl = (tdp.flatten_up_to(stale) if stale is not None
                      else [None] * len(leaves_p))
                w_half = jax.tree.unflatten(
                    tdp, [extrap(w, g, e, s)
                          for w, g, e, s in zip(leaves_p, gl, el, sl)])
            else:  # global: lookahead with the previously applied update
                upd_tree = state.prev_update
                if stale is not None:
                    upd_tree = jax.tree.map(lambda u, s: u + s.astype(u.dtype),
                                            upd_tree, stale)
                w_half = jax.tree.map(
                    lambda w, u: w - u.astype(w.dtype),
                    params, upd_tree,
                )
        else:
            w_half = params  # adam/oadam/sgd evaluate at current params

        # ---------- local stochastic field -------------------------------- #
        with OBS.device_span("field", self._obs_spans):
            grads, metrics = self.field_fn(w_half, batch, kfield)

        # ---------- message + schedule dataflow --------------------------- #
        if dq.message == "update" and dq.optimizer == "omd":
            message = jax.tree.map(lambda g: (eta * g).astype(jnp.float32), grads)
        else:
            message = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        # schedule dataflow — one component method shared with the vmap
        # path: accumulate (local_k), ring-shift + version advance
        # (delayed), or pass the fresh message through (every_step).
        exch_msg, new_sched = sched_c.fold(
            sched_st, message, pending, do_exchange, state.step,
            part[0] if part is not None else None, _tree_zeros, widx)

        # ---------- exchange + server-side update ------------------------- #
        new_fsdp = fsdp_st
        if exch_msg is not None and self.fsdp:
            # fsdp fuses exchange and apply: reduce-scatter → shard-owner
            # optimizer → all-gather, one pass per bucket (DESIGN.md §15)
            with OBS.device_span("exchange", self._obs_spans):
                fin = (finish_xchg if finish_xchg is not None
                       else self._start_fsdp(exch_msg, ef, fsdp_st, params,
                                             state.step, kq, axes,
                                             col=col))
            with OBS.device_span("apply", self._obs_spans):
                new_params, new_ef, new_fsdp = fin()
            new_m, new_v, new_prev_update = state.m, state.v, state.prev_update
        elif exch_msg is not None:
            with OBS.device_span("exchange", self._obs_spans):
                if finish_xchg is not None:
                    # overlap: for delayed, fold returns the wire head the
                    # start above already put on the wire — consume it.
                    qhat, new_ef = finish_xchg()
                else:
                    qhat, new_ef = self._exchange_tree(
                        exch_msg, ef, plans, kq, axes, part=part,
                        plan_sel=plan_sel, col=col)
            with OBS.device_span("apply", self._obs_spans):
                new_params, new_m, new_v, new_prev_update = (
                    self._server_update(state, qhat))
        else:
            new_params = params
            new_m, new_v, new_prev_update = state.m, state.v, state.prev_update
            new_ef = ef

        new_prev_grad = None
        if state.prev_grad is not None:
            new_prev_grad = jax.tree.map(
                lambda o, g: g.astype(o.dtype), prev_grad, grads
            )

        # ---------- metrics ------------------------------------------------ #
        gn = _global_norm(grads)
        en = _global_norm(new_ef) if new_ef is not None else jnp.zeros(())
        loss = metrics.get("loss", jnp.zeros(()))
        st_now = sched_c.staleness_now(state.step, new_sched)
        st_max = st_mean = st_now
        if axes:
            loss = jax.lax.pmean(loss, axes)
            gn = jax.lax.pmean(gn, axes)
            en = jax.lax.pmean(en, axes)
            st_max = jax.lax.pmax(st_now, axes)
            st_mean = jax.lax.pmean(st_now, axes)

        obs_spec = self.obs_spec
        obs_out = None
        if obs_spec.on:
            # fleet reduction: sums (not means) across workers, so the
            # δ̂ ratio and moment denominators weigh every worker's
            # elements once and masked participation rounds drop out
            sums = col.sums()
            if obs_spec.ef_norms:
                sums["e1_sq"], sums["e2_sq"] = OBS.ef_norms_sq(new_ef)
            if obs_spec.staleness:
                sums["staleness_hist"] = OBS.staleness_hist(
                    st_now, self._obs_bins())
            if axes:
                sums = jax.tree.map(lambda x: jax.lax.psum(x, axes), sums)
            obs_out = OBS.finalize(obs_spec, sums, col.counts(), W,
                                   col.n_buckets if col.enabled else 0)

        new_state = DQState(
            step=state.step + 1,
            params=new_params,
            prev_grad=putw(new_prev_grad),
            prev_update=new_prev_update,
            ef=putw(new_ef),
            m=new_m,
            v=new_v,
            sched=putw(new_sched),
            fsdp=putw(new_fsdp),
        )
        out_metrics = {"loss": loss, "grad_norm": gn, "error_norm": en,
                       "staleness_max": st_max, "staleness_mean": st_mean}
        if obs_out is not None:
            out_metrics["obs"] = obs_out
        return StepOutput(state=new_state, metrics=out_metrics)

    # ------------------------------------------------------------------ #
    # (the schedule/participation dataflow helpers live on the strategy
    # components — Schedule.wire_head/fold/staleness_correction and
    # Participation.round_setup — shared by both SPMD paths.)
    # ------------------------------------------------------------------ #
    def _server_update(self, state, qhat):
        """Apply the averaged message q̂ on (replicated) server state.
        Shared by the shard_map and vmap paths."""
        dq = self.dq
        eta = dq.lr
        params = state.params
        new_m, new_v, new_prev_update = state.m, state.v, state.prev_update
        if dq.optimizer == "omd":
            if dq.message == "update":
                update = qhat
            else:
                update = jax.tree.map(lambda q: eta * q, qhat)
            new_params = jax.tree.map(
                lambda w, u: w - u.astype(w.dtype), params, update
            )
            if dq.extrapolation == "global":
                new_prev_update = update
        elif dq.optimizer in ("adam", "oadam"):
            # bias correction counts applied updates, not raw steps — with
            # local_k this runs only at round ends ((step+1) % K == 0).
            t = ((state.step + 1)
                 // self.strategy.schedule.period).astype(jnp.float32)
            b1, b2 = dq.beta1, dq.beta2
            new_m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.m, qhat)
            new_v = jax.tree.map(
                lambda v, g: b2 * v + (1 - b2) * jnp.square(g), state.v, qhat
            )
            bc1 = 1.0 - b1**t
            bc2 = 1.0 - b2**t
            direction = jax.tree.map(
                lambda m, v: (m / bc1) / (jnp.sqrt(v / bc2) + dq.eps),
                new_m, new_v,
            )
            direction = self._scale_groups(direction)
            if dq.optimizer == "oadam":
                # optimistic Adam: w ← w − η (2 d_t − d_{t−1})
                new_params = jax.tree.map(
                    lambda w, d, dp: w
                    - (eta * (2.0 * d - dp)).astype(w.dtype),
                    params, direction, state.prev_update,
                )
                new_prev_update = direction
            else:
                new_params = jax.tree.map(
                    lambda w, d: w - (eta * d).astype(w.dtype), params, direction
                )
        elif dq.optimizer == "sgd":
            new_params = jax.tree.map(
                lambda w, q: w - (eta * q).astype(w.dtype), params, qhat
            )
        else:
            raise ValueError(dq.optimizer)
        return new_params, new_m, new_v, new_prev_update

    # ------------------------------------------------------------------ #
    def _exchange_tree(self, message, ef, plans, key, axes, part=None,
                       plan_sel=None, col=None):
        """Blocking exchange: start + immediate finish. The eager start
        keeps per-leaf/per-bucket op emission order identical to the
        pre-split API, so every_step/local_k (and overlap=False delayed)
        compile to bit-identical graphs."""
        return self._start_exchange_tree(
            message, ef, plans, key, axes, part=part,
            plan_sel=plan_sel, col=col, eager=True)()

    def _start_exchange_tree(self, message, ef, plans, key, axes, part=None,
                             plan_sel=None, col=None, eager=True):
        """Issue the exchange's compress + wire collectives and return a
        finish thunk yielding (q̂, new_ef) — the tree-level face of the
        split-phase contract (core.exchange.start/finish, DESIGN.md §13).

        ``eager=True``: each leaf/bucket is finished as soon as it is
        started (the blocking graphs). ``eager=False``: every start is
        emitted before the thunk is built, and all local post-processing
        (decompress, unpack, participation rescale + EF merge) waits in
        the thunk — the caller puts field compute between the two so the
        scheduler can hide the wire time. Observability records happen
        at finish time in lazy mode; collector records are pure
        observers, so the round's numbers are unchanged."""
        if col is None:
            col = OBS.NullCollector()
        if part is not None:
            return self._start_with_participation(
                message, ef, plans, key, axes, part, plan_sel, col, eager)
        if self.bucketed:
            return self._start_bucketed(message, ef, plans, key, axes,
                                        plan_sel=plan_sel, col=col,
                                        eager=eager)
        dq = self.dq
        comp = self.compressor
        exch_c = self.strategy.exchange
        W = self.n_workers
        leaves, treedef = jax.tree.flatten(message)
        plan_leaves = treedef.flatten_up_to(plans)
        if ef is None:
            ef_leaves = [
                X.ef_state_zeros(pl, l.shape, jnp.dtype(dq.ef_dtype), W, False)
                for pl, l in zip(plan_leaves, leaves)
            ]
        else:
            ef_leaves = treedef.flatten_up_to(ef)
            ef_leaves = [e if e is not None else {} for e in ef_leaves]

        done, handles = [], []
        for i, (p, pl, e) in enumerate(zip(leaves, plan_leaves, ef_leaves)):
            k = jax.random.fold_in(key, i)
            if not axes:  # single worker: exchange degenerates to (EF-)compress
                q1, ne1 = self._single_worker_leaf(comp, pl, p, e, k)
                h = X.ExchangeHandle(pl["strategy"],
                                     lambda q=q1, ne=ne1: (q, ne))
            else:
                h = exch_c.start(comp, pl, p, e, k, W, dq.error_feedback)
            if eager:
                q, ne = exch_c.finish(h)
                if col.enabled:
                    col.leaf(p, *_obs_op_err(p, e, ne))
                done.append((q, ne))
            else:
                handles.append(h)

        def finish():
            pairs = done if eager else [exch_c.finish(h) for h in handles]
            out, new_ef = [], []
            for (q, ne), p, e in zip(pairs, leaves, ef_leaves):
                if not eager and col.enabled:
                    col.leaf(p, *_obs_op_err(p, e, ne))
                out.append(q)
                new_ef.append(ne if ne else None)
            qhat = jax.tree.unflatten(treedef, out)
            if ef is None and not dq.error_feedback and not exch_c.owner_ef:
                return qhat, None
            return qhat, jax.tree.unflatten(treedef, new_ef)

        return finish

    def _start_with_participation(self, message, ef, plans, key, axes,
                                  part, plan_sel, col, eager):
        """Partial participation (sched.participation, DESIGN.md §5.3):
        this worker's message and worker-side residual are masked to zero
        at START when it sits the round out — every registry compressor
        maps 0 to a zero payload, so masked workers ride through the
        unmodified collectives contributing nothing. At FINISH the
        averaged q̂ is rescaled from 1/W to 1/n_participants (a static
        constant), and non-participants fold the would-have-been message
        into their EF residual instead. ``plan_sel`` (adaptive
        PlanFamily) rides through to the bucketed exchange, which
        re-spends the absent workers' byte budget on finer quantization
        for the reporting ones (DESIGN.md §10).
        """
        mask, n_part = part  # mask: this worker's 0/1 flag; n_part: static
        W = self.n_workers
        leaves, treedef = jax.tree.flatten(message)
        msg_in = jax.tree.unflatten(treedef, [l * mask for l in leaves])

        def mask_e1(tree):
            out = []
            for e in treedef.flatten_up_to(tree):
                if e and "e1" in e:
                    e = dict(e)
                    e["e1"] = e["e1"] * mask.astype(e["e1"].dtype)
                out.append(e)
            return jax.tree.unflatten(treedef, out)

        if ef is None:
            ef_in = None
        elif self.bucketed:
            ef_in = {"leaf": mask_e1(ef["leaf"]), "bucket": ef["bucket"]}
        else:
            ef_in = mask_e1(ef)

        inner = self._start_exchange_tree(msg_in, ef_in, plans, key, axes,
                                          plan_sel=plan_sel, col=col,
                                          eager=eager)

        def finish():
            qhat, new_ef = inner()
            scale = W / n_part
            qhat = jax.tree.map(lambda q: (q * scale).astype(q.dtype), qhat)

            if not self.dq.error_feedback or ef is None:
                return qhat, new_ef
            # EF merge: participants keep the exchange's residual, the
            # rest accumulate the unsent message on top of their old one.
            old_leaf = ef["leaf"] if self.bucketed else ef
            new_leaf = new_ef["leaf"] if self.bucketed else new_ef
            olds = treedef.flatten_up_to(old_leaf)
            news = [dict(n) if n else n
                    for n in treedef.flatten_up_to(new_leaf)]
            for m_leaf, o, n in zip(leaves, olds, news):
                if o and "e1" in o:
                    keep = o["e1"].astype(jnp.float32) + m_leaf
                    n["e1"] = (mask * n["e1"].astype(jnp.float32)
                               + (1.0 - mask) * keep).astype(o["e1"].dtype)
            merged = jax.tree.unflatten(treedef, news)
            if self.bucketed:
                return qhat, {"leaf": merged, "bucket": new_ef["bucket"]}
            return qhat, merged

        return finish

    def _single_worker_leaf(self, comp, plan, p, e, key):
        from .error_feedback import compress_with_ef

        if plan["strategy"] == "exact" or comp.name == "identity":
            return p, dict(e)
        e1 = e.get("e1", jnp.zeros_like(p))
        _, p_hat, e_new = compress_with_ef(
            comp, p, e1, key, use_ef=self.dq.error_feedback
        )
        ne = dict(e)
        if self.dq.error_feedback:
            ne["e1"] = e_new
        return p_hat, ne

    # ------------------------------------------------------------------ #
    # repro.comm flat-bucket fast path (DESIGN.md §3)
    # ------------------------------------------------------------------ #
    def _start_bucketed(self, message, ef, plans, key, axes, plan_sel=None,
                        col=None, eager=True):
        """Exchange over bucket views: unsharded leaves are packed into a
        handful of flat, worker-divisible arrays (one collective each, per-
        bucket compressor from the comm planner); sharded leaves keep the
        per-tensor path. EF: e1 is packed/unpacked alongside the message so
        the per-leaf residual tree stays intact; two_phase owner error e2
        lives per-bucket under ef["bucket"].

        Split phase: start = pack + per-bucket compress + wire
        collectives (and the skipped leaves' starts, in lazy mode);
        finish = decompress, unpack_into, EF reassembly.

        ``plan_sel`` (traced, = round participant count − 1) selects the
        adaptive PlanFamily member: every family member shares one payload
        layout, so the per-bucket compressor becomes a `TracedQuant` whose
        level count is a gather from the family's jit-static stacked
        bit-width table — branch-free, and a different round size is new
        data, not a new trace. ``plan_sel=None`` (full participation, or
        a non-adaptive strategy) keeps the static per-bucket compressors,
        which is byte- and bit-identical to the pre-family behavior."""
        from repro.comm import buckets as B

        if col is None:
            col = OBS.NullCollector()
        dq = self.dq
        W = self.n_workers
        exch_c = self.strategy.exchange
        ef_dtype = jnp.dtype(dq.ef_dtype)
        layout, cplan = self._comm(message)
        family = self._family(message)
        levels_tab = None
        if (plan_sel is not None and family is not None
                and family.n_distinct > 1):
            # (M, n_buckets) level counts, stacked once at trace time
            levels_tab = jnp.asarray(family.levels_table(), jnp.float32)
            family_block = C.get(family.base_compressor).per_block
        leaves, treedef = jax.tree.flatten(message)
        plan_leaves = treedef.flatten_up_to(plans)

        leaf_ef = ef["leaf"] if ef is not None else None
        bucket_ef = ef["bucket"] if ef is not None else {}
        if leaf_ef is None:
            ef_leaves = [{}] * len(leaves)
        else:
            ef_leaves = [e if e is not None else {}
                         for e in treedef.flatten_up_to(leaf_ef)]

        # ---- buckets: start = compress + wire collectives ----------------- #
        flats = B.pack(layout, leaves)
        e1_flats = None
        if dq.error_feedback:
            e1_leaves = [
                e.get("e1", jnp.zeros(l.shape, ef_dtype))
                for l, e in zip(leaves, ef_leaves)
            ]
            e1_flats = B.pack(layout, e1_leaves)

        out_flats, new_e1_flats, new_bucket_ef = [], [], {}

        def finish_bucket(b, plan_b, est, h):
            q, ne = exch_c.finish(h)
            if col.enabled:
                col.bucket(b.bid, flats[b.bid],
                           *_obs_op_err(flats[b.bid], est, ne))
            out_flats.append(q)
            if dq.error_feedback:
                new_e1_flats.append(ne.get("e1", est.get("e1")))
            if X.plan_has_owner_ef(plan_b):
                new_bucket_ef[str(b.bid)] = {"e2": ne["e2"].astype(ef_dtype)}

        started = []
        for b, assign in zip(layout.buckets, cplan.assignments):
            if levels_tab is not None:
                comp_b = C.TracedQuant(levels_tab[plan_sel, b.bid],
                                       per_block=family_block)
            else:
                comp_b = C.get(assign.compressor)
            plan_b = exch_c.bucket_plan(b.size, W)
            est = {}
            if dq.error_feedback:
                est["e1"] = e1_flats[b.bid]
            if X.plan_has_owner_ef(plan_b):
                est["e2"] = (bucket_ef[str(b.bid)]["e2"]
                             if str(b.bid) in bucket_ef
                             else jnp.zeros((b.size // max(W, 1),), ef_dtype))
            k = jax.random.fold_in(key, 100_000 + b.bid)
            if not axes:
                q1, ne1 = self._single_worker_leaf(comp_b, plan_b,
                                                   flats[b.bid], est, k)
                h = X.ExchangeHandle(plan_b["strategy"],
                                     lambda q=q1, ne=ne1: (q, ne))
            else:
                h = exch_c.start(comp_b, plan_b, flats[b.bid], est, k, W,
                                 dq.error_feedback)
            if eager:
                finish_bucket(b, plan_b, est, h)
            else:
                started.append((b, plan_b, est, h))

        # ---- skipped (sharded) leaves keep the per-tensor path ------------ #
        base_comp = self.compressor

        def start_skipped(s):
            k = jax.random.fold_in(key, s.index)
            if not axes:
                q1, ne1 = self._single_worker_leaf(
                    base_comp, plan_leaves[s.index], leaves[s.index],
                    ef_leaves[s.index], k)
                return X.ExchangeHandle(plan_leaves[s.index]["strategy"],
                                        lambda q=q1, ne=ne1: (q, ne))
            return exch_c.start(
                base_comp, plan_leaves[s.index], leaves[s.index],
                ef_leaves[s.index], k, W, dq.error_feedback)

        skipped_started = []
        if not eager:
            skipped_started = [(s, start_skipped(s)) for s in layout.skipped]

        def finish():
            for item in started:  # lazy: buckets' local post-processing
                finish_bucket(*item)
            out_leaves = B.unpack_into(layout, out_flats, leaves)
            if dq.error_feedback:
                new_e1_leaves = B.unpack_into(layout, new_e1_flats,
                                              e1_leaves)
            skipped_new = {}
            # eager keeps the historical order: start+finish each skipped
            # leaf AFTER the bucket unpack, one leaf at a time
            pairs = (skipped_started if not eager
                     else ((s, start_skipped(s)) for s in layout.skipped))
            for s, h in pairs:
                q, ne = exch_c.finish(h)
                if col.enabled:
                    col.leaf(leaves[s.index],
                             *_obs_op_err(leaves[s.index],
                                          ef_leaves[s.index], ne))
                out_leaves[s.index] = q
                skipped_new[s.index] = ne if ne else None

            qhat = jax.tree.unflatten(treedef, out_leaves)
            if ef is None and not dq.error_feedback and not exch_c.owner_ef:
                return qhat, None

            in_bucket = {s.index for b in layout.buckets for s in b.slots}
            new_leaf_ef = []
            for i in range(len(leaves)):
                if i in skipped_new:
                    new_leaf_ef.append(skipped_new[i])
                elif i in in_bucket and dq.error_feedback:
                    new_leaf_ef.append({"e1": new_e1_leaves[i]})
                else:
                    new_leaf_ef.append(None)
            return qhat, {"leaf": jax.tree.unflatten(treedef, new_leaf_ef),
                          "bucket": new_bucket_ef}

        return finish

    # ------------------------------------------------------------------ #
    # compressed-gradient FSDP (DESIGN.md §15)
    # ------------------------------------------------------------------ #
    def _start_fsdp(self, message, ef, fb, params, step, key, axes,
                    col=None):
        """One fsdp round over the flat buckets: pack → per-bucket
        (compressed) reduce-scatter of the gradient message (worker-side
        e1 EF, per-bucket compressor from the comm planner) → shard-owner
        optimizer update on its (size/W,) flat shard (`_shard_update`) →
        quantized all-gather of the update shard (zero-2) or the updated
        parameter shard (zero-3) under `strategy.moments`' compressor
        with the owner-side "age" residual → unpack into the parameter
        tree.

        Split phase: this call issues the reduce-scatter wire
        collectives; everything downstream of the optimizer (which needs
        the reduced shard) waits in the returned thunk, so under
        exchange.overlap only the gradient leg hides behind compute —
        the return leg is sequential by data dependency. Returns a thunk
        yielding (new_params, new_ef, new_fsdp_state)."""
        from repro.comm import buckets as B

        if col is None:
            col = OBS.NullCollector()
        dq = self.dq
        W = self.n_workers
        exch_c = self.strategy.exchange
        mom_c = self.strategy.moments
        mom_comp = mom_c.get()
        ef_dtype = jnp.dtype(dq.ef_dtype)
        layout, cplan = self._comm(message)
        leaves, treedef = jax.tree.flatten(message)
        param_leaves = treedef.flatten_up_to(params)

        leaf_ef = ef["leaf"] if ef is not None else None
        if leaf_ef is None:
            ef_leaves = [{}] * len(leaves)
        else:
            ef_leaves = [e if e is not None else {}
                         for e in treedef.flatten_up_to(leaf_ef)]

        flats = B.pack(layout, leaves)
        e1_flats = None
        e1_leaves = None
        if dq.error_feedback:
            e1_leaves = [e.get("e1", jnp.zeros(l.shape, ef_dtype))
                         for l, e in zip(leaves, ef_leaves)]
            e1_flats = B.pack(layout, e1_leaves)
        w_flats = B.pack(layout, [p.astype(jnp.float32)
                                  for p in param_leaves])

        started = []
        for b, assign in zip(layout.buckets, cplan.assignments):
            comp_b = C.get(assign.compressor)
            est = {}
            if dq.error_feedback:
                est["e1"] = e1_flats[b.bid]
            k = jax.random.fold_in(key, 100_000 + b.bid)
            h = exch_c.start_reduce_scatter(
                comp_b, flats[b.bid], est, k, W, dq.error_feedback)
            started.append((b, est, h, jax.random.fold_in(k, 1)))

        def finish():
            new_w_flats, new_e1_flats, new_fb = [], [], {}
            for b, est, h, kag in started:
                q_shard, ne = exch_c.finish(h)
                if col.enabled:
                    col.bucket(b.bid, flats[b.bid],
                               *_obs_op_err(flats[b.bid], est, ne))
                if dq.error_feedback:
                    new_e1_flats.append(ne.get("e1", est.get("e1")))
                fb_b = fb[str(b.bid)] if fb is not None else {}
                ent, ag_in = self._shard_update(q_shard.astype(jnp.float32),
                                                fb_b, step)
                age = fb_b.get("age")
                if age is None:
                    age = jnp.zeros_like(ag_in)
                h_ag = exch_c.start_all_gather_shard(
                    mom_comp, ag_in, age.astype(jnp.float32), kag, W,
                    mom_c.error_feedback)
                full, new_age = exch_c.finish(h_ag)
                ent["age"] = new_age.astype(jnp.float32)
                new_fb[str(b.bid)] = ent
                if exch_c.zero_stage == 3:
                    new_w_flats.append(full)
                else:
                    new_w_flats.append(w_flats[b.bid] - full)
            out_w = B.unpack_into(layout, new_w_flats, param_leaves)
            new_params = jax.tree.unflatten(treedef, out_w)

            in_bucket = {s.index for b in layout.buckets for s in b.slots}
            new_leaf_ef = []
            if dq.error_feedback:
                new_e1_leaves = B.unpack_into(layout, new_e1_flats,
                                              e1_leaves)
            for i in range(len(leaves)):
                if i in in_bucket and dq.error_feedback:
                    new_leaf_ef.append({"e1": new_e1_leaves[i]})
                else:
                    new_leaf_ef.append(None)
            new_ef = ef
            if ef is not None:
                new_ef = {"leaf": jax.tree.unflatten(treedef, new_leaf_ef),
                          "bucket": {}}
            return new_params, new_ef, new_fb

        return finish

    def _shard_update(self, q_shard, fb_b, step):
        """The optimizer update on this worker's owned flat shard — the
        same elementwise math as `_server_update`, applied by the shard
        owner on its (size/W,) chunk of the reduce-scattered mean
        message. Returns (new shard state dict, the all-gather operand:
        the update shard for zero-2, the updated parameter shard for
        zero-3). Bucket padding stays at zero under every optimizer
        (zero gradient, zero moments ⇒ zero update)."""
        dq = self.dq
        eta = dq.lr
        ent = {}
        if dq.optimizer == "omd":
            update = q_shard if dq.message == "update" else eta * q_shard
        elif dq.optimizer in ("adam", "oadam"):
            t = ((step + 1)
                 // self.strategy.schedule.period).astype(jnp.float32)
            b1, b2 = dq.beta1, dq.beta2
            m = b1 * fb_b["m"] + (1 - b1) * q_shard
            v = b2 * fb_b["v"] + (1 - b2) * jnp.square(q_shard)
            bc1 = 1.0 - b1 ** t
            bc2 = 1.0 - b2 ** t
            direction = (m / bc1) / (jnp.sqrt(v / bc2) + dq.eps)
            ent["m"], ent["v"] = m, v
            if dq.optimizer == "oadam":
                update = eta * (2.0 * direction - fb_b["dir"])
                ent["dir"] = direction
            else:
                update = eta * direction
        elif dq.optimizer == "sgd":
            update = eta * q_shard
        else:
            raise ValueError(dq.optimizer)
        if self.strategy.exchange.zero_stage == 3:
            w = fb_b["w"] - update
            ent["w"] = w
            return ent, w
        return ent, update


def _is_ef_leaf(x):
    return isinstance(x, dict) and ("e1" in x or "e2" in x)


def _never(x):
    return False


def _obs_op_err(p, e, ne):
    """(compression operand, fresh residual) for obs collection: the
    operand is message + e_prev (exactly what the compressor saw, f32),
    the residual the leaf's new e1. Streams that never compress
    (exact/identity) keep their zero residual, so they read δ̂ = 1."""
    e1 = e.get("e1") if e else None
    op = p if e1 is None else p + e1.astype(jnp.float32)
    err = ne.get("e1") if ne else None
    return op, (jnp.zeros_like(p) if err is None else err)


def _global_norm(tree):
    leaves = [
        l for l in jax.tree.leaves(tree) if hasattr(l, "dtype")
    ]
    if not leaves:
        return jnp.zeros(())
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )

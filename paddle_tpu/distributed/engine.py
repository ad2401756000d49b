"""ParallelEngine: compiles an eager model + optimizer into ONE sharded
XLA train step over the hybrid mesh.

This is the TPU-native replacement for the reference's per-op dispatch
inside `fleet.distributed_model` training loops (reference call stack:
SURVEY.md §3.3 — Python-driven 1F1B + eager NCCL ops). Instead of
host-dispatching thousands of ops per step, the engine traces the whole
forward + tape-backward + fused optimizer update under
``jax.shard_map`` over the ``HybridCommunicateGroup`` mesh, so:

- every mp/dp/sharding/pp collective lowers to an XLA collective on ICI,
- XLA fuses/overlaps compute and comm (the reference does this by hand
  with comm streams + hooks, reducer.cc / sharding overlap),
- parameters live as global ``jax.Array``s physically sharded per their
  ``dist_attr`` PartitionSpec (set by the mpu/sharded layers), and the
  step donates them (buffer aliasing → ZeRO-style memory behavior).

The eager tape (autograd/engine.py) records on tracers, so
``loss.backward()`` inside the traced step emits the backward into the
same XLA program — the mechanism the reference approximates with
jit.to_static + PIR interpreter (SURVEY.md §3.4).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import collective as C
from . import failpoints as _fp
from ..autograd import engine as _ad
from ..core import rng as _rng
from ..core.compile_stats import CompileStats
from ..observability import commledger as _cl
from ..observability import flops as _flops
from ..observability import goodput as _gp
from ..observability import healthmon as _hm
from ..observability import memledger as _ml
from ..observability import moestats as _moestats
from ..observability.catalog import train_metrics as _train_metrics
from ..observability.trace import annotate as _annotate, span as _span
from ..tensor import Tensor


def _shard_map(f, mesh, in_specs, out_specs, check_rep=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


__all__ = ["ParallelEngine", "bind_params", "param_spec", "shard_module_params"]

# axes the token batch is sharded over. 'ep' rides here too: expert
# parallelism subdivides the data-parallel replicas (GShard/DeepSpeed-MoE
# deployment) — each ep rank sees its own token shard, MoE expert params
# shard over 'ep' (distinct per-rank grads, so ZeRO leaves them out and
# the grad mean skips the axis exactly like experts-over-dp).
_DATA_AXES = ("dp", "sharding", "ep")


def param_spec(p) -> P:
    """The PartitionSpec a tensor is sharded with (replicated default)."""
    da = getattr(p, "dist_attr", None)
    return da if isinstance(da, P) else P()


def _with_axis(spec: P, ndim: int, dim: int, axis: str) -> P:
    """``spec`` with mesh ``axis`` added as the sharding of dim ``dim``."""
    parts = list(spec) + [None] * (ndim - len(spec))
    parts[dim] = axis
    return P(*parts)


class _ZeroPlan:
    """ZeRO param/state sharding plan over the 'sharding' mesh axis.

    The reference partitions params greedily by size and hand-codes the
    reduce-scatter/broadcast traffic (dygraph_sharding_optimizer.py:224,
    group_sharded_stage3.py). Here the plan is declarative: each eligible
    parameter gets a shard *dim* (first dim divisible by the sharding
    degree and not already sharded by tp/pp), and the engine emits
    all_gather / psum_scatter on that dim inside the compiled step —
    XLA schedules and overlaps the traffic on ICI.

    Stage 1/2 ("os"/"os_g"): optimizer states (and the update math) are
    sharded; params stay replicated across 'sharding'.
    Stage 3 ("p_g_os"):   params are *stored* sharded and all-gathered
    just-in-time at forward entry (donated buffers keep persistent
    memory at shard size — per-device model-state bytes land at
    1/sharding_degree exactly, the memledger closed form). Selected by
    ``sharding_configs["sharding_stage"] = 3`` (the strategy surface),
    the per-param ``_zero3`` marker (group_sharded_parallel "p_g_os"),
    or quant_comm's param_gather (see ``store_sharded`` below). The
    gather runs through the comm_overlap bucket plan when one exists
    (grad_buckets.BucketPlan.gather — coalesced per signature bucket,
    the stacked-params seam as a scan_trips-exact lax.scan), else per
    parameter; the grads keep flowing through EXACTLY the stage-2
    reduce-scatter path, which is what makes stage-3 loss/params
    bit-match stage-2 (pinned by tests/test_zero_stage3.py).

    ``row_dims`` (the per-bucket ZeRO plan): {id(param): k} marking k
    leading stacked-layer dims the shard-dim search must skip — set
    when comm_overlap buckets the grad sync along the pp stacked-params
    seam (distributed/grad_buckets.py), so the reduce-scatter dim never
    collides with the layer-row axis the bucket scan chunks over. Only
    WHERE states shard moves; the update math is unchanged.

    ``store_sharded``: store EVERY plan entry's param sharded and
    all-gather at step entry (the stage-3 storage discipline) even at
    stage 1/2. Set when quant_comm's ``param_gather`` compresses the
    gather wire: the authoritative state must be the exact per-rank
    shard — a quantized post-update gather would otherwise either bake
    compression noise into the weights or leave device-divergent
    "replicated" copies that can't checkpoint (quant_comm.py
    quantized_param_gather docstring).
    """

    def __init__(self, mesh: Mesh, trainable, optimizer, row_dims=None,
                 store_sharded: bool = False):
        axis = getattr(optimizer, "state_partition_axis", None) \
            if optimizer is not None else None
        stage3 = any(getattr(p, "_zero3", False) for p in trainable)
        if (stage3 or store_sharded) and axis is None:
            axis = "sharding"
        self.axis = axis
        self.n = (mesh.shape[axis]
                  if axis is not None and axis in mesh.axis_names else 1)
        self.entries = {}
        if self.n <= 1:
            self.axis = None
            return
        for p in trainable:
            spec = param_spec(p)
            flat_spec = set()
            for ax in spec:
                flat_spec.update(ax if isinstance(ax, (tuple, list))
                                 else (ax,))
            # params already sharded over a data axis (MoE experts over dp)
            # have per-rank-distinct grads; the ZeRO scatter math below
            # assumes replicated grads, so leave them out of the plan
            if flat_spec & set(_DATA_AXES):
                continue
            shape = tuple(p._value.shape)
            start = (row_dims or {}).get(id(p), 0)
            for d in range(start, len(shape)):
                used = spec[d] if d < len(spec) else None
                if used is None and shape[d] % self.n == 0 \
                        and shape[d] >= self.n:
                    self.entries[id(p)] = (
                        d, getattr(p, "_zero3", False) or store_sharded)
                    break

    def entry(self, p):
        return self.entries.get(id(p)) if self.axis else None

    def state_spec(self, p) -> P:
        e = self.entry(p)
        if e is None:
            return param_spec(p)
        return _with_axis(param_spec(p), p._value.ndim, e[0], self.axis)

    def storage_spec(self, p) -> P:
        e = self.entry(p)
        if e is None or not e[1]:
            return param_spec(p)
        return _with_axis(param_spec(p), p._value.ndim, e[0], self.axis)


@contextlib.contextmanager
def bind_params(params: Sequence, values: Sequence):
    """Temporarily swap each Parameter's backing array (functional call).

    The analog of functorch-style functional_call; lets one model object
    serve both the eager path and the traced SPMD step.
    """
    saved = [p._value for p in params]
    saved_nodes = [(p._grad_node, p.grad) for p in params]
    try:
        for p, v in zip(params, values):
            p._value = v
            p._grad_node = None
            p.grad = None
        yield
    finally:
        for p, v, (n, g) in zip(params, saved, saved_nodes):
            p._value = v
            p._grad_node = n
            p.grad = g


def _mesh_data_axes(mesh: Mesh):
    return tuple(a for a in _DATA_AXES
                 if a in mesh.axis_names and mesh.shape[a] > 1)


def _batch_tokens(leaf_vals) -> int:
    """Tokens one (host-local) batch carries: the largest integer leaf
    (token ids [B, S] beat labels [B]); falls back to the leading dim of
    the first leaf (samples) for non-token workloads like vision."""
    tok = 0
    for v in leaf_vals:
        if getattr(v, "ndim", 0) >= 1 and \
                jnp.issubdtype(v.dtype, jnp.integer):
            tok = max(tok, int(np.prod(v.shape)))
    if tok == 0 and leaf_vals:
        v0 = leaf_vals[0]
        tok = int(v0.shape[0]) if getattr(v0, "ndim", 0) >= 1 else 1
    return tok


def _multiprocess(mesh: Mesh) -> bool:
    return jax.process_count() > 1


def global_put(value, mesh: Mesh, spec: P):
    """Place a host-replicated value as a global array sharded by spec.

    Single-process: plain device_put. Multi-process: every process holds
    the FULL value (deterministic init); each contributes its addressable
    shards (reference analog: broadcast_mp_parameters — here no traffic,
    the copy is local because the host already has the bytes).
    """
    sh = NamedSharding(mesh, spec)
    if not _multiprocess(mesh):
        return jax.device_put(value, sh)
    np_val = np.asarray(value)
    return jax.make_array_from_callback(np_val.shape, sh,
                                        lambda idx: np_val[idx])


def _globalize_batch(leaf_vals, b_specs, mesh: Mesh):
    """Multi-process: each process feeds its LOCAL batch (the
    DistributedBatchSampler contract); assemble global arrays whose
    data-axis shards are the per-process pieces."""
    if not _multiprocess(mesh):
        return leaf_vals
    from jax.experimental import multihost_utils as mh

    out = []
    for v, spec in zip(leaf_vals, b_specs):
        if spec == P() or all(s is None for s in spec):
            out.append(global_put(v, mesh, spec))
        else:
            out.append(mh.host_local_array_to_global_array(
                np.asarray(v), mesh, spec))
    return tuple(out)


def materialize_lazy_params(model, mesh: Optional[Mesh] = None,
                            spec_fn=None, seed: int = 0):
    """Materialize LazyGuard-built parameters directly at their sharding.

    Each parameter's windows are generated by the keyed shard-local
    initializer path (nn/initializer.py _generate_window): a process
    only ever materializes its addressable shards, so host+device bytes
    are O(shard) — the scalable replacement for full-host init +
    global_put (reference rank-0 broadcast:
    fleet/utils/hybrid_parallel_util.py:213). Deterministic in
    (seed, qualified parameter name, window offsets) — identical across
    processes with no communication.
    """
    import zlib

    from ..framework.lazy_init import LazySpec
    from ..nn.initializer import _generate_window

    base = jax.random.PRNGKey(seed)
    for name, p in model.named_parameters():
        lz = p._value
        if not isinstance(lz, LazySpec):
            continue
        key = jax.random.fold_in(base, zlib.crc32(name.encode()))
        shape, dtype, init = lz.shape, lz.dtype, lz.init
        if mesh is None:
            window = tuple(slice(0, s) for s in shape)
            p._value = _generate_window(init, shape, window, dtype, key)
            continue
        spec = spec_fn(p) if spec_fn is not None else param_spec(p)
        sh = NamedSharding(mesh, spec)

        def cb(idx, init=init, shape=shape, dtype=dtype, key=key):
            return np.asarray(_generate_window(init, shape, idx, dtype,
                                               key))

        p._value = jax.make_array_from_callback(shape, sh, cb)
    return model


def shard_module_params(model, mesh: Mesh):
    """Physically shard every parameter per its dist_attr (global arrays)."""
    materialize_lazy_params(model, mesh)
    for p in model.parameters():
        p._value = global_put(p._value, mesh, param_spec(p))
    return model


class ParallelEngine:
    """Compile model+optimizer into a donated, sharded train step.

    Usage::

        hcg = fleet.init(strategy)           # builds the hybrid mesh
        eng = ParallelEngine(model, opt, hcg.mesh)
        step = eng.train_step(lambda model, batch:
                              loss_fn(model(batch["x"]), batch["y"]))
        loss = step({"x": xb, "y": yb})      # one XLA execution
    """

    def __init__(self, model, optimizer=None, mesh: Optional[Mesh] = None,
                 comm_overlap: Optional[bool] = None,
                 comm_buffer_size_mb: Optional[float] = None,
                 mem_ledger: Optional[bool] = None,
                 quant_comm=None, sharding_stage: Optional[int] = None,
                 stage3_release_after_forward: Optional[bool] = None,
                 offload=None):
        import os

        from . import grad_buckets as _gb
        from . import host_offload as _ho
        from . import quant_comm as _qc

        self.model = model
        self.optimizer = optimizer
        if mesh is None:
            from . import fleet as _fleet

            hcg = _fleet.get_hybrid_communicate_group()
            mesh = hcg.mesh if hcg is not None else C.get_world_mesh()
        if mesh is None:
            C.init_parallel_env()
            mesh = C.get_world_mesh()
        self.mesh = mesh
        self.params: List = list(model.parameters())
        self.trainable: List = [p for p in self.params if p.trainable]
        self._seed = 0
        self._mesh_epoch = C.mesh_epoch()
        self._compiled: Dict[Any, Callable] = {}
        # compile-cache telemetry (same counters as the serving path):
        # a healthy train loop compiles each (shape, spec) signature
        # once and shows only cache hits in steady state — regressions
        # that force recompiles (e.g. an overlap path keyed on a traced
        # shape) surface here
        self.stats = CompileStats()
        # the tape's op nodes in the newest traced step's backward():
        # (took an explicit grad kernel, took the generic jax.vjp, which
        # runs the op's forward again). Written while tracing only.
        self.backward_nodes: Optional[Tuple[int, int]] = None
        # unified telemetry (observability/): per-step wall time, loss,
        # grad-norm, tokens/s, MFU, device memory, compile counters —
        # all host-side on fetched scalars, never inside the trace
        self._metrics = _train_metrics()
        # run-health watcher (observability/healthmon): rolling robust
        # spike/stall detection over the scalars the lagged fetch below
        # already pays for. PER-ENGINE windows — a fresh model's first
        # loss must never be judged against another run's converged
        # baseline — surfaced on /healthz via a weakref provider
        self._health = _hm.HealthMonitor()
        self._health.register_healthz("train_health")
        self._n_params_cfg = _flops.params_from_config(
            getattr(model, "config", None))
        self._stats_reported = (0, 0)    # (compiles, cache_hits) synced
        self._pending_scalars = None     # (loss_dev, gnorm_dev) lazy
        self._pending_found = None       # scaler found_inf of that step
        self._pending_moe = None         # MoE stats devices, same lag
        self._prev_step_entry = None
        # per-program static comm ledgers (observability/commledger):
        # filled when a program first traces, re-published every step
        self._ledgers: Dict[Any, Any] = {}
        self._last_key = None
        # per-program HBM memory ledgers (observability/memledger):
        # XLA memory_analysis of the SAME program, stored next to the
        # comm ledger. Analysis costs one extra trace + AOT compile
        # per program, so it is eager only behind the knob (ctor arg
        # or PADDLE_TPU_MEM_LEDGER=1); memory_ledger() computes on
        # demand either way from the per-key example args kept below.
        self._mem_on = (bool(int(os.environ.get(
            "PADDLE_TPU_MEM_LEDGER", "0") or 0))
            if mem_ledger is None else bool(mem_ledger))
        self._mem_ledgers: Dict[Any, Any] = {}
        self._mem_args: Dict[Any, Any] = {}
        # durable metrics time-series journal (observability/timeseries):
        # a background sampler snapshots the registry into
        # <dir>/metrics.jsonl every PADDLE_TPU_TIMESERIES_S seconds.
        # Pure host-side file IO on an existing snapshot — adds zero ops
        # to compiled programs, so compile caches stay flat.
        self.sampler = None
        ts_dir = os.environ.get("PADDLE_TPU_TIMESERIES_DIR")
        if ts_dir:
            from ..observability import timeseries as _ts
            try:
                self.sampler = _ts.attach_dir(ts_dir, interval_s=float(
                    os.environ.get("PADDLE_TPU_TIMESERIES_S", "5.0")))
            except (OSError, ValueError):
                self.sampler = None
        self._state_acct = None          # cached StateAccounting
        self._live_peak = 0              # live-bytes high-water mark
        self._last_tokens = 0
        self._last_step_seconds = 0.0
        self._last_dispatch_fresh = False
        # set by restore_checkpoint, cleared after the next dispatch:
        # the first execution after a cross-process restore can pay a
        # silent XLA-level relayout/recompile (loaded arrays' layouts
        # differ from compiled-step outputs) that the host-side key
        # cache never sees — goodput books that dispatch as compile
        # (warmup), and the health monitor's step-time baseline skips it
        self._post_restore_warmup = False
        # profile_exposed_comm() replays: suppress telemetry/counters
        # so offline attribution never pollutes the live metrics
        self._profiling = False
        # T3-style bucketed grad sync (distributed/grad_buckets.py):
        # knob from strategy.hybrid_configs["sharding_configs"], or the
        # explicit constructor override (tests / engines built without
        # fleet.init). Default off — the unbucketed tail sync.
        cfg_on, cfg_mb = _gb.strategy_config()
        self._overlap_on = bool(cfg_on if comm_overlap is None
                                else comm_overlap)
        self._overlap_mb = float(cfg_mb if comm_buffer_size_mb is None
                                 else comm_buffer_size_mb)
        # the pp stacked-params chunk seam: the natural bucketing grain
        # for pipelined models (PipelineLayer.grad_bucket_seam)
        self._seam_row_dims = None
        seam_fn = getattr(model, "grad_bucket_seam", None)
        if self._overlap_on and callable(seam_fn):
            self._seam_row_dims = {id(p): int(k) for p, k in seam_fn()}
        self._bucket_plan = None
        # quantized collectives (distributed/quant_comm.py): the
        # strategy.hybrid_configs["quant_comm"] sub-config, or the
        # explicit constructor override (a dict or QuantConfig). The
        # grad_sync half rides the comm_overlap bucket plan; the
        # mp_rings half is read by collective_matmul from the fleet
        # strategy directly.
        self._quant_cfg = (_qc.strategy_config() if quant_comm is None
                           else _qc.make_config(quant_comm))
        # per-bucket error-feedback residuals: f32 global arrays,
        # rank-distinct (dim 0 sharded over every mesh axis), created
        # lazily by _ensure_quant_state once the bucket plan exists and
        # carried through the compiled step as donated train state
        self._quant_residuals: Dict[str, Any] = {}
        self._quant_specs: Dict[str, P] = {}
        self._pending_qnorm = None
        # ZeRO sharding stage (distributed_strategy sharding_configs,
        # or the explicit constructor override): stage 3 stores every
        # plan entry's param shard-only and gathers just-in-time at
        # forward entry; stage3_release_after_forward picks the gather
        # grain (True = per signature bucket / seam scan through the
        # comm_overlap plan, False = per-parameter entry wave). Both
        # are exact data movement — same bytes on the wire, same
        # values, different node granularity.
        cfg_stage, cfg_rel = _gb.stage_config()
        self._sharding_stage = int(cfg_stage if sharding_stage is None
                                   else sharding_stage)
        self._stage3_release = bool(
            cfg_rel if stage3_release_after_forward is None
            else stage3_release_after_forward)
        self._zero = _ZeroPlan(
            mesh, self.trainable, optimizer,
            row_dims=self._seam_row_dims if self._overlap_on else None,
            store_sharded=bool(self._quant_cfg.enabled
                               and self._quant_cfg.param_gather)
            or self._sharding_stage >= 3)
        # host-memory offload tier (distributed/host_offload.py): the
        # strategy sharding_configs["offload"] sub-config, or the
        # explicit constructor override. When active, optimizer moments
        # / AMP masters / EF residuals (optionally stored param shards)
        # live on the host between steps and are prefetched per
        # signature bucket at dispatch — bit-exact, ledger-booked.
        self._offload = _ho.make_tier(
            offload if offload is not None else _ho.offload_config(),
            mesh)
        # LazyGuard-built params materialize straight into their (zero3-
        # aware) storage sharding: O(shard) bytes per process, no full-
        # size init anywhere
        materialize_lazy_params(model, mesh,
                                spec_fn=self._zero.storage_spec)
        for p in self.params:
            p._value = global_put(p._value, mesh, self._zero.storage_spec(p))

    # -- optimizer state management -------------------------------------
    def _ensure_opt_states(self):
        from . import host_offload as _ho

        opt = self.optimizer
        shapes = opt._state_shapes()
        states = []
        for p in self.trainable:
            st = opt._param_state(p, shapes)
            spec = self._zero.state_spec(p)
            # host-tier entries (HostState) already carry their live
            # sharding and re-place through the offload tier, never a
            # fresh global_put
            st = {k: global_put(v, self.mesh, spec)
                  if not _ho.is_host(v)
                  and v.shape == tuple(p._value.shape)
                  else v for k, v in st.items()}
            opt._states[id(p)] = st
            states.append(st)
            mw = opt._master_weights.get(id(p))
            if mw is not None and not _ho.is_host(mw):
                opt._master_weights[id(p)] = global_put(mw, self.mesh, spec)
        return states

    # -- sync-signature helpers (shared by train_step + quant state) -----
    def _sync_axes_env(self):
        mesh = self.mesh
        data_axes = _mesh_data_axes(mesh)
        sep_axes = tuple(a for a in ("sep",) if a in mesh.axis_names
                         and mesh.shape[a] > 1)
        pp_axes = tuple(a for a in ("pp",)
                        if getattr(self.model, "_pp_ownership", False)
                        and a in mesh.axis_names and mesh.shape[a] > 1)
        return data_axes, data_axes + sep_axes, pp_axes

    def _param_spec_axes(self, p):
        spec_axes = set()
        for ax in param_spec(p):
            if isinstance(ax, (tuple, list)):
                spec_axes.update(ax)
            elif ax is not None:
                spec_axes.add(ax)
        return spec_axes

    def _param_grad_axes(self, p, pp_axes):
        spec_axes = self._param_spec_axes(p)
        extra = tuple(a for a in pp_axes if a not in spec_axes)
        # sequence-parallel replicated params (LayerNorm etc.) see only
        # a seq shard per mp rank: their grads must psum over mp
        # (reference sequence_parallel_utils.py:156 allreduce hooks)
        if getattr(p, "sequence_parallel", False):
            extra += tuple(
                a for a in ("mp",) if a in self.mesh.axis_names
                and self.mesh.shape[a] > 1 and a not in spec_axes)
        return extra

    def _build_bucket_plan(self):
        """The deterministic comm_overlap bucket plan (None when the
        knob is off or nothing buckets) — same construction train_step
        performs, callable standalone so restore_checkpoint can size
        the quantization residual buffers before any step traced."""
        if not self._overlap_on:
            return None
        from . import grad_buckets as _gb

        data_axes, gmean_axes, pp_axes = self._sync_axes_env()
        return _gb.build_plan(
            self.trainable, self.mesh, self._zero, gmean_axes,
            data_axes, self._param_spec_axes,
            lambda p: self._param_grad_axes(p, pp_axes), param_spec,
            seam_row_dims=self._seam_row_dims,
            buffer_mb=self._overlap_mb)

    def _quant_grad_cfg(self):
        """The active grad-sync quantization config, or None. Rides
        the comm_overlap bucket plan: quantizing an unbucketed tail
        sync is not supported (the bucket is the chunk-lattice grain —
        ISSUE/EQuARX), so knob-on without comm_overlap is full
        precision."""
        cfg = self._quant_cfg
        return cfg if (cfg is not None and cfg.enabled
                       and cfg.grad_sync and self._overlap_on) else None

    def _ensure_quant_state(self):
        """Create (once) the per-bucket error-feedback residual
        buffers: f32 zeros at the bucket payload size, dim 0 sharded
        over EVERY >1 mesh axis so each rank owns exactly its local
        residual (compression error is rank-local state — it
        checkpoints shard-exact and never reshards meaningfully, like
        the per-process RNG streams)."""
        qcfg = self._quant_grad_cfg()
        if qcfg is None or not qcfg.error_feedback:
            return
        plan = self._build_bucket_plan()
        if plan is None:
            return
        axes = tuple(a for a in self.mesh.axis_names
                     if self.mesh.shape[a] > 1)
        prod = 1
        for a in axes:
            prod *= int(self.mesh.shape[a])
        spec = P(axes) if axes else P()
        for name, lshape in plan.residual_shapes().items():
            self._quant_specs[name] = spec
            if name in self._quant_residuals:
                continue
            gshape = (int(lshape[0]) * prod,) + tuple(lshape[1:])
            self._quant_residuals[name] = global_put(
                np.zeros(gshape, np.float32), self.mesh, spec)

    # -- the compiled step ----------------------------------------------
    def train_step(self, fn: Callable, batch_specs=None,
                   donate: bool = True, scaler=None):
        """Build ``step(batch) -> loss`` running fwd+bwd+update as one
        sharded XLA program. ``fn(model, batch)`` must return a scalar
        loss Tensor.

        ``scaler``: an ``amp.GradScaler`` — when given, the whole dynamic
        loss-scaling protocol runs INSIDE the compiled step (reference:
        hybrid_parallel_gradscaler.py — found_inf allreduced over every
        parallel group; here a traced pmax over all mesh axes, with the
        scale/counters as carried device state and the param/state update
        where-guarded so an overflow step is a true no-op).
        """
        mesh = self.mesh
        # 'sep' (context parallel) splits the *sequence*: grads of
        # replicated params are per-block partials, so they average over
        # sep exactly like a batch split (but batch dims are NOT sharded
        # over sep — the model slices seq itself)
        data_axes, gmean_axes, pp_axes = self._sync_axes_env()
        opt = self.optimizer
        params, trainable = self.params, self.trainable
        t_index = [i for i, p in enumerate(params) if p.trainable]

        self._ensure_opt_states()
        zero = self._zero
        pspecs = tuple(zero.storage_spec(p) for p in params)
        sspecs = tuple({k: zero.state_spec(p)
                        if v.shape == tuple(p._value.shape) else P()
                        for k, v in opt._states[id(p)].items()}
                       for p in trainable)

        use_scaler = scaler is not None and scaler.is_enable()

        def _step(pvals, svals, mvals, qvals, batch, lr, stepc, seed,
                  amp_in):
            with C.spmd_region():
                if gmean_axes:
                    # distinct RNG stream per data-parallel/sep rank (mp/pp
                    # ranks share a stream: replicated tensors must drop
                    # identically; mp-sharded ones use 'local_seed')
                    seed = seed * jnp.uint32(1000003) + \
                        C.axis_index(gmean_axes).astype(jnp.uint32)
                ctx = _rng.fork_traced(seed)
                ctx.__enter__()
                try:
                    return _step_inner(pvals, svals, mvals, qvals,
                                       batch, lr, stepc, amp_in)
                finally:
                    ctx.__exit__(None, None, None)

        def _spec_axes(p):
            return self._param_spec_axes(p)

        def _grad_axes(p):
            return self._param_grad_axes(p, pp_axes)

        def _shard_of(p, v, dim):
            idx = lax.axis_index(zero.axis)
            loc = v.shape[dim] // zero.n
            return lax.dynamic_slice_in_dim(v, idx * loc, loc, axis=dim)

        # T3-style bucketed grad sync (grad_buckets.py): a static plan
        # over (signature groups x size-targeted buckets, the stacked-
        # params seam as a lax.scan) built HERE from shapes/specs only —
        # nothing shape-derived reaches a compile key, and knob-off
        # leaves the unbucketed path byte-for-byte untouched
        bucket_plan = self._build_bucket_plan()
        self._bucket_plan = bucket_plan
        # quantized grad sync (quant_comm): rides the bucket plan; the
        # error-feedback residuals are per-bucket donated train state
        # (created once — zeros — then carried step to step)
        qcfg = self._quant_grad_cfg() if bucket_plan is not None \
            else None
        self._ensure_quant_state()
        # offload adoption: page the freshly-ensured state classes out
        # to the host tier before the first dispatch (the first
        # prefetch_step brings them back bucket-by-bucket)
        if self._offload is not None:
            self._offload.page_out_step(self, spawn=False)
        qspecs = dict(self._quant_specs)
        # quantized ZeRO param all-gather (stage 2 post-update, stage 3
        # entry): int8 wire with each rank's own exact shard spliced
        # back, so the authoritative shard path never sees noise
        pg_cfg = (self._quant_cfg
                  if self._quant_cfg.enabled
                  and self._quant_cfg.param_gather else None)

        def _zero_gather(v, dim):
            if pg_cfg is not None:
                from . import quant_comm as _qc

                return _qc.quantized_param_gather(v, (zero.axis,), dim,
                                                  pg_cfg)
            return C.t_all_gather(v, zero.axis, axis=dim, tiled=True)

        # stage-3 stored-sharded params (store_sharded plan entries):
        # gathered just-in-time at forward entry. With a bucket plan
        # and the release knob on, the gather goes through the SAME
        # signature buckets the backward scatters grads through
        # (grad_buckets.BucketPlan.gather — coalesced flat all_gather
        # per bucket, the stacked seam as a scan_trips-exact lax.scan,
        # quantized wire + own-shard splice under quant_comm's
        # param_gather); otherwise one per-parameter gather wave. Both
        # are exact data movement, so the wire bytes and the resulting
        # values are identical — only the node granularity differs.
        s3_gather = [(i, zero.entry(p)[0]) for i, p in enumerate(params)
                     if zero.entry(p) is not None and zero.entry(p)[1]]
        s3_bucketed = bool(s3_gather) and bucket_plan is not None \
            and self._stage3_release

        def _step_inner(pvals, svals, mvals, qvals, batch, lr, stepc,
                        amp_in):
            # ZeRO-3 params arrive as shards: all-gather for the forward,
            # but keep the stored shard for the optimizer update
            pshards = pvals
            pvals = list(pvals)
            if s3_gather:
                gathered = {}
                if s3_bucketed:
                    gathered = bucket_plan.gather(
                        {id(params[i]): pvals[i] for i, _ in s3_gather},
                        qcfg=pg_cfg)
                for i, d in s3_gather:
                    pid = id(params[i])
                    pvals[i] = gathered[pid] if pid in gathered \
                        else _zero_gather(pvals[i], d)
            pvals = tuple(pvals)
            # MoE routing telemetry: collect the traced expert-load /
            # drop stats each MoELayer records during the forward, to be
            # returned as extra (replicated) step outputs. The pipelined
            # path is excluded — its stage-masked scan records values the
            # gauges would misreport (observability/moestats.py).
            collect_moe = not getattr(self.model, "_pp_ownership", False)
            with bind_params(params, pvals):
                t_batch = jax.tree_util.tree_map(
                    lambda v: Tensor(v, stop_gradient=True), batch)
                if collect_moe:
                    _moestats.begin()
                try:
                    with _annotate("forward"):
                        loss = fn(self.model, t_batch)
                finally:
                    moe_recs = _moestats.drain() if collect_moe else []
                moe_tel = {}
                for li, st in enumerate(moe_recs):
                    load, routed = st["load"], st["routed"]
                    dropped, aux = st["dropped"], st["aux"]
                    if gmean_axes:
                        # token counts ADD over the batch-sharding axes
                        # (each rank routed its own token shard); the
                        # aux loss averages like the reported loss
                        load = C.t_psum(load, gmean_axes)
                        routed = C.t_psum(routed, gmean_axes)
                        dropped = C.t_psum(dropped, gmean_axes)
                        aux = C.t_pmean(aux, gmean_axes)
                    moe_tel[f"layer{li}"] = {
                        "load": load, "routed": routed,
                        "dropped": dropped, "aux": aux}
                if use_scaler:
                    scale_v, good_v, bad_v, tstep_v = amp_in
                    # cap the scale below the loss dtype's max so the
                    # backward seed can never itself overflow to inf
                    # (f16 max is 65504 — one doubling past the default
                    # 2^15 scale would cross it). Power-of-two cap keeps
                    # scale/unscale an exact mantissa-preserving round
                    # trip and leaves the default 2^15 init untouched.
                    ldt = loss._value.dtype
                    scale_cap = 2.0 ** 15 if ldt == jnp.float16 else 2.0 ** 62
                    scale_v = jnp.minimum(scale_v, jnp.float32(scale_cap))
                    # loss scaling = seeding the tape with `scale` instead
                    # of 1 (same grads as (loss*scale).backward(), one
                    # less op); the reported loss stays unscaled
                    with _annotate("backward"):
                        loss.backward(Tensor(
                            scale_v.astype(loss._value.dtype),
                            stop_gradient=True))
                else:
                    with _annotate("backward"):
                        loss.backward()
                self.backward_nodes = _ad.last_backward_nodes()
                raw_grads = {
                    id(p): (p.grad._value if p.grad is not None
                            else jnp.zeros_like(p._value))
                    for p in trainable}
                # comm_overlap: issue the per-bucket collectives (the
                # seam scan + the eager flat buckets) — bit-exact vs
                # the per-parameter path below (when quant_comm is off),
                # with the grad-norm sum-of-squares folded into the
                # bucket scan and the quantization error-feedback
                # residuals threaded through as train state
                with _annotate("grad_sync"):
                    if bucket_plan is not None:
                        bsync, bgsq, new_qr = bucket_plan.sync(
                            raw_grads, qcfg=qcfg, residuals=qvals)
                    else:
                        bsync, bgsq, new_qr = {}, None, {}
                    upd_in, grads = [], []
                    for i, p in zip(t_index, trainable):
                        g = raw_grads[id(p)]
                        e = zero.entry(p)
                        if id(p) in bsync:
                            g = bsync[id(p)]
                            if e is not None:
                                upd_in.append(
                                    mvals[i] if mvals and i in mvals
                                    else (pshards[i] if e[1]
                                          else _shard_of(p, pvals[i], e[0])))
                            else:
                                upd_in.append(mvals[i] if mvals and i in mvals
                                              else pvals[i])
                        elif e is not None:
                            # grad mean over plain dp, then reduce-scatter the
                            # sharding axis onto the owner shard (ZeRO)
                            dim = e[0]
                            dp_only = tuple(a for a in gmean_axes
                                            if a != zero.axis)
                            if dp_only:
                                g = C.t_pmean(g, dp_only)
                            psum_axes = _grad_axes(p)
                            if psum_axes:
                                g = C.t_psum(g, psum_axes)
                            if zero.axis in data_axes:
                                g = C.t_psum_scatter(
                                    g, zero.axis, scatter_dimension=dim,
                                    tiled=True) / zero.n
                            else:
                                g = _shard_of(p, g, dim)
                            upd_in.append(
                                mvals[i] if mvals and i in mvals
                                else (pshards[i] if e[1]
                                      else _shard_of(p, pvals[i], dim)))
                        else:
                            # params sharded over a data axis (MoE experts over
                            # dp) already receive their cross-rank grad sum via
                            # the all_to_all transpose — no pmean over that
                            # axis, only the global-batch mean rescale
                            spec_axes = _spec_axes(p)
                            pm = tuple(a for a in gmean_axes
                                       if a not in spec_axes)
                            if pm:
                                g = C.t_pmean(g, pm)
                            dup = 1
                            for a in gmean_axes:
                                if a in spec_axes:
                                    dup *= mesh.shape[a]
                            if dup > 1:
                                g = g / dup
                            psum_axes = _grad_axes(p)
                            if psum_axes:
                                g = C.t_psum(g, psum_axes)
                            upd_in.append(mvals[i] if mvals and i in mvals
                                          else pvals[i])
                        grads.append(g)
                amp_out = ()
                if use_scaler:
                    # traced found_inf, synced across EVERY parallel axis
                    # (the reference allreduces found_inf over mp/pp/
                    # sharding groups one by one; one pmax is equivalent)
                    finite = jnp.float32(1.0)
                    for g in grads:
                        finite = finite * jnp.all(
                            jnp.isfinite(g)).astype(jnp.float32)
                    found = 1.0 - finite
                    sync_axes = tuple(a for a in mesh.axis_names
                                      if mesh.shape[a] > 1)
                    if sync_axes:
                        found = C.t_pmax(found, sync_axes)
                    found_b = found > 0
                    # unscale in f32; zero overflowed grads so the (thrown
                    # away) update math stays NaN-free
                    inv = jnp.where(found_b, 0.0, 1.0 / scale_v)
                    grads = [(g.astype(jnp.float32) * inv).astype(g.dtype)
                             for g in grads]
                    # bias-correction step count advances only on applied
                    # steps (the reference skips optimizer.step entirely)
                    stepc = tstep_v + (1 - found.astype(jnp.int32))
                    # a skipped step must be a true no-op for the EF
                    # residuals too: they were updated from the scaled
                    # (possibly overflowed → NaN-decoding) grads, so
                    # roll them back exactly like params/moments
                    if new_qr:
                        new_qr = {k: jnp.where(found_b, qvals[k], v)
                                  for k, v in new_qr.items()}
                # global grad-norm (telemetry): local sum-of-squares,
                # psum'd over exactly the axes each grad is sharded on
                # (spec axes, + the ZeRO axis for scattered shards) so
                # replicated grads contribute once. Bucketed params
                # arrive pre-folded (one psum per signature group, the
                # seam contribution accumulated in the scan carry);
                # they were summed pre-unscale, so the scaler's inverse
                # applies squared (inv=0 on overflow matches the zeroed
                # per-param grads).
                gsq = jnp.float32(0.0)
                if bgsq is not None:
                    gsq = bgsq * (inv * inv if use_scaler
                                  else jnp.float32(1.0))
                for p, g in zip(trainable, grads):
                    if id(p) in bsync:
                        continue
                    loc = jnp.sum(jnp.square(g.astype(jnp.float32)))
                    axes_set = set(_spec_axes(p))
                    e = zero.entry(p)
                    if e is not None:
                        axes_set.add(zero.axis)
                    ax = tuple(a for a in axes_set
                               if a in mesh.axis_names
                               and mesh.shape[a] > 1)
                    if ax:
                        loc = C.t_psum(loc, ax)
                    gsq = gsq + loc
                gnorm = jnp.sqrt(gsq)
                with _annotate("optimizer"):
                    new_p, new_s = opt._fused_update(
                        tuple(upd_in), tuple(grads), tuple(svals), lr, stepc)
                if use_scaler:
                    new_p = tuple(jnp.where(found_b, u, n)
                                  for u, n in zip(upd_in, new_p))
                    new_s = tuple(
                        {k: jnp.where(found_b, old[k], ns[k])
                         if hasattr(ns[k], "shape") else ns[k]
                         for k in ns}
                        for old, ns in zip(svals, new_s))
                    if scaler.is_use_dynamic_loss_scaling():
                        # dynamic loss-scale bookkeeping, pure arithmetic
                        bad1 = jnp.where(found_b, bad_v + 1, 0)
                        good1 = jnp.where(found_b, 0, good_v + 1)
                        dec = found_b & (bad1 >= scaler._decr_every)
                        scale1 = jnp.where(
                            dec,
                            jnp.maximum(scale_v * scaler._decr_ratio, 1.0),
                            scale_v)
                        bad2 = jnp.where(dec, 0, bad1)
                        inc = (~found_b) & (good1 >= scaler._incr_every)
                        scale2 = jnp.minimum(
                            jnp.where(inc, scale1 * scaler._incr_ratio,
                                      scale1),
                            jnp.float32(scale_cap))
                        good2 = jnp.where(inc, 0, good1)
                    else:  # static scale: counters track, scale is fixed
                        scale2 = scale_v
                        good2 = jnp.where(found_b, 0, good_v + 1)
                        bad2 = jnp.where(found_b, bad_v + 1, 0)
                    amp_out = (scale2, good2, bad2, stepc,
                               found.astype(jnp.float32))
                out_p = list(pvals)
                out_m = dict(mvals) if mvals else {}
                for i, p, nv in zip(t_index, trainable, new_p):
                    e = zero.entry(p)
                    if e is not None and not e[1]:
                        # stage 1/2: params stay replicated — gather the
                        # updated shards (the reference's param broadcast,
                        # dygraph_sharding_optimizer.py:317; quantized
                        # wire + own-shard splice behind quant_comm's
                        # param_gather knob)
                        nv_p = _zero_gather(nv, e[0])
                    else:
                        nv_p = nv
                    if out_m and i in out_m:
                        out_m[i] = nv
                        out_p[i] = nv_p.astype(pvals[i].dtype)
                    else:
                        out_p[i] = nv_p
                lv = loss._value
                all_axes = tuple(a for a in mesh.axis_names
                                 if mesh.shape[a] > 1)
                if all_axes:
                    lv = C.t_pmean(lv, all_axes)
                # quantization telemetry: global L2 of the carried EF
                # residuals (how much gradient signal is in flight in
                # the compensation state) — one scalar psum, only in
                # the quantized program
                qnorm = jnp.float32(0.0)
                if new_qr:
                    qsq = jnp.float32(0.0)
                    for v in new_qr.values():
                        qsq = qsq + jnp.sum(jnp.square(
                            v.astype(jnp.float32)))
                    if all_axes:
                        qsq = C.t_psum(qsq, all_axes)
                    qnorm = jnp.sqrt(qsq)
            return (lv, gnorm, qnorm, tuple(out_p), tuple(new_s), out_m,
                    new_qr, amp_out, moe_tel)

        def make(batch_treedef, b_specs, mspecs):
            def flat_step(pvals, svals, mvals, qvals, batch_leaves, lr,
                          stepc, seed, amp_in):
                batch = jax.tree_util.tree_unflatten(batch_treedef,
                                                     batch_leaves)
                return _step(pvals, svals, mvals, qvals, batch, lr,
                             stepc, seed, amp_in)

            amp_ispec = (P(),) * 4 if use_scaler else ()
            amp_ospec = (P(),) * 5 if use_scaler else ()
            in_specs = (pspecs, sspecs, mspecs, qspecs, tuple(b_specs),
                        P(), P(), P(), amp_ispec)
            # the trailing P() is a pytree-prefix spec for the MoE
            # telemetry dict: every entry is replicated (psum'd over the
            # batch axes inside the step)
            out_specs = (P(), P(), P(), pspecs, sspecs, mspecs, qspecs,
                         amp_ospec, P())
            sharded = _shard_map(flat_step, mesh, in_specs, out_specs)
            return jax.jit(sharded,
                           donate_argnums=(0, 1, 2, 3) if donate else ())

        def step(batch):
            with _span("train.step", step=int(opt._step_count) + 1):
                return _step_host(batch)

        def _step_host(batch):
            t_entry = time.perf_counter()
            # fault-injection site for crash/hang tests: fires before
            # any state mutates, so a killed dispatch never tears a step
            _fp.hit("engine.step_dispatch")
            # previous step's loss/grad-norm scalars are fetched HERE
            # (one-step lag): the device has certainly finished the
            # prior step by the next dispatch, so telemetry never adds
            # a sync on the critical path
            with _span("train.flush_scalars"):
                self._flush_pending_scalars()
            with _span("train.assemble"):
                self._check_mesh_epoch()
                # host-offload prefetch: every offloaded slot re-placed
                # at its live sharding, bucket by bucket, BEFORE the
                # mvals / pvals assembly below reads them. Same shapes,
                # dtypes and shardings every step — the compile key
                # never notices.
                if self._offload is not None:
                    self._offload.prefetch_step(self)
                leaves, treedef = jax.tree_util.tree_flatten(
                    batch, is_leaf=lambda x: isinstance(x, Tensor))
                leaf_vals = tuple(v._value if isinstance(v, Tensor) else
                                  jnp.asarray(v) for v in leaves)
                if batch_specs is not None:
                    b_specs = tuple(batch_specs)
                else:
                    b_specs = tuple(
                        P(data_axes) if data_axes and v.ndim > 0 else P()
                        for v in leaf_vals)
                n_tok = _batch_tokens(leaf_vals)  # host-local batch tokens
                mvals = {i: opt._master_weights[id(p)]
                         for i, p in zip(t_index, trainable)
                         if id(p) in opt._master_weights}
                mspecs = {i: zero.state_spec(params[i]) for i in mvals}
                # scaler hyperparameters are baked into the trace as
                # Python constants — key them so two differently-
                # configured scalers never share an executable
                amp_key = ((scaler._dynamic, scaler._incr_every,
                            scaler._decr_every, scaler._incr_ratio,
                            scaler._decr_ratio) if use_scaler else None)
                # commledger.ablation_token() keys the exposed-comm
                # profiler's comm-ablated replays OUT of the real program
                # cache (None in normal operation, so live keys are
                # unchanged and steady state stays recompile-free)
                key = (treedef, tuple((v.shape, str(v.dtype))
                                      for v in leaf_vals), b_specs,
                       tuple(sorted(mvals)), amp_key, _cl.ablation_token())
            if not self._profiling:
                self.stats.note("train", key)
            # goodput attribution (observability/goodput): a known key
            # is productive step_compute; a fresh one pays trace + XLA
            # compile in this very call, so the whole dispatch window
            # books as compile. Host-side journal writes only — the
            # compiled program and its cache key are untouched.
            fresh_key = key not in self._compiled
            self._last_dispatch_fresh = (fresh_key
                                         or self._post_restore_warmup)
            _gp_led = None if self._profiling else _gp.current()
            if _gp_led is not None:
                _gp_led.begin("compile" if self._last_dispatch_fresh
                              else "step_compute",
                              step=int(opt._step_count) + 1)
            try:
                return _dispatch(key, treedef, b_specs, mspecs,
                                 leaf_vals, t_entry, n_tok, mvals)
            finally:
                # restore warmup ends at the first dispatch whose key
                # was already compiled: in a relaunched process that is
                # dispatch #2 (dispatch #1 traces; its outputs then
                # shift the avals off the restored arrays' layouts), in
                # an in-process restore it is dispatch #1
                if not fresh_key:
                    self._post_restore_warmup = False
                if _gp_led is not None:
                    _gp_led.end()

        def _dispatch(key, treedef, b_specs, mspecs, leaf_vals,
                      t_entry, n_tok, mvals):
            # the program on its way: state gathered, scalars uploaded,
            # the compiled call returned (a fresh key traces and
            # compiles inside it)
            with _span("train.dispatch", fresh=key not in self._compiled):
                if key not in self._compiled:
                    self._compiled[key] = make(treedef, b_specs, mspecs)
                pvals = tuple(p._value for p in params)
                svals = tuple(opt._states[id(p)] for p in trainable)
                qvals = dict(self._quant_residuals)
                opt._step_count += 1
                self._seed += 1
                lr = jnp.asarray(opt.get_lr(), jnp.float32)
                stepc = jnp.asarray(opt._step_count, jnp.int32)
                seed = jnp.asarray(self._seed, jnp.uint32)
                # -1: _step_count was already incremented for THIS step; the
                # traced counter advances inside the step on application
                amp_in = (scaler._traced_state(
                    fallback_step=opt._step_count - 1)
                    if use_scaler else ())
                leaf_vals = _globalize_batch(leaf_vals, b_specs, mesh)
                if _multiprocess(mesh):
                    lr = global_put(lr, mesh, P())
                    stepc = global_put(stepc, mesh, P())
                    seed = global_put(seed, mesh, P())
                    # amp state from a previous compiled step is already a
                    # committed global array — re-global_put would force a
                    # blocking host sync on every step
                    if use_scaler and not scaler._dev_global:
                        amp_in = tuple(global_put(v, mesh, P())
                                       for v in amp_in)
                        scaler._dev = amp_in
                        scaler._dev_global = True
                # the capture collects comm notes only if THIS call traces
                # (first execution of the program); cached executions note
                # nothing and reuse the stored ledger
                with _cl.capture() as cap:
                    (lv, gnorm, qnorm, new_p, new_s, new_m, new_qr, amp_out,
                     moe_tel) = \
                        self._compiled[key](pvals, svals, mvals, qvals,
                                            leaf_vals, lr, stepc, seed,
                                            amp_in)
            # what the host owes the step after it: the new state
            # written back, ledgers, `_note_step`
            with _span("train.record"):
                if len(cap):
                    self._ledgers[key] = cap
                for k, v in new_qr.items():
                    self._quant_residuals[k] = v
                if not self._profiling:
                    self._last_key = key
                    # example args for on-demand AOT memory analysis of
                    # this program (references only; the batch leaves are
                    # never donated). Params/states are rebuilt from the
                    # engine's CURRENT values at analysis time, so the
                    # stored tuple only pins shapes/dtypes/tree structure.
                    self._mem_args[key] = (leaf_vals, lr, stepc, seed,
                                           amp_in)
                for p, nv in zip(params, new_p):
                    p._value = nv
                for p, ns in zip(trainable, new_s):
                    opt._states[id(p)] = ns
                for i, nv in new_m.items():
                    opt._master_weights[id(params[i])] = nv
                if use_scaler:
                    scaler._store_traced(amp_out)
                # host-offload page-out: the step's FRESH output state (the
                # donated inputs are already dead buffers) moves to the
                # host tier, then the leading buckets start warming on the
                # background thread for the next dispatch
                if self._offload is not None:
                    self._offload.page_out_step(self)
                from ..optimizer.lr import LRScheduler

                if isinstance(opt._lr, LRScheduler):
                    opt._lr.step()  # advance the schedule once per train step
                if not self._profiling:
                    led = self._ledgers.get(key)
                    if led is not None:
                        led.publish(self._metrics["comm_bytes"],
                                    self._metrics["comm_ops"])
                        # realized per-axis wire compression of this
                        # program (quant_comm payload_ratio stamps); empty
                        # when nothing on the wire is quantized
                        for ax, rv in led.quant_ratios().items():
                            self._metrics["comm_quant_ratio"].set(
                                rv, axis=ax)
                    self._note_step(t_entry, n_tok, lv, gnorm,
                                    found=amp_out[4] if amp_out else None,
                                    qnorm=qnorm if new_qr else None)
                    self._pending_moe = moe_tel
            return Tensor(lv, stop_gradient=True)

        return step

    # -- telemetry (observability/) -------------------------------------
    def _flush_pending_scalars(self):
        """Fetch the PREVIOUS step's loss/grad-norm device scalars into
        the loss/grad_norm gauges. Called at the next step's entry (and
        from metrics_snapshot), so the fetch blocks only on work that
        is already done — telemetry adds no sync to the hot path."""
        pend = self._pending_scalars
        moe_pend = self._pending_moe
        self._pending_moe = None
        if moe_pend:
            try:
                _moestats.publish(moe_pend, self._metrics)
            except Exception:
                pass    # a dead device must not take telemetry down
        if pend is None:
            return
        self._pending_scalars = None
        found = self._pending_found
        self._pending_found = None
        qn = self._pending_qnorm
        self._pending_qnorm = None
        lv, gnorm = pend
        try:
            m = self._metrics
            lvf = float(np.asarray(lv))
            gnf = float(np.asarray(gnorm))
            m["loss"].set(lvf)
            m["grad_norm"].set(gnf)
            if qn is not None:
                m["quant_residual_norm"].set(float(np.asarray(qn)))
            # health monitor: robust spike/nonfinite detection on the
            # SAME fetched scalars (one-step lag — still off the hot
            # path; events ring + health_* gauges + goodput journal).
            # A step the AMP GradScaler SKIPPED (found_inf: grads
            # zeroed, update dropped) is protocol, not an anomaly —
            # its scalars never enter the detector's windows.
            if found is None or float(np.asarray(found)) == 0.0:
                self._health.observe(
                    loss=lvf, grad_norm=gnf,
                    step=int(self.optimizer._step_count)
                    if self.optimizer is not None else None)
        except Exception:
            pass        # a dead device must not take telemetry down

    def _note_step(self, t_entry: float, n_tok: int, lv, gnorm,
                   found=None, qnorm=None):
        """Host-side per-step instrumentation on fetched/host values
        only (never called under tracing). ``found``: the traced AMP
        found_inf flag of THIS step (device scalar; fetched with the
        same one-step lag as the loss). ``qnorm``: the quantization
        error-feedback residual norm device scalar (same lag)."""
        now = time.perf_counter()
        m = self._metrics
        m["step_seconds"].observe(now - t_entry)
        m["steps"].inc()
        m["tokens"].inc(n_tok)
        # step-time stall watch on the DISPATCH window (entry to
        # return): unlike the inter-step interval it contains no
        # checkpoint stalls / input waits, and compile dispatches are
        # excluded — so the rolling baseline only ever sees the
        # compiled step itself (coarse thresholds regardless: host
        # noise is real; healthmon docstring)
        if not self._last_dispatch_fresh:
            try:
                self._health.observe(step_seconds=now - t_entry)
            except Exception:
                pass
        # steady-state throughput between step ENTRIES: on an async
        # backend the dispatch returns early, so the inter-step gap is
        # the honest per-step wall time once the pipeline fills
        if self._prev_step_entry is not None:
            dt = max(t_entry - self._prev_step_entry, 1e-9)
            self._last_step_seconds = dt
            tps = n_tok / dt
            m["tokens_per_sec"].set(tps)
            n_params = self._n_params_cfg or sum(
                int(np.prod(p._value.shape)) for p in self.params)
            dev = next(iter(self.mesh.devices.flat))
            peak, _ = _flops.peak_flops_per_chip(dev)
            m["mfu"].set(_flops.mfu(
                n_params, tps * jax.process_count(), self.mesh.size,
                peak, config=getattr(self.model, "config", None)))
        self._prev_step_entry = t_entry
        self._pending_scalars = (lv, gnorm)
        self._pending_found = found
        self._pending_qnorm = qnorm
        # gradient-sync bucketing: how many per-bucket collectives the
        # compiled step issues (0 = the unbucketed tail sync, i.e.
        # sharding_configs["comm_overlap"] off or nothing bucketable)
        m["grad_buckets"].set(
            float(self._bucket_plan.num_buckets)
            if self._bucket_plan is not None else 0.0)
        # pipelined models: publish the analytic bubble fraction of the
        # attached schedule — (S-1)/(vpp*M+S-1) with the circular
        # interleave's vpp as a label, so dashboards can see the
        # schedule regime a run trains under (pp_layers._pipe_fn)
        if getattr(self.model, "_pp_ownership", False) and \
                "pp" in self.mesh.axis_names and self.mesh.shape["pp"] > 1:
            S = getattr(self.model, "_num_stages", 1)
            vpp = getattr(self.model, "_vpp", 1)
            n_mb = getattr(self.model, "_num_microbatches", 1)
            if S > 1:
                m["pp_bubble"].set(
                    (S - 1) / (vpp * n_mb + S - 1), pp_vpp=str(vpp))
        # compile-cache counters: report the delta since last step so
        # the Prometheus counters stay monotonic
        rc, rh = self._stats_reported
        if self.stats.compiles > rc:
            m["compiles"].inc(self.stats.compiles - rc,
                              site="train_engine")
        if self.stats.cache_hits > rh:
            m["cache_hits"].inc(self.stats.cache_hits - rh,
                                site="train_engine")
        self._stats_reported = (self.stats.compiles,
                                self.stats.cache_hits)
        try:
            for d in jax.local_devices():
                ms = d.memory_stats()
                for k in ("bytes_in_use", "peak_bytes_in_use",
                          "bytes_limit"):
                    if ms and k in ms:
                        m["device_memory"].set(
                            ms[k], device=str(d.id), stat=k)
        except Exception:
            pass        # CPU backends may not expose memory_stats
        self._last_tokens = n_tok
        # HBM memory ledger (observability/memledger): knob-gated eager
        # analysis once per program, gauges republished per step, state
        # accounting cached, live-bytes watermark at the step boundary
        if self._mem_on:
            led = self._mem_ledgers.get(self._last_key)
            if led is None:
                led = self.memory_ledger()
            if led is not None:
                led.publish(m, program="train")
            if self._state_acct is None:
                try:
                    self._state_acct = _ml.account_engine(
                        self, batch_tokens=n_tok,
                        accumulate_steps=int(getattr(
                            self.model, "_num_microbatches", 1) or 1))
                except Exception:
                    pass    # accounting must never take the step down
            if self._state_acct is not None:
                self._state_acct.publish(m)
            lb = _ml.live_bytes()
            if lb:
                self._live_peak = max(self._live_peak, lb)
                m["mem_live"].set(lb)
                m["mem_live_peak"].set(self._live_peak)
        # goodput gauges: the live view of the attached run ledger
        # (the crash-durable journal remains the source of truth)
        led_gp = _gp.current()
        if led_gp is not None:
            try:
                led_gp.publish(m)
            except Exception:
                pass    # a dead journal must not take the step down
        from ..observability import get_registry

        get_registry().snapshot()    # feeds the stall flight-record ring

    def metrics_snapshot(self):
        """Fetch pending scalars, then return the registry snapshot:
        the in-process API."""
        self._flush_pending_scalars()
        from ..observability import get_registry

        return get_registry().snapshot()

    def pod_throughput(self) -> Dict[str, float]:
        """Pod-level tokens/s: every host contributes its local gauge
        through a cross-host all_gather, so rank 0 can report aggregate
        throughput. Call BETWEEN steps (it synchronizes all hosts)."""
        from ..observability import cross_host_sum

        local = self._metrics["tokens_per_sec"].value()
        total = cross_host_sum(local)
        self._metrics["pod_tokens_per_sec"].set(total)
        return {"local_tokens_per_sec": local,
                "pod_tokens_per_sec": total,
                "processes": float(jax.process_count())}

    def pod_step_skew(self) -> Dict[str, Any]:
        """Cross-host straggler check: all-gather every host's last
        inter-step interval (the pod_throughput pattern — synchronizes
        all hosts, call BETWEEN steps) and publish the
        paddle_tpu_health_step_time_skew / slowest_host gauges. A
        persistently hot skew names the straggler host."""
        return self._health.observe_pod_skew(self._last_step_seconds)

    # -- communication accounting (observability/commledger) ------------
    def comm_ledger(self):
        """The static comm ledger of the last-run compiled step (None
        before any step has traced)."""
        return self._ledgers.get(self._last_key)

    # -- memory accounting (observability/memledger) ---------------------
    def memory_ledger(self, key=None):
        """Static HBM memory ledger of the last-run (or given-key)
        compiled train step: lowers the SAME jitted program AOT against
        the engine's current param/state values and reads XLA's
        ``memory_analysis()`` (temp / argument / output / alias / code
        bytes per device). Cached per program key — one extra trace +
        XLA compile the first time, zero thereafter, and the live
        step's jit cache / CompileStats are never touched. Returns
        None before any step has run."""
        key = key if key is not None else self._last_key
        led = self._mem_ledgers.get(key)
        if led is not None:
            return led
        led = self._with_aot_args(
            key, lambda fn, args: _ml.analyze(fn, args, program="train"))
        if led is not None:
            self._mem_ledgers[key] = led
        return led

    def lowered_text(self, key=None,
                     debug_info: bool = False) -> Optional[str]:
        """StableHLO text of the last-run (or given-key) compiled train
        step, lowered again from the SAME jitted program at the engine's
        current values (one extra trace, no XLA compile; the live jit
        cache and CompileStats are untouched). This is how a caller
        proves which kernels the step contains: each Pallas kernel is a
        ``tpu_custom_call`` carrying its ``kernel_name``. With
        ``debug_info`` the text also carries every op's name stack: the
        step's ``forward`` / ``backward`` / ``grad_sync`` / ``optimizer``
        scopes and the models' own, as they reach a device trace. None
        before any step has run."""
        key = key if key is not None else self._last_key
        return self._with_aot_args(
            key, lambda fn, args: fn.lower(*args).as_text(
                debug_info=debug_info))

    def _with_aot_args(self, key, use):
        """``use(jitted_step, example_args)`` for the program under
        ``key``, with the args rebuilt from the engine's current
        param/state values; None when that program has not run."""
        if key is None or key not in self._compiled:
            return None
        stored = self._mem_args.get(key)
        if stored is None or self.optimizer is None:
            return None
        leaf_vals, lr, stepc, seed, amp_in = stored
        opt = self.optimizer
        import contextlib

        with contextlib.ExitStack() as stack:
            # AOT analysis needs live jax.Arrays: page the host tier in
            # for the analysis window, back out after
            if self._offload is not None:
                stack.enter_context(self._offload.resident(self))
            pvals = tuple(p._value for p in self.params)
            svals = tuple(opt._states[id(p)] for p in self.trainable)
            qvals = dict(self._quant_residuals)
            # key[3] pins which params carried masters at trace time
            mvals = {i: opt._master_weights[id(self.params[i])]
                     for i in key[3]}
            return use(self._compiled[key],
                       (pvals, svals, mvals, qvals, leaf_vals, lr, stepc,
                        seed, amp_in))

    def state_accounting(self, batch_tokens: Optional[int] = None):
        """Measured per-device model-state accounting
        (memledger.account_engine): params / grads / optimizer state /
        master weights at addressable-shard size plus the analytic
        activation-checkpoint term, with the auto_tuner cost-model
        drift. Cached after the first step; ``batch_tokens`` overrides
        the last step's token count for the checkpoint term."""
        if self._state_acct is not None and batch_tokens is None:
            return self._state_acct
        acct = _ml.account_engine(
            self, batch_tokens=int(batch_tokens if batch_tokens
                                   is not None else self._last_tokens),
            accumulate_steps=int(getattr(self.model,
                                         "_num_microbatches", 1) or 1))
        if batch_tokens is None:
            self._state_acct = acct
        return acct

    def roofline_report(self, exposed=None):
        """Roofline bottleneck verdict of the last-run compiled step
        (memledger.roofline): joins the flop accountant (peak
        FLOPs/HBM/ICI tables), the memory ledger's HBM-traffic
        estimate, and the comm ledger — ``exposed`` (an
        ExposedCommReport from profile_exposed_comm) supplies measured
        exposed-ICI seconds and the measured step time; without it the
        analytic wire floor and the last inter-step interval stand in.
        All quantities are one chip's share."""
        n_params = self._n_params_cfg or sum(
            int(np.prod(p._value.shape)) for p in self.params)
        tokens = self._last_tokens * jax.process_count()
        fl = _flops.train_flops_per_token(
            n_params, config=getattr(self.model, "config", None)) \
            * tokens / max(self.mesh.size, 1)
        led = self.memory_ledger()
        traffic = led.traffic_bytes if led is not None and \
            led.available else 0.0
        comm = self.comm_ledger()
        wire = comm.bytes_for() if comm is not None else 0.0
        exp_ici = None
        step_s = self._last_step_seconds
        if exposed is not None:
            exp_ici = sum(exposed.exposed_seconds.values())
            step_s = exposed.step_seconds or step_s
        dev = next(iter(self.mesh.devices.flat))
        return _ml.roofline(
            step_seconds=step_s, flops_per_step=fl,
            hbm_traffic_bytes=traffic, wire_bytes=wire, device=dev,
            exposed_ici_seconds=exp_ici, program="train")

    def _state_snapshot(self):
        """Device-copy of everything a step mutates (jnp.copy keeps
        each array's sharding; immutable host-tier entries pass
        through by reference), so offline replays can be undone."""
        from . import host_offload as _ho

        def _copy(v):
            if _ho.is_host(v) or not hasattr(v, "shape"):
                return v
            return jnp.copy(v)

        opt = self.optimizer
        snap = {
            "params": [_copy(p._value) for p in self.params],
            "states": {id(p): {k: _copy(v)
                               for k, v in opt._states[id(p)].items()}
                       for p in self.trainable if id(p) in opt._states},
            "masters": {k: _copy(v)
                        for k, v in opt._master_weights.items()},
            "qresid": {k: _copy(v)
                       for k, v in self._quant_residuals.items()},
            "step_count": opt._step_count,
            "seed": self._seed,
            "pending": self._pending_scalars,
            "pending_found": self._pending_found,
            "pending_qnorm": self._pending_qnorm,
            "pending_moe": self._pending_moe,
        }
        from ..optimizer.lr import LRScheduler

        if isinstance(opt._lr, LRScheduler):
            snap["lr_state"] = dict(opt._lr.__dict__)
        return snap

    def _state_restore(self, snap):
        opt = self.optimizer
        for p, v in zip(self.params, snap["params"]):
            p._value = v
        for pid, st in snap["states"].items():
            opt._states[pid] = st
        opt._master_weights = dict(snap["masters"])
        self._quant_residuals = dict(snap["qresid"])
        opt._step_count = snap["step_count"]
        self._seed = snap["seed"]
        self._pending_scalars = snap["pending"]
        self._pending_found = snap["pending_found"]
        self._pending_qnorm = snap["pending_qnorm"]
        self._pending_moe = snap["pending_moe"]
        if "lr_state" in snap:
            opt._lr.__dict__.update(snap["lr_state"])

    # -- crash-consistent checkpointing (distributed/checkpoint) ---------
    def _checkpoint_state(self, scaler=None):
        """The full training state as (sharded state dict, scalar meta):
        params (+ buffers), optimizer moments in their live ZeRO/tp/pp
        sharding (shard-exact for pp x vpp stacked chunks — the state
        dict holds the global jax.Arrays, whose addressable shards the
        writer records with global offsets), AMP master weights, the
        GradScaler protocol state, step counters, the LR schedule, and
        the per-process RNG streams. Everything a bit-exact resume
        needs rides in ONE commit unit."""
        from ..core import rng as _rng_mod
        from ..optimizer.lr import LRScheduler
        from .fleet.elastic.resume import opt_state_tensors

        state: Dict[str, Any] = {"model": self.model.state_dict()}
        opt = self.optimizer
        meta: Dict[str, Any] = {"format": 1,
                                "engine_seed": int(self._seed)}
        if opt is not None:
            self._ensure_opt_states()
            meta["opt_step_count"] = int(opt._step_count)
            # optimizer state keyed by STRUCTURED model names (auto
            # p.name counters shift across in-process rebuilds)
            _, tensors = opt_state_tensors(self.model, opt)
            if tensors:
                state["optim"] = tensors
            if isinstance(opt._lr, LRScheduler):
                meta["lr_scheduler"] = opt._lr.state_dict()
            else:
                meta["lr"] = float(opt.get_lr())
        if scaler is not None:
            meta["scaler"] = scaler.state_dict()
        # quantized-collective error-feedback residuals (quant_comm):
        # per-bucket rank-local compression error carried as training
        # state — a resume that silently dropped it would re-inject the
        # lost gradient mass as a one-step bias, so it commits in the
        # SAME unit as params/moments (shard-exact: dim 0 is sharded
        # over every mesh axis, each process writes its own windows)
        if self._quant_residuals:
            state["quant_residual"] = dict(self._quant_residuals)
            meta["quant_residual_keys"] = sorted(self._quant_residuals)
        # per-process RNG streams: the host key + every named tracker
        # stream, keyed by process index so each relaunched rank gets
        # ITS stream back (the in-step per-rank forking derives from
        # engine_seed + axis_index, so it resumes exactly by itself)
        pi = jax.process_index()
        rng: Dict[str, Any] = {
            f"key_proc{pi}": np.asarray(
                jax.random.key_data(_rng_mod.get_rng_state()))}
        for name, key in _rng_mod.get_rng_tracker().states_.items():
            rng[f"tracker.{name}.proc{pi}"] = np.asarray(
                jax.random.key_data(key))
        state["rng"] = rng
        return state, meta

    def save_checkpoint(self, path: Optional[str] = None, *,
                        manager=None, step: Optional[int] = None,
                        scaler=None, extra_meta: Optional[Dict] = None,
                        async_save: bool = False) -> None:
        """Write a crash-consistent checkpoint of the engine's whole
        training state (see ``_checkpoint_state``).

        ``path``: one atomic checkpoint directory; or pass ``manager``
        (a ``checkpoint.CheckpointManager``) for rolling keep-last-k
        retention. ``async_save``/the manager's async mode stall only
        for the device→host snapshot; the commit happens in the
        background (``checkpoint.wait_async_saves()`` /
        ``manager.wait()`` to join). ``step`` defaults to the
        optimizer's applied-step count."""
        import contextlib

        from ..core.enforce import enforce

        with contextlib.ExitStack() as stack:
            # host-offloaded state pages in for the save window: the
            # checkpoint format (and its resharding metadata) is
            # IDENTICAL with the knob on or off, so restores cross the
            # offload boundary freely. The device->host snapshot
            # happens inside manager.save()/save_state_dict before the
            # exit pages everything back out.
            if self._offload is not None:
                stack.enter_context(self._offload.resident(self))
            state, meta = self._checkpoint_state(scaler)
            if step is None:
                step = meta.get("opt_step_count", 0)
            meta["step"] = int(step)
            if extra_meta:
                meta.update(extra_meta)
            if manager is not None:
                manager.save(state, step=int(step), extra_meta=meta)
            else:
                enforce(path is not None,
                        "save_checkpoint needs a path or a "
                        "CheckpointManager")
                from .checkpoint import save_state_dict

                save_state_dict(state, path, async_save=async_save,
                                extra_meta=meta)

    def restore_checkpoint(self, path: str, scaler=None) -> Dict[str, Any]:
        """Restore the engine (in place) from a committed checkpoint:
        params, optimizer moments + master weights (resharded to the
        CURRENT topology via the metadata's global offsets), scaler,
        counters, LR schedule, RNG streams. Returns the checkpoint's
        meta dict (incl. ``step``).

        Restoring never changes a shape, dtype, sharding spec, or the
        master-weight key set, so already-compiled steps keep hitting
        their cache — 0 recompiles after restore (pinned by tests).
        Restore also never touches CompileStats: the warmup compile of
        a restored engine books as a compile exactly once, and a
        restore into an already-compiled engine books nothing (pinned
        by tests against the registry counters too). Wall time spent
        here is journaled as the goodput ``restore`` segment."""
        import contextlib

        with _gp.segment("restore"):
            with contextlib.ExitStack() as stack:
                # the load targets are built from the live state dicts,
                # so the host tier pages in first; the exit pages the
                # LOADED arrays back out — the host-tier buffers are
                # rebuilt from the checkpoint bytes deterministically
                # (pinned by the SIGKILL-mid-prefetch crash matrix)
                if self._offload is not None:
                    stack.enter_context(self._offload.resident(self))
                meta = self._restore_checkpoint_inner(path, scaler)
        self._post_restore_warmup = True
        return meta

    def _restore_checkpoint_inner(self, path: str, scaler=None
                                  ) -> Dict[str, Any]:
        from ..core import rng as _rng_mod
        from ..optimizer.lr import LRScheduler
        from .checkpoint import load_state_dict, read_extra_meta, \
            resolve_committed

        resolved = resolve_committed(path)
        from ..core.enforce import enforce

        enforce(resolved is not None,
                f"no committed checkpoint at {path!r} "
                "(checkpoint.latest_committed(base) finds the newest "
                "committed one under a CheckpointManager base dir)")
        meta = read_extra_meta(resolved)
        from .fleet.elastic.resume import (_apply_opt_state,
                                           opt_state_tensors)

        opt = self.optimizer
        # phase 1: model params FIRST — optimizer state materialized
        # below (fresh AMP masters) must copy the LOADED weights
        targets: Dict[str, Any] = {"model": self.model.state_dict()}
        load_state_dict(targets, resolved)
        if opt is not None:
            self._ensure_opt_states()
            slots, tensors = opt_state_tensors(self.model, opt)
            if tensors:
                load_state_dict({"optim": tensors}, resolved)
                _apply_opt_state(opt, slots, tensors)
            opt._step_count = int(meta.get("opt_step_count",
                                           meta.get("step", 0)))
            if "lr_scheduler" in meta and isinstance(opt._lr,
                                                     LRScheduler):
                opt._lr.set_state_dict(meta["lr_scheduler"])
            if "lr" in meta and not isinstance(opt._lr, LRScheduler):
                opt.set_lr(float(meta["lr"]))
        self._seed = int(meta.get("engine_seed", self._seed))
        if scaler is not None and "scaler" in meta:
            scaler.load_state_dict(meta["scaler"])
        # quantization error-feedback residuals: materialize the (zero)
        # buffers from the deterministic bucket plan, then overwrite
        # with the checkpointed bytes at the live sharding. Checkpoints
        # written without quant_comm (or restored into an engine with
        # the knob off) skip this — the buffers stay zeros / absent.
        qkeys = meta.get("quant_residual_keys") or []
        if qkeys:
            self._ensure_quant_state()
            targets = {k: self._quant_residuals[k] for k in qkeys
                       if k in self._quant_residuals}
            if targets:
                loaded = {"quant_residual": dict(targets)}
                load_state_dict(loaded, resolved)
                for k, arr in loaded["quant_residual"].items():
                    # the loader hands raw (non-Tensor) leaves back as
                    # host arrays — re-place at the live sharding
                    if not isinstance(arr, jax.Array):
                        self._quant_residuals[k] = global_put(
                            np.asarray(arr, dtype=np.float32),
                            self.mesh, self._quant_specs[k])
        # per-process RNG streams (missing entries — e.g. resuming on
        # MORE hosts than saved — keep their current stream)
        pi = jax.process_index()
        rng_t: Dict[str, Any] = {f"key_proc{pi}": np.zeros(0)}
        tracker = _rng_mod.get_rng_tracker()
        for name in tracker.states_:
            rng_t[f"tracker.{name}.proc{pi}"] = np.zeros(0)
        rng_targets = {"rng": rng_t}
        load_state_dict(rng_targets, resolved)
        key = rng_targets["rng"][f"key_proc{pi}"]
        if getattr(key, "size", 0):
            _rng_mod.set_rng_state(
                jax.random.wrap_key_data(jnp.asarray(key)))
        for name in tracker.states_:
            kd = rng_targets["rng"][f"tracker.{name}.proc{pi}"]
            if getattr(kd, "size", 0):
                tracker.states_[name] = jax.random.wrap_key_data(
                    jnp.asarray(kd))
        return meta

    @staticmethod
    def _time_calls(fn, repeats: int) -> float:
        """Median wall time of ``fn()`` over ``repeats`` blocked calls
        (one unmeasured warmup call first — it may compile)."""
        jax.block_until_ready(fn())
        times = []
        for _ in range(max(repeats, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    def profile_exposed_comm(self, step, batch, repeats: int = 3,
                             publish: bool = True):
        """Exposed-comm attribution: split each mesh axis's comm time
        into exposed vs overlapped (observability/commledger.py).

        For every axis label in the step's comm ledger this compiles a
        REPLAY of the same step with that axis's collectives ablated to
        shape-preserving local ops, and a standalone back-to-back
        replay of the axis's recorded collectives; then

        - exposed(axis) = t(full) - t(ablated): what the axis's comm
          adds to the critical path,
        - replay(axis): the axis's total comm time, nothing hiding it,
        - comm_exposed_fraction{axis} = exposed / max(replay, exposed),
        - grad_sync_exposed_seconds = exposed summed over dp/sharding.

        Offline only: params / optimizer state / rng / lr schedule are
        snapshotted and restored (the ablated replays compute garbage
        on purpose), telemetry counters and CompileStats are suppressed
        while it runs, and the replay executables are dropped from the
        program cache afterwards — the next real step hits the original
        compiled program. Run between steps, never under an AMP
        GradScaler whose state you care about.

        Returns an ``ExposedCommReport``; ``publish=True`` also sets
        the comm_exposed_* / grad_sync_exposed_seconds gauges.
        """
        self._flush_pending_scalars()
        led = self.comm_ledger()
        if led is None or not len(led):
            rep = _cl.build_report(0.0, {}, {})
            if publish:
                rep.publish(self._metrics)
            return rep
        snap = self._state_snapshot()
        self._profiling = True
        try:
            t_full = self._time_calls(lambda: step(batch)._value, repeats)
            exposed: Dict[str, float] = {}
            replay: Dict[str, float] = {}
            for label in led.axis_labels():
                with _cl.ablate({label}):
                    t_abl = self._time_calls(
                        lambda: step(batch)._value, repeats)
                exposed[label] = t_full - t_abl
                recs = [r for r in led.records if r.axis == label]
                rfn = _cl.replay_callable(recs, self.mesh, _shard_map,
                                          jax.jit)
                replay[label] = self._time_calls(rfn, repeats)
        finally:
            self._profiling = False
            self._state_restore(snap)
            # drop the ablated executables (ablation_token is the last
            # key component; None marks the real programs)
            self._compiled = {k: v for k, v in self._compiled.items()
                              if k[-1] is None}
            self._ledgers = {k: v for k, v in self._ledgers.items()
                             if k[-1] is None}
            self._mem_ledgers = {k: v for k, v
                                 in self._mem_ledgers.items()
                                 if k[-1] is None}
            self._mem_args = {k: v for k, v in self._mem_args.items()
                              if k[-1] is None}
        rep = _cl.build_report(t_full, exposed, replay)
        if publish:
            rep.publish(self._metrics)
        return rep

    def _check_mesh_epoch(self):
        if C.mesh_epoch() != self._mesh_epoch:
            from ..core.enforce import PreconditionNotMetError

            raise PreconditionNotMetError(
                "the world mesh was rebuilt (split_group factored an "
                "axis) after this ParallelEngine was created; its "
                "compiled steps reference deleted axis names. Call "
                "split_group BEFORE building engines/shardings, or "
                "recreate the ParallelEngine.")

    # -- forward-only (eval / inference) --------------------------------
    def eval_step(self, fn: Callable, batch_specs=None):
        mesh = self.mesh
        data_axes = _mesh_data_axes(mesh)
        params = self.params
        zero = self._zero
        pspecs = tuple(zero.storage_spec(p) for p in params)
        compiled: Dict[Any, Callable] = {}

        def make(treedef, b_specs, out_spec):
            def flat_fwd(pvals, batch_leaves):
                pvals = list(pvals)
                for i, p in enumerate(params):
                    e = zero.entry(p)
                    if e is not None and e[1]:
                        pvals[i] = C.t_all_gather(pvals[i], zero.axis,
                                                  axis=e[0], tiled=True)
                pvals = tuple(pvals)
                with C.spmd_region(), bind_params(params, pvals), \
                        _ad.no_grad():
                    batch = jax.tree_util.tree_unflatten(treedef,
                                                         batch_leaves)
                    t_batch = jax.tree_util.tree_map(
                        lambda v: Tensor(v, stop_gradient=True), batch)
                    out = fn(self.model, t_batch)
                    return (out._value if isinstance(out, Tensor) else
                            jax.tree_util.tree_map(
                                lambda t: t._value if isinstance(t, Tensor)
                                else t, out))

            sharded = _shard_map(flat_fwd, mesh,
                                 (pspecs, tuple(b_specs)), out_spec)
            return jax.jit(sharded)

        def step(batch, out_spec=None):
            self._check_mesh_epoch()
            # host-offloaded param shards must be live before the
            # p._value assembly below (they page out again at the next
            # train step)
            if self._offload is not None:
                self._offload.restore_params(self)
            leaves, treedef = jax.tree_util.tree_flatten(
                batch, is_leaf=lambda x: isinstance(x, Tensor))
            leaf_vals = tuple(v._value if isinstance(v, Tensor) else
                              jnp.asarray(v) for v in leaves)
            b_specs = (tuple(batch_specs) if batch_specs is not None else
                       tuple(P(data_axes) if data_axes and v.ndim > 0
                             else P() for v in leaf_vals))
            ospec = out_spec if out_spec is not None else (
                P(data_axes) if data_axes else P())
            key = (treedef, tuple((v.shape, str(v.dtype))
                                  for v in leaf_vals), b_specs, str(ospec))
            self.stats.note("eval", key)
            if key not in compiled:
                compiled[key] = make(treedef, b_specs, ospec)
            leaf_vals = _globalize_batch(leaf_vals, b_specs, mesh)
            out = compiled[key](tuple(p._value for p in params), leaf_vals)
            return jax.tree_util.tree_map(
                lambda v: Tensor(v, stop_gradient=True), out)

        return step

"""Pipeline-parallel model partitioning — TPU-native PipelineLayer.

Reference surface: python/paddle/distributed/fleet/meta_parallel/
parallel_layers/pp_layers.py — ``LayerDesc`` (:56), ``SharedLayerDesc``,
``SegmentLayers`` (:92), ``PipelineLayer`` (:261). There, each pp rank
builds ONLY its stage's layers and microbatches flow between ranks via
NCCL p2p driven from Python (pp_utils/p2p_communication.py).

TPU-native redesign: every rank traces the SAME program (SPMD). The
homogeneous middle run of the layer list (the transformer blocks) is
stored as *stacked* parameters with a leading layer axis sharded over the
'pp' mesh axis — each pp rank physically holds L/pp layers. The schedule
is a ``lax.scan`` over pipeline ticks with ``lax.ppermute`` rotating
activations stage→stage+1 over the ICI ring (see pipeline schedule in
``PipelineLayer._pipe_fn``); jax.vjp of that function IS the reverse
pipeline, so backward scheduling needs no hand-written p2p.

Memory (the 1F1B question): the reference's 1F1B
(meta_parallel/pipeline_parallel.py:455) exists to keep at most S
microbatches of activations alive instead of M. In a single compiled
SPMD program the fwd/bwd tick interleaving of 1F1B is not expressible
(jax.vjp replays backward after all of forward), so the same memory
property is achieved differently: each pipeline TICK is wrapped in
``jax.checkpoint`` (on by default, ``tick_checkpoint=False`` to
disable), so the only activations that survive the forward scan are the
O(microbatch) stage-boundary carries — per-block residuals exist for
just ONE tick at a time during backward. Cost: one extra stage-forward
per tick (the standard remat trade).

Interleaved virtual stages (the CIRCULAR schedule): contrary to the
folk claim that interleave presupposes 1F1B's hand-scheduled fwd/bwd
ticks, a GSPMD-style *circular* schedule expresses it inside the same
single ``lax.scan`` + ``lax.ppermute`` program. With
``num_virtual_pipeline_stages = vpp > 1`` each stage holds ``vpp``
NON-contiguous layer chunks of ``L/(pp*vpp)`` layers: the stacked
parameters are shaped ``[vpp, L/vpp, ...]`` with axis 1 sharded over
'pp', so rank ``s`` physically owns, for every circuit ``v``, the
global layers ``[v*L/vpp + s*K, v*L/vpp + (s+1)*K)`` (``K =
L/(pp*vpp)``) — the round-robin chunk→stage map of Megatron/GSPMD
interleave. Each microbatch makes ``vpp`` circuits of the ICI ring
(stage S-1's output ppermutes back into stage 0, which applies its
NEXT chunk to it), so the scan runs ``T = vpp*M + S - 1`` ticks of
``1/vpp``-sized stage work: bubble (S-1)/(vpp*M+S-1) instead of
(S-1)/(M+S-1): up to ~2x at small M by that arithmetic, which no chip
run has measured (the four-chip cell runs vpp = 1). Microbatches are
admitted in groups of S (circuit v+1 of a microbatch re-enters stage 0
exactly S ticks after circuit v left it — a pure shift register, no
carry buffering), which is why ``accumulate_steps % pp == 0`` is
required when vpp > 1. ``jax.vjp`` of the circular program IS the
exact reverse schedule, and ``tick_checkpoint`` remat keeps the
O(microbatch) memory property per chunk (each tick now recomputes only
K layers). RNG streams are distinct per (tick, stage, chunk) — see
``_tick_seed``.

Stage ownership: the prologue (embedding) runs under ``lax.cond`` only
on stage 0 and the epilogue (final norm + 50K-vocab head + loss) only
on the last stage — other ranks execute the zero branch, so the
redundant FLOPs are actually skipped at runtime, not just masked.
Gradient ownership falls out of ``lax.cond``'s vjp (non-owners
contribute zero cotangents) and the engine psums replicated-param
grads over 'pp' (tied word embeddings then work with no special
casing — stage-0 and last-stage contributions sum, which is what the
reference's SharedLayerDesc allreduce does by hand).
"""
from __future__ import annotations

from contextlib import nullcontext as _nullcontext
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..... import ops
from .... import collective as C
from .....autograd import engine as _engine
from .....autograd.engine import no_grad
from .....core import rng as _rng
from .....core.enforce import enforce
from .....nn.container import LayerList
from .....nn.layer import Layer
from .....observability import commledger as _cl
from .....tensor import Parameter, Tensor
from .... import collective as C

__all__ = ["LayerDesc", "SharedLayerDesc", "SegmentLayers", "PipelineLayer"]


class LayerDesc:
    """Deferred layer construction (reference pp_layers.py:56)."""

    def __init__(self, layer_func, *inputs, **kwargs):
        self.layer_func = layer_func
        self.inputs = inputs
        self.kwargs = kwargs
        if not issubclass(layer_func, Layer) if isinstance(layer_func, type) \
                else not callable(layer_func):
            raise TypeError("layer_func must be a Layer subclass or callable")

    def build_layer(self) -> Layer:
        return self.layer_func(*self.inputs, **self.kwargs)

    def __repr__(self):
        return f"LayerDesc({getattr(self.layer_func, '__name__', self.layer_func)})"


class SharedLayerDesc(LayerDesc):
    """A layer whose parameters are shared across its occurrences
    (reference pp_layers.py SharedLayerDesc — embedding/head weight
    tying across first/last stage). Occurrences after the first reuse
    the built instance; ``forward_func`` overrides how it is applied."""

    def __init__(self, key, layer_func, forward_func=None,
                 shared_weight_attr="weight", *inputs, **kwargs):
        super().__init__(layer_func, *inputs, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class SegmentLayers:
    """Partition N layers into num_parts parts (reference pp_layers.py:92).

    method: "uniform" or "layer:<ClassName>" (cut so each part starts at
    an instance of the named class).

    With ``num_virtual_pipeline_stage = vpp > 1`` the layer list is cut
    into ``num_stages * vpp`` parts whose stage ASSIGNMENT is
    interleaved round-robin (part j → stage ``j % num_stages``, circuit
    ``j // num_stages``) — the circular-schedule chunk→stage map — NOT
    the reference's contiguous ``num_parts *= vpp`` blocks-per-stage
    pre-multiplication."""

    def __init__(self, layers_desc, num_parts, method="uniform",
                 num_virtual_pipeline_stage=None):
        self._layers_desc = layers_desc
        self.method = method
        self.num_stages = num_parts
        self.num_virtual = num_virtual_pipeline_stage or 1
        self.num_parts = num_parts * self.num_virtual
        self.num_items = len(layers_desc)
        enforce(self.num_items >= self.num_parts,
                f"layer number ({self.num_items}) should be no less than "
                f"the number of segments = pp degree ({self.num_stages}) "
                f"x num_virtual_pipeline_stages ({self.num_virtual}) = "
                f"{self.num_parts}")

    def part_stage(self, part_idx: int) -> int:
        """Physical pp stage owning segment ``part_idx``: interleaved
        round-robin under virtual stages (part j → stage j % pp during
        circuit j // pp), contiguous identity otherwise."""
        enforce(0 <= part_idx < self.num_parts,
                f"part {part_idx} out of range [0, {self.num_parts})")
        return part_idx % self.num_stages

    def part_chunk(self, part_idx: int) -> int:
        """Circuit (virtual-stage chunk) index of segment ``part_idx``
        on its owning stage."""
        enforce(0 <= part_idx < self.num_parts,
                f"part {part_idx} out of range [0, {self.num_parts})")
        return part_idx // self.num_stages

    def do_segment(self) -> List[int]:
        if self.method == "uniform":
            return self.uniform(self.num_items, self.num_parts)
        if self.method.startswith("layer:"):
            cls_name = self.method.split(":", 1)[1]
            weights = [0] * len(self._layers_desc)
            for i, d in enumerate(self._layers_desc):
                fn = d.layer_func if isinstance(d, LayerDesc) else type(d)
                name = getattr(fn, "__name__", str(fn))
                if name == cls_name:
                    weights[i] = 1
            idxs = [i for i, w in enumerate(weights) if w]
            total = len(idxs)
            enforce(total % self.num_parts == 0,
                    f"the number of {cls_name} ({total}) must be divisible "
                    f"by pp degree ({self.num_stages}) x "
                    f"num_virtual_pipeline_stages ({self.num_virtual}) "
                    f"= {self.num_parts}")
            per = total // self.num_parts
            return ([0] + [idxs[k * per] for k in range(1, self.num_parts)]
                    + [self.num_items])
        raise ValueError(f"unknown seg_method {self.method}")

    @staticmethod
    def uniform(num_items, num_parts) -> List[int]:
        result = [0]
        part = num_items // num_parts
        extra = num_items % num_parts
        for i in range(num_parts):
            result.append(result[-1] + part + (1 if i < extra else 0))
        return result


class _FuncLayer(Layer):
    """Wraps a bare callable desc entry as a (parameterless) Layer."""

    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def forward(self, *a, **k):
        return self._fn(*a, **k)


class _SharedApply(Layer):
    """Later occurrence of a SharedLayerDesc: applies ``forward_func`` to
    the shared instance (does NOT own the parameters)."""

    def __init__(self, shared: Layer, forward_func):
        super().__init__()
        object.__setattr__(self, "_shared_ref", shared)  # not a sublayer
        self._forward_func = forward_func

    def forward(self, *a, **k):
        if self._forward_func is not None:
            return self._forward_func(self._shared_ref, *a, **k)
        return self._shared_ref(*a, **k)


def _bind(params: Sequence[Parameter], values):
    """Functional bind (same contract as distributed.engine.bind_params)."""
    from ....engine import bind_params

    return bind_params(params, values)


def _tick_seed(base_seed, t, stage, chunk):
    """Distinct rng stream per (tick, stage, chunk): dropout masks must
    differ across microbatches, stages, AND the vpp chunks a stage
    applies on different circuits of the same tick phase. Affine mix of
    odd/coprime constants over uint32; uniqueness over realistic
    (t, stage, chunk) grids is pinned by tests/test_pp_vpp.py."""
    return (base_seed * jnp.uint32(1000003)
            + t.astype(jnp.uint32) * jnp.uint32(2654435761)
            + stage.astype(jnp.uint32)
            + chunk.astype(jnp.uint32) * jnp.uint32(40503))


class PipelineLayer(Layer):
    """Pipeline-partitioned model (reference pp_layers.py:261).

    ``layers`` is a list of LayerDesc / SharedLayerDesc / Layer /
    callables. The longest homogeneous run of LayerDescs (the decoder
    blocks) becomes the pipelined middle; everything before/after is
    prologue/epilogue, replicated over pp ranks.
    """

    def __init__(self, layers, num_stages: Optional[int] = None,
                 topology=None, loss_fn=None, seg_method: str = "uniform",
                 recompute_interval: int = 0, recompute_ctx=None,
                 num_virtual_pipeline_stages: Optional[int] = None,
                 tick_checkpoint: bool = True):
        super().__init__()
        from ... import fleet as _fleet_pkg  # noqa: F401 (cycle guard)

        if num_stages is None:
            hcg = self._hcg()
            num_stages = (hcg.get_pipe_parallel_world_size()
                          if hcg is not None else 1)
        self._num_stages = int(num_stages)
        if num_virtual_pipeline_stages is None:
            # plumbed from strategy.hybrid_configs["pp_configs"] via
            # fleet.init -> HybridCommunicateGroup
            hcg = self._hcg()
            num_virtual_pipeline_stages = (
                hcg.get_virtual_pipeline_parallel_world_size()
                if hcg is not None else 1)
        vpp = int(num_virtual_pipeline_stages or 1)
        enforce(vpp >= 1,
                f"num_virtual_pipeline_stages must be >= 1; got {vpp}")
        if vpp > 1:
            enforce(self._num_stages > 1,
                    f"num_virtual_pipeline_stages={vpp} (the circular "
                    f"interleaved schedule) needs a pipelined mesh, but "
                    f"pp_degree is {self._num_stages} — set "
                    "hybrid_configs['pp_degree'] > 1 or drop "
                    "hybrid_configs['pp_configs']"
                    "['num_virtual_pipeline_stages']")
        self._vpp = vpp
        self._tick_checkpoint = bool(tick_checkpoint)
        self._loss_fn = loss_fn
        # the stacked blocks share ONE scanned body, so recompute is
        # all-or-nothing here: every block (interval=1) or none (0) —
        # a per-k-th-layer policy is not expressible inside lax.scan
        enforce(recompute_interval in (0, 1),
                "recompute_interval must be 0 (off) or 1 (recompute every "
                f"block); got {recompute_interval}")
        self._recompute_interval = recompute_interval
        self._seg_method = seg_method
        self._num_microbatches = 1
        self._descs = list(layers)
        # pipelined models use grad-ownership masking: the engine must
        # psum replicated-param grads over 'pp' (see module docstring)
        self._pp_ownership = True

        self._shared: Dict[str, Layer] = {}
        built: List[Layer] = []
        for d in self._descs:
            built.append(self._build_one(d))

        lo, hi = self._homogeneous_run(self._descs)
        mid = built[lo:hi]
        n_mid = len(mid)
        total = self._num_stages * self._vpp
        enforce(n_mid % total == 0 if total > 1 else True,
                f"pipelined middle has {n_mid} layers (L), not divisible "
                f"by pp_degree ({self._num_stages}) x "
                f"num_virtual_pipeline_stages ({self._vpp}) = {total}; "
                "each stage must own num_virtual_pipeline_stages chunks "
                f"of L/{total} layers — adjust num_layers or the "
                "pp_degree / num_virtual_pipeline_stages knobs")
        self.prologue = LayerList(built[:lo])
        self.epilogue = LayerList(built[hi:])
        self._n_blocks = n_mid

        # stack the middle blocks' params along a leading layer axis
        template = mid[0] if mid else None
        object.__setattr__(self, "_template", template)
        self._t_params: List[Parameter] = []
        self._s_params: List[Parameter] = []
        if template is not None:
            names = [n for n, _ in template.named_parameters()]
            per_block = [dict(b.named_parameters()) for b in mid]
            for n in names:
                tp = per_block[0][n]
                stacked = jnp.stack([pb[n]._value for pb in per_block])
                base = getattr(tp, "dist_attr", None)
                base = tuple(base) if isinstance(base, P) else \
                    (None,) * tp.ndim
                if self._vpp > 1:
                    # circular interleave: leading chunk axis laid out
                    # round-robin over stages — sharding axis 1 (L/vpp
                    # layer rows) over 'pp' hands rank s, for every
                    # circuit v, the non-contiguous global layers
                    # [v*L/vpp + s*K, v*L/vpp + (s+1)*K)
                    stacked = stacked.reshape(
                        (self._vpp, n_mid // self._vpp) + stacked.shape[1:])
                    sp = Parameter(stacked, trainable=tp.trainable)
                    sp.dist_attr = P(None, "pp", *base)
                    sp.is_distributed = True
                elif total > 1:
                    sp = Parameter(stacked, trainable=tp.trainable)
                    sp.dist_attr = P("pp", *base)
                    sp.is_distributed = True
                else:
                    sp = Parameter(stacked, trainable=tp.trainable)
                    if any(a is not None for a in base):
                        sp.dist_attr = P(None, *base)
                        sp.is_distributed = True
                self.add_parameter("blocks__" + n.replace(".", "__"), sp)
                self._t_params.append(tp)
                self._s_params.append(sp)
        # segment bookkeeping (reference parity: part boundaries, plus
        # the interleaved part→(stage, chunk) map under virtual stages)
        if mid:
            seg = SegmentLayers(
                self._descs[lo:hi], self._num_stages, seg_method,
                self._vpp if self._vpp > 1 else None)
            self.segment_parts = seg.do_segment()
            self.segment_part_stages = [seg.part_stage(j)
                                        for j in range(seg.num_parts)]
            self.segment_part_chunks = [seg.part_chunk(j)
                                        for j in range(seg.num_parts)]
        else:
            self.segment_parts = [0]
            self.segment_part_stages = []
            self.segment_part_chunks = []

    # -- construction helpers -------------------------------------------
    def _hcg(self):
        from ... import fleet as _fleet

        return _fleet.get_hybrid_communicate_group()

    def _build_one(self, d) -> Layer:
        if isinstance(d, SharedLayerDesc):
            if d.layer_name in self._shared:
                return _SharedApply(self._shared[d.layer_name],
                                    d.forward_func)
            inst = d.build_layer()
            self._shared[d.layer_name] = inst
            return inst
        if isinstance(d, LayerDesc):
            return d.build_layer()
        if isinstance(d, Layer):
            return d
        if callable(d):
            return _FuncLayer(d)
        raise TypeError(f"cannot build pipeline entry {d!r}")

    @staticmethod
    def _homogeneous_run(descs) -> tuple:
        """[lo, hi) of the longest run of plain LayerDescs with the same
        layer_func — the pipelineable middle."""
        best = (0, 0)
        i = 0
        n = len(descs)
        while i < n:
            d = descs[i]
            if type(d) is LayerDesc:
                j = i
                while j < n and type(descs[j]) is LayerDesc and \
                        descs[j].layer_func is d.layer_func:
                    j += 1
                if j - i > best[1] - best[0]:
                    best = (i, j)
                i = j
            else:
                i += 1
        return best

    # -- pure functions over stacked values ------------------------------
    def _block_apply(self, row_vals, x_val):
        """Apply the template block with its params bound to one stacked
        row. Pure in (row_vals, x_val) given the ambient rng seed."""
        with no_grad(), _bind(self._t_params, row_vals):
            out = self._template(Tensor(x_val, stop_gradient=True))
        if isinstance(out, tuple):
            out = out[0]
        return out._value

    def _apply_rows(self, x_val, stacked_vals, n_rows):
        """lax.scan over the stacked layer axis — program size stays O(1)
        in depth (40-layer stacks compile as one block body)."""
        if n_rows == 0:
            return x_val
        base_seed = _rng.traced_seed()
        block = self._block_apply
        if self._recompute_interval:
            block = jax.checkpoint(block)

        def body(x, xs):
            row, ridx = xs
            if base_seed is None:
                return block(list(row), x), None
            # distinct rng stream per layer row (dropout sites must not
            # share masks across the scanned layers)
            seed_j = base_seed * jnp.uint32(31) + ridx.astype(jnp.uint32)
            with _rng.fork_traced(seed_j):
                return block(list(row), x), None

        xs = (tuple(stacked_vals), jnp.arange(n_rows))
        out, _ = lax.scan(body, x_val, xs)
        return out

    def _pp_axes(self):
        hcg = self._hcg()
        if hcg is None:
            return None
        g = hcg.get_pipe_parallel_group()
        if g is None or not g.axis_names or g.nranks <= 1:
            return None
        return g.axis_names

    def _pipe_fn(self, M, base_seed, pp_axes):
        """The pipeline schedule: microbatch rotation over the pp ring.

        Returns pure fn(x, *stacked) -> last-stage outputs (valid rows
        only on the last pp stage; zeros-masked elsewhere).

        vpp=1 (GPipe-family): T = M + S - 1 ticks; at tick t, stage s
        computes microbatch t - s; lax.ppermute rotates activations one
        stage forward per tick on ICI.

        vpp>1 (circular interleave): each stage holds vpp chunks of
        K = L/(S*vpp) layers (round-robin layout, see __init__); every
        activation makes vpp circuits of the ring before emitting, so
        the scan runs T = vpp*M + S - 1 ticks of 1/vpp-sized stage work
        — bubble (S-1)/(vpp*M+S-1). Work items (microbatch m, circuit
        v) enter stage 0 in groups of S microbatches, all circuits of a
        group before the next group (entry order e = g*S*vpp + v*S +
        (m - g*S)): circuit v+1 of an item re-enters stage 0 exactly S
        ticks after circuit v entered, which is precisely when its
        carry returns from stage S-1 — a pure shift register, no
        buffering, hence the accumulate_steps % pp == 0 requirement.
        The item at stage s on tick t is e = t - s; its chunk is
        v = (e mod S*vpp) // S.

        jax.vjp of this function yields the exact reverse schedule
        (backward pipeline) automatically — for vpp>1 included, because
        the circular rotation is ordinary data flow through scan +
        ppermute.
        """
        enforce(len(pp_axes) == 1, "pp must map to a single mesh axis")
        axis = pp_axes[0]
        V = self._vpp

        def fn(x_val, *stacked_vals):
            S = C.axis_size(axis)
            enforce(S == self._num_stages,
                    f"model was built for {self._num_stages} pipeline "
                    f"stages but the mesh '{axis}' axis has {S} — build "
                    "the PipelineLayer after fleet.init (or pass "
                    "num_stages)")
            if V > 1:
                enforce(M % S == 0,
                        f"accumulate_steps (microbatches M={M}) must be "
                        f"a multiple of pp_degree (S={S}) when "
                        f"num_virtual_pipeline_stages={V}: the circular "
                        "schedule admits microbatches in groups of "
                        "pp_degree so returning circuits slot into the "
                        "ring without buffering")
            stage = lax.axis_index(axis)
            B = x_val.shape[0]
            enforce(B % M == 0, f"local batch {B} not divisible by "
                    f"microbatches {M}")
            mb = B // M
            xm = x_val.reshape((M, mb) + x_val.shape[1:])
            if stacked_vals:
                n_rows = stacked_vals[0].shape[1 if V > 1 else 0]
            else:
                n_rows = 0
            carry = jnp.zeros((mb,) + x_val.shape[1:], x_val.dtype)
            out_buf = jnp.zeros_like(xm)
            perm = [(i, (i + 1) % self._num_stages)
                    for i in range(self._num_stages)]
            SV = S * V
            E = V * M          # total work items (microbatch, circuit)

            def tick(x_in, seed_t, v, *sv):
                if V > 1:
                    # chunk selection INSIDE the remat boundary: the
                    # backward recomputes the [K, ...] gather instead
                    # of saving a per-tick copy of the chunk params
                    # (T x param bytes — the memory-flatness test
                    # catches the difference)
                    sv = tuple(
                        lax.dynamic_index_in_dim(s_, v, 0, keepdims=False)
                        for s_ in sv)
                with _rng.fork_traced(seed_t):
                    return self._apply_rows(x_in, sv, n_rows)

            if self._tick_checkpoint:
                # memory-honest schedule: only the O(microbatch) stage
                # boundary carries survive the forward scan; the blocks'
                # residuals exist for one tick at a time during backward
                # (recomputed), so activation memory does NOT scale with
                # microbatch count (see module docstring). Under vpp>1
                # each tick rematerializes only its K-layer chunk.
                tick = jax.checkpoint(tick)

            def body(state, t):
                carry, out_buf = state
                # work item at this stage this tick: entry index e,
                # chunk v = (e mod S*vpp) // S, microbatch
                # m = (e // S*vpp)*S + (e mod S*vpp) mod S
                e = jnp.clip(t - stage, 0, E - 1)
                r = e % SV
                v = r // S
                m_in = jnp.clip((e // SV) * S + r, 0, M - 1)
                x_mb = lax.dynamic_index_in_dim(xm, m_in, 0,
                                                keepdims=False)
                # stage 0 injects a fresh microbatch on circuit 0; on
                # later circuits it consumes the carry returning from
                # stage S-1 (the circular rotation)
                x_in = jnp.where((stage == 0) & (v == 0), x_mb, carry)
                seed_t = _tick_seed(base_seed, t, stage, v)
                y = tick(x_in, seed_t, v, *stacked_vals)
                # the last stage emits items on their FINAL circuit only
                ew = t - (S - 1)
                ewc = jnp.clip(ew, 0, E - 1)
                rw = ewc % SV
                idx = jnp.clip((ewc // SV) * S + (rw - S * (V - 1)),
                               0, M - 1)
                write = ((stage == S - 1) & (ew >= 0) & (ew < E)
                         & (rw >= S * (V - 1)))
                cur = lax.dynamic_index_in_dim(out_buf, idx, 0,
                                               keepdims=False)
                out_buf = lax.dynamic_update_index_in_dim(
                    out_buf, jnp.where(write, y, cur), idx, 0)
                carry = C.t_ppermute(y, axis, perm)
                return (carry, out_buf), None

            # the ring ppermute in `body` is traced ONCE but executes
            # E + S - 1 times per forward; noting it under scan_trips
            # makes the comm ledger trips-exact for the pipeline axis
            # (observability/commledger.py — AD synthesizes the reverse
            # ring as the ppermute transpose without re-entering the
            # noting shim, so only the forward schedule is recorded)
            with _cl.scan_trips(E + S - 1):
                (carry, out_buf), _ = lax.scan(
                    body, (carry, out_buf), jnp.arange(E + S - 1))
            return out_buf.reshape(x_val.shape)

        return fn

    # -- forward ---------------------------------------------------------
    def _run_seq(self, layers, x):
        for lyr in layers:
            if isinstance(x, tuple):
                x = lyr(*x)
            else:
                x = lyr(x)
        return x

    def _middle(self, x: Tensor) -> Tensor:
        if self._n_blocks == 0:
            return x
        pp_axes = self._pp_axes() if C.in_spmd_region() else None
        stacked = self._s_params
        svals = [p._value for p in stacked]
        seed = _rng.traced_seed()
        if seed is None:
            seed = jnp.uint32(np.random.randint(0, 2**31))
        if pp_axes is None:
            n_blocks = self._n_blocks
            vpp = self._vpp

            def fn(xv, *sv):
                if vpp > 1:
                    # chunked layout [vpp, L/vpp, ...] flattens back to
                    # global layer order for sequential application
                    sv = [s.reshape((n_blocks,) + s.shape[2:])
                          for s in sv]
                with _rng.fork_traced(seed):
                    return self._apply_rows(xv, sv, n_blocks)
        else:
            fn = self._pipe_fn(self._num_microbatches, seed, pp_axes)

        if _engine.is_grad_enabled() and (not x.stop_gradient or
                                          any(p.trainable for p in stacked)):
            out_val, vjp_fn = jax.vjp(fn, x._value, *svals)
            out = Tensor(out_val, stop_gradient=False)
            _engine.record_custom("pipeline_middle", lambda g: vjp_fn(g),
                                  [x] + list(stacked), [out], out_val)
        else:
            out = Tensor(fn(x._value, *svals), stop_gradient=True)
        return out

    # -- stage-owned prologue/epilogue -----------------------------------
    @staticmethod
    def _reachable_params(layers, extra=()) -> List[Parameter]:
        """Params the given layers (incl. shared-instance references and
        e.g. a parameterized loss Layer in ``extra``) can touch — the
        bind/vjp set for one _owned_apply call."""
        seen: Dict[int, Parameter] = {}
        def add(lyr):
            for p in lyr.parameters():
                seen.setdefault(id(p), p)
            ref = getattr(lyr, "_shared_ref", None)
            if ref is not None:
                add(ref)
        for lyr in list(layers) + [e for e in extra if isinstance(e, Layer)]:
            add(lyr)
        return list(seen.values())

    def _owned_apply(self, fn_eager, inputs: List[Tensor], owner: int,
                     pp_axes, own: Optional[List[Parameter]] = None
                     ) -> Tensor:
        """Run ``fn_eager(*inputs)`` only on pp stage ``owner`` via
        ``lax.cond`` — the other stages execute the zero branch, so the
        FLOPs (e.g. the 50K-vocab head) are actually skipped at
        runtime. ``lax.cond``'s vjp hands non-owners zero cotangents,
        which is exactly the grad-ownership masking the engine's 'pp'
        psum expects. ``own`` scopes the bind/vjp set to the params the
        callee can actually reach (no zero-cotangent churn for the
        other stage's params)."""
        if own is None:
            sid = {id(p) for p in self._s_params}
            own = [p for p in self.parameters() if id(p) not in sid]
        in_vals = [t._value for t in inputs]
        pvals = [p._value for p in own]
        axes = tuple(pp_axes)
        amb_seed = _rng.traced_seed()

        def pure(iv, pv):
            # fork an owner-distinct rng stream for the duration of the
            # call: without it, dropout inside the prologue/epilogue
            # splits the ambient traced key under jax.eval_shape's /
            # lax.cond's inner trace and leaks that tracer into the
            # global rng state (UnexpectedTracerError on the next use)
            ctx = (_rng.fork_traced(
                amb_seed * jnp.uint32(48271) + jnp.uint32(owner + 1))
                if amb_seed is not None else _nullcontext())
            with ctx, no_grad(), _bind(own, pv):
                out = fn_eager(*[Tensor(v, stop_gradient=True)
                                 for v in iv])
            return out._value

        out_sd = jax.eval_shape(pure, in_vals, pvals)

        def fn(iv, pv):
            stage = C.axis_index(axes)
            return lax.cond(
                stage == owner,
                lambda ops_: pure(*ops_),
                lambda ops_: jnp.zeros(out_sd.shape, out_sd.dtype),
                (iv, pv))

        needs_grad = _engine.is_grad_enabled() and (
            any(not t.stop_gradient for t in inputs)
            or any(p.trainable for p in own))
        if needs_grad:
            out_val, vjp_fn = jax.vjp(fn, in_vals, pvals)
            out = Tensor(out_val, stop_gradient=False)

            def bwd(g):
                div, dpv = vjp_fn(g)
                return list(div) + list(dpv)

            _engine.record_custom("pp_owned", bwd, list(inputs) + own,
                                  [out], out_val)
        else:
            out = Tensor(fn(in_vals, pvals), stop_gradient=True)
        return out

    def _pp_trunk(self, ins, pp_axes) -> Tensor:
        """Stage-0-owned prologue + pipelined middle (shared by
        forward/compute_loss under pp). Output rows are valid on the
        last stage only."""
        if len(self.prologue):
            x = self._owned_apply(
                lambda *ts: self._run_seq(
                    self.prologue, ts if len(ts) > 1 else ts[0]),
                list(ins), 0, pp_axes,
                own=self._reachable_params(self.prologue))
        else:
            x = ins[0]
        return self._middle(x)

    def forward(self, *args):
        pp_axes = self._pp_axes() if C.in_spmd_region() else None
        if pp_axes is None:
            x = self._run_seq(self.prologue,
                              args if len(args) > 1 else args[0])
            enforce(isinstance(x, Tensor),
                    "the pipelined middle takes a single Tensor")
            x = self._middle(x)
            return self._run_seq(self.epilogue, x)

        S = self._num_stages
        x = self._pp_trunk(args, pp_axes)
        if len(self.epilogue):
            out = self._owned_apply(
                lambda t: self._run_seq(self.epilogue, t), [x], S - 1,
                pp_axes, own=self._reachable_params(self.epilogue))
        else:
            out = x
        return _pp_collect(out, pp_axes, S - 1)

    def compute_loss(self, inputs, labels) -> Tensor:
        """forward + loss_fn; under pp the epilogue AND the loss run
        only on the last stage (lax.cond) and the scalar is broadcast."""
        enforce(self._loss_fn is not None,
                "PipelineLayer needs loss_fn for train_batch")
        ins = inputs if isinstance(inputs, (tuple, list)) else (inputs,)
        lbs = list(labels) if isinstance(labels, (tuple, list)) else [labels]
        pp_axes = self._pp_axes() if C.in_spmd_region() else None
        if pp_axes is None:
            out = self.forward(*ins)
            return self._loss_fn(out, *lbs)

        S = self._num_stages
        x = self._pp_trunk(ins, pp_axes)

        def tail(t, *lb):
            return self._loss_fn(self._run_seq(self.epilogue, t), *lb)

        loss = self._owned_apply(
            tail, [x] + lbs, S - 1, pp_axes,
            own=self._reachable_params(self.epilogue,
                                       extra=(self._loss_fn,)))
        return _pp_collect(loss, pp_axes, S - 1)

    def grad_bucket_seam(self):
        """The stacked-params chunk seam for layer-grained gradient
        bucketing (distributed/grad_buckets.py): ``[(param, k)]`` where
        the first ``k`` dims of each stacked parameter enumerate layer
        rows — 1 for the plain ``[L/pp, ...]`` stack, 2 for the circular
        interleave's ``[vpp, L/(pp*vpp), ...]`` chunk layout. The engine
        cuts these rows into size-targeted buckets and runs the grad
        reduce-scatter / pmean as a scan over them, so the per-bucket
        collective can overlap the neighboring buckets' work instead of
        waiting for the whole stacked grad."""
        k = 2 if self._vpp > 1 else 1
        return [(p, k) for p in self._s_params if p.trainable]

    # reference API parity helpers
    def get_num_stages(self) -> int:
        return self._num_stages

    def get_num_virtual_stages(self) -> int:
        """Chunks per stage in the circular interleaved schedule (1 =
        plain GPipe-family rotation)."""
        return self._vpp

    @property
    def parameters_in_stacked_blocks(self):
        return list(self._s_params)


# -- pp ownership / collect custom ops ----------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _pp_collect_raw(x, axes, src):
    stage = C.axis_index(axes)
    return C.t_psum(jnp.where(stage == src, x,
                             jnp.zeros((), x.dtype)), axes)


_pp_collect_raw.defvjp(
    lambda x, axes, src: (_pp_collect_raw(x, axes, src), None),
    lambda axes, src, _, g: (jnp.where(C.axis_index(axes) == src, g,
                                       jnp.zeros((), g.dtype)),))


def _pp_collect(x: Tensor, axes, src) -> Tensor:
    """Broadcast the last stage's tensor to all pp ranks; cotangent is
    masked to the source stage (gradient ownership)."""
    val = _pp_collect_raw(x._value, tuple(axes), src)
    out = Tensor(val, stop_gradient=x.stop_gradient)
    if _engine.is_grad_enabled() and not x.stop_gradient:
        out.stop_gradient = False

        def bwd(g):
            return (jnp.where(C.axis_index(tuple(axes)) == src, g,
                              jnp.zeros((), g.dtype)),)

        _engine.record_custom("pp_collect", bwd, [x], [out], val)
    return out


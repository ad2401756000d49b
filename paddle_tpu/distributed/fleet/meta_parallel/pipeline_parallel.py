"""PipelineParallel — the train_batch driver for PipelineLayer models.

Reference surface: python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py — ``PipelineParallel.train_batch`` (:689) driving the
1F1B schedule (forward_backward_pipeline :455) with Python-side NCCL p2p
per microbatch.

TPU-native: the whole schedule (all microbatches, forward AND backward,
plus the optimizer update) is ONE compiled XLA program built by
``ParallelEngine`` — the pipeline rotation lives inside the model's
``PipelineLayer._pipe_fn`` (lax.scan + ppermute), and its jax.vjp is the
reverse schedule. Host Python dispatches one executable per step instead
of 4·M p2p calls, which removes the per-microbatch launch overhead the
reference pays (SURVEY.md §7 hard parts: "1F1B under XLA"). The same
program expresses interleaved virtual stages (``pp_configs
["num_virtual_pipeline_stages"] > 1``) as a circular rotation — see the
pp_layers module docstring; this wrapper validates the microbatch-count
constraint that schedule adds (accumulate_steps % pp == 0).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from ....core.enforce import enforce
from ....tensor import Tensor
from ...engine import ParallelEngine
from .parallel_layers.pp_layers import PipelineLayer
from .tensor_parallel import _DelegateWrapper

__all__ = ["PipelineParallel"]


def _unwrap_optimizer(opt):
    return getattr(opt, "_inner_opt", opt)


class PipelineParallel(_DelegateWrapper):
    def __init__(self, layers: PipelineLayer, hcg, strategy):
        enforce(isinstance(layers, PipelineLayer),
                "PipelineParallel expects a PipelineLayer model")
        super().__init__(layers, hcg, strategy)
        pconf = getattr(strategy, "pipeline_configs", {}) or {}
        self.accumulate_steps = int(pconf.get("accumulate_steps", 1))
        self.micro_batch_size = int(pconf.get("micro_batch_size", 0))
        self._engine: Optional[ParallelEngine] = None
        self._train_step = None
        self._eval_steps: Dict[bool, Any] = {}
        self.total_loss = None

    # -- engine plumbing -------------------------------------------------
    @property
    def engine(self) -> Optional[ParallelEngine]:
        """The ParallelEngine behind the compiled pipeline step (its
        stats, ledgers and lowered text); None before the first
        train_batch."""
        return self._engine

    def _ensure_engine(self, optimizer):
        if self._engine is None:
            self._layers._num_microbatches = self.accumulate_steps
            self._engine = ParallelEngine(
                self._layers, _unwrap_optimizer(optimizer),
                self._hcg.mesh if self._hcg is not None else None)
        return self._engine

    def _check_batch(self, inputs):
        if self._hcg is None:
            return
        # circular-interleave feasibility, named by knob: microbatches
        # enter the ring in groups of pp_degree (pp_layers._pipe_fn)
        vpp = getattr(self._layers, "_vpp", 1)
        pp = self._hcg.get_pipe_parallel_world_size()
        if vpp > 1:
            enforce(self.accumulate_steps % pp == 0,
                    "pipeline_configs['accumulate_steps'] "
                    f"({self.accumulate_steps}) must be a multiple of "
                    f"pp_degree ({pp}) when pp_configs"
                    f"['num_virtual_pipeline_stages'] is {vpp}: the "
                    "circular schedule admits microbatches in groups of "
                    "pp_degree so each returning circuit slots into the "
                    "ring tick its carry arrives on")
        if self.micro_batch_size <= 0:
            return
        first = inputs[0] if isinstance(inputs, (tuple, list)) else inputs
        data_deg = (self._hcg.get_data_parallel_world_size()
                    * self._hcg.get_sharding_parallel_world_size())
        want = self.micro_batch_size * self.accumulate_steps * data_deg
        enforce(first.shape[0] == want,
                f"global batch {first.shape[0]} != micro_batch_size "
                f"{self.micro_batch_size} x accumulate_steps "
                f"{self.accumulate_steps} x data degree {data_deg}")

    # -- reference API ---------------------------------------------------
    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        """One full pipeline step: data = [inputs, labels].

        (reference pipeline_parallel.py:689 — here fwd+bwd over all
        microbatches plus the optimizer step execute as one XLA program.)
        """
        inputs, labels = data
        self._check_batch(inputs)
        if lr_scheduler is not None:
            # the engine advances the optimizer's attached schedule once
            # per step — attach the caller's so it is the one advanced
            _unwrap_optimizer(optimizer).set_lr_scheduler(lr_scheduler)
        eng = self._ensure_engine(optimizer)
        if self._train_step is None:
            def fn(model, batch):
                return model.compute_loss(batch["inputs"], batch["labels"])

            # the scaler of the FIRST call is baked into the compiled
            # step (the traced dynamic loss-scaling protocol)
            self._train_step = eng.train_step(fn, scaler=scaler)
        return self._train_step({"inputs": inputs, "labels": labels})

    # -- crash-consistent checkpointing ---------------------------------
    def save_checkpoint(self, path=None, **kw):
        """Checkpoint the compiled pipeline's full training state
        (ParallelEngine.save_checkpoint): params incl. the pp x vpp
        stacked chunks shard-exact, ZeRO-scattered moments, AMP
        state, counters, RNG."""
        enforce(self._engine is not None,
                "run train_batch once before save_checkpoint (the "
                "engine owns the optimizer state being saved)")
        return self._engine.save_checkpoint(path, **kw)

    def restore_checkpoint(self, path, optimizer=None, scaler=None):
        """Restore from a committed checkpoint, resharding to the
        current topology. Callable before the first train_batch when
        ``optimizer`` is given (the engine is built here so moments
        have shaped, sharded targets to land in)."""
        if self._engine is None:
            enforce(optimizer is not None,
                    "restore_checkpoint before the first train_batch "
                    "needs the optimizer (it owns the moment targets)")
            self._ensure_engine(optimizer)
        return self._engine.restore_checkpoint(path, scaler=scaler)

    def profile_exposed_comm(self, data, repeats: int = 3,
                             publish: bool = True):
        """Exposed-comm attribution of the compiled pipeline step
        (ParallelEngine.profile_exposed_comm): per-axis overlapped-vs-
        exposed comm split + the grad_sync_exposed_seconds gauge.
        Offline — run between steps; engine state is restored."""
        inputs, labels = data
        enforce(self._train_step is not None,
                "run train_batch once before profile_exposed_comm "
                "(the compiled step and its comm ledger must exist)")
        return self._engine.profile_exposed_comm(
            self._train_step, {"inputs": inputs, "labels": labels},
            repeats=repeats, publish=publish)

    def eval_batch(self, data, compute_loss: bool = True):
        inputs, labels = data
        eng = self._engine
        enforce(eng is not None, "call train_batch once before eval_batch "
                "(or use forward directly)")
        if compute_loss not in self._eval_steps:
            from jax.sharding import PartitionSpec as P

            from ... import collective as C

            axes = tuple(a for a in eng.mesh.axis_names
                         if eng.mesh.shape[a] > 1)

            def fn(model, batch, _loss=compute_loss):
                if _loss:
                    loss = model.compute_loss(batch["inputs"],
                                              batch["labels"])
                    v = C.t_pmean(loss._value, axes) if axes else loss._value
                    return Tensor(v, stop_gradient=True)
                return model(batch["inputs"])

            self._eval_steps[compute_loss] = (
                eng.eval_step(fn), P() if compute_loss else None)
        step, out_spec = self._eval_steps[compute_loss]
        return step({"inputs": inputs, "labels": labels}, out_spec=out_spec)

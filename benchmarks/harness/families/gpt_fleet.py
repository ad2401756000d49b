"""GPT through Fleet: ``fleet.init`` -> ``ParallelEngine.train_step`` on
one chip or a dp/mp mesh, ``GPTForCausalLMPipe`` ->
``fleet.distributed_model(...).train_batch`` where ``pp_degree`` > 1
(the way ``chip_smoke.py`` builds both). Serves ``train_steps`` traffic.
"""
from __future__ import annotations

import gc
import re
import statistics
from typing import Dict, List

import numpy as np

from .. import weights
from ..laps import Laps
from ...references import gpt as ref

FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv")
# Left out of the update-norm comparison: a third of ``qkv.b`` is the key
# bias, whose gradient is zero in exact arithmetic (softmax does not see
# a shift of every key), so AdamW divides rounding noise by rounding
# noise there: the bf16 program moves those elements a full step, the
# float32 reference does not, and the leaf reads a gap of 0.18-0.20 in
# every sound run. Its first gradient stays under the gradient-norm
# comparison.
UPDATE_NORM_SKIP = "qkv.b"
_LAYER = {"ln1.weight": "ln1.w", "ln1.bias": "ln1.b",
          "attn.qkv_proj.weight": "qkv.w", "attn.qkv_proj.bias": "qkv.b",
          "attn.out_proj.weight": "proj.w", "attn.out_proj.bias": "proj.b",
          "ln2.weight": "ln2.w", "ln2.bias": "ln2.b",
          "mlp.fc1.weight": "fc1.w", "mlp.fc1.bias": "fc1.b",
          "mlp.fc2.weight": "fc2.w", "mlp.fc2.bias": "fc2.b"}


def _names_of(pname: str, n_layers: int):
    """Program parameter name -> the reference's leaf name(s)."""
    m = re.match(r"gpt\.layers\.(\d+)\.(.+)$", pname)
    if m:
        return f"h.{m.group(1)}.{_LAYER[m.group(2)]}"
    if pname.startswith("blocks__"):           # the pipeline's stack
        short = _LAYER[pname[len("blocks__"):].replace("__", ".")]
        return [f"h.{i}.{short}" for i in range(n_layers)]
    fixed = {"gpt.embeddings.word_embeddings.weight": "wte",
             "gpt.embeddings.position_embeddings.weight": "wpe",
             "gpt.final_ln.weight": "lnf.w", "gpt.final_ln.bias": "lnf.b",
             "prologue.0.word_embeddings.weight": "wte",
             "prologue.0.position_embeddings.weight": "wpe",
             "epilogue.0.weight": "lnf.w", "epilogue.0.bias": "lnf.b"}
    return fixed[pname]


class System:
    def __init__(self, cfg: Dict, traffic: Dict, plan: Dict, seed: int,
                 devices):
        import jax
        from jax.sharding import NamedSharding

        import paddle_tpu as paddle
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.engine import (ParallelEngine,
                                                   param_spec)
        from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                       GPTForCausalLMPipe,
                                       GPTPretrainingCriterion)

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.table = ref.leaf_table(cfg)
        par = {k: v for k, v in cfg["parallel"].items() if v > 1}
        self.n_chips = int(np.prod(list(par.values()) or [1]))
        self.pipe = par.get("pp_degree", 1) > 1
        B, S = plan["batch"], plan["seq"]
        self.tokens_per_step = B * S
        gcfg = GPTConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["d_model"],
            num_layers=cfg["n_layers"], num_heads=cfg["n_heads"],
            intermediate_size=cfg["d_ff"],
            max_position_embeddings=cfg["n_ctx"],
            layer_norm_eps=cfg["layer_norm_eps"],
            initializer_range=cfg["initializer_range"],
            dtype=cfg["dtype"])
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = dict(par) or {"dp_degree": 1,
                                                "mp_degree": 1}
        if self.pipe:
            mb = plan["micro_batch"]
            strategy.pipeline_configs = {"accumulate_steps": B // mb,
                                         "micro_batch_size": mb}
        hcg = fleet.init(is_collective=True, strategy=strategy)
        self.mesh = hcg.mesh
        paddle.seed(seed % (2 ** 31))    # the program's own stream; the
        #                                  weights below do not use it
        if self.pipe:
            # the pipeline stacks its blocks' values as it is built, so
            # it cannot be built lazily; build it in the stored type
            paddle.set_default_dtype(cfg["dtype"])
            model = GPTForCausalLMPipe(gcfg)
        else:
            with paddle.LazyGuard():
                model = GPTForCausalLM(gcfg)
        named = list(model.named_parameters())
        self.names = [_names_of(n, cfg["n_layers"]) for n, _ in named]
        self.gen = weights.load(
            named, dict(zip([n for n, _ in named], self.names)),
            self.table, seed, cfg["dtype"],
            lambda p: NamedSharding(self.mesh, param_spec(p)))
        self.params = [p for _, p in named]
        o = cfg["optimizer"]
        opt = paddle.optimizer.AdamW(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"],
            weight_decay=o["weight_decay"],
            parameters=model.parameters(), state_dtype=o["state_dtype"])
        ids = plan["ids"]
        self.batches = [(paddle.to_tensor(ids[k, :, :-1]),
                         paddle.to_tensor(ids[k, :, 1:]))
                        for k in range(ids.shape[0])]
        self.host_batches = [(ids[k, :, :-1], ids[k, :, 1:])
                             for k in range(ids.shape[0])]
        if self.pipe:
            dist_model = fleet.distributed_model(model)
            dopt = fleet.distributed_optimizer(opt)
            self._engine = lambda: dist_model.engine
            self._step = lambda x, y: dist_model.train_batch([x, y], dopt)
        else:
            crit = GPTPretrainingCriterion(gcfg)
            eng = ParallelEngine(model, opt, hcg.mesh)
            fn = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
            self._engine = lambda: eng
            self._step = lambda x, y: fn({"x": x, "y": y})
        self.steps_done = 0
        self.first = {}
        self._jax = jax

    # -- the timed call -------------------------------------------------------
    def step(self, i: int) -> float:
        x, y = self.batches[i % len(self.batches)]
        self.steps_done = i + 1
        return float(self._step(x, y))      # float() waits for the device

    def warm(self) -> Dict:
        """The first three steps, through the window's own call and feed:
        step 0 compiles; the optimizer's state after it gives the first
        gradient; the parameters after step 1 give the change the
        reference follows."""
        laps = Laps()
        losses = [self.step(0)]
        laps.mark("step0_compile_or_fetch")
        self.first["grad_norm"] = self._moment_norms()
        self.first["grad_sample"] = self._moment_sample()
        laps.mark("moment_norms")
        losses.append(self.step(1))
        laps.mark("step1")
        self.first["update_norm"] = self._update_norms()
        laps.mark("update_norms")
        losses.append(self.step(2))
        laps.mark("step2")
        self.first["loss"] = losses
        return {"first_losses": losses, "seconds": laps.seconds}

    def _leaf_norms(self, f, *lists) -> Dict[str, float]:
        """||f(x, ...)|| per leaf, reduced inside one jitted call (no
        full-size temporaries left on the device); a stacked parameter
        gives one norm per layer."""
        import jax.numpy as jnp

        def norms(*ls):
            out = []
            for n, *vs in zip(self.names, *ls):
                v = f(*[x.astype(jnp.float32) for x in vs])
                axes = None if isinstance(n, str) \
                    else tuple(range(1, v.ndim))
                out.append(jnp.sqrt(jnp.sum(jnp.square(v), axis=axes)))
            return out

        got = self._jax.device_get(self._jax.jit(norms)(*lists))
        out = {}
        for n, g in zip(self.names, got):
            if isinstance(n, str):
                out[n] = float(g)
            else:
                out.update(zip(n, map(float, np.asarray(g).reshape(-1))))
        return out

    def _moment_norms(self) -> Dict[str, float]:
        """||g|| per leaf from AdamW's first moment after one step:
        m1 = (1 - beta1) g."""
        states = self._engine().optimizer._states
        m1 = [states[id(p)]["moment1"] for p in self.params]
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        return {k: v * scale
                for k, v in self._leaf_norms(lambda m: m, m1).items()}

    def _moment_sample(self) -> Dict[str, np.ndarray]:
        """The first rows of the first gradient of a few leaves, whole,
        from the same first moment (see ``references/gpt.py``)."""
        states = self._engine().optimizer._states
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        out = {}
        for want in ref.sampled_leaves(self.cfg):
            for p, n in zip(self.params, self.names):
                m1 = states[id(p)]["moment1"]
                if n == want:
                    block = m1[:ref.SAMPLE_ROWS]
                elif not isinstance(n, str) and want in n:
                    block = m1[n.index(want), :ref.SAMPLE_ROWS]
                else:
                    continue
                out[want] = np.asarray(block).astype(np.float32) * scale
        return out

    def _update_norms(self) -> Dict[str, float]:
        """||p - p0|| per leaf, p0 made again from the seed."""
        p0 = list(self.gen(weights.seed_key(self.seed)))
        return self._leaf_norms(lambda a, b: a - b,
                                [p._value for p in self.params], p0)

    # -- after the window -----------------------------------------------------
    def kernels_present(self) -> Dict[str, bool]:
        text = self._engine().lowered_text() or ""
        found = set(re.findall(r'kernel_name = "([^"]+)"', text))
        return {k: k in found for k in FLASH_KERNELS}

    def compiles(self) -> int:
        return self._engine().stats.compiles

    def free(self) -> None:
        for p in self.params:
            p._value = None
        self.params = self.batches = None
        self._step = self._engine = self.gen = None
        gc.collect()
        self._jax.clear_caches()
        gc.collect()


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


def worst_leaf_gap(prog: Dict[str, float], want: Dict[str, float],
                   skip: str = "") -> Dict:
    """The gap between the program's norm and the reference's, by the
    worst leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero).
    Leaves whose name ends in ``skip`` are left out; ``next`` is the
    runner-up."""
    med = statistics.median(want.values())
    gaps = {k: abs(prog[k] - want[k]) / max(want[k], med, 1e-30)
            for k in want if not (skip and k.endswith(skip))}
    k, *rest = sorted(gaps, key=gaps.get, reverse=True)
    return {"value": gaps[k], "leaf": k, "program": prog[k],
            "reference": want[k],
            "next": f"{rest[0]}:{gaps[rest[0]]:.4g}" if rest else None}


def numbers(got: Dict, want: Dict, limits: Dict) -> List[Dict]:
    """Each number compared, beside its limit. ``got`` is the program's
    first steps (or the control's), ``want`` the reference's."""
    out = []
    for i in range(2):
        out.append({"name": f"loss step {i} |program - reference|",
                    "value": abs(got["loss"][i] - want["loss"][i]),
                    "limit": limits["loss_abs"],
                    "program": got["loss"][i],
                    "reference": want["loss"][i]})
    out.append(dict(worst_leaf_gap(got["grad_norm"], want["grad_norm"]),
                    name="first gradient norm, worst leaf gap",
                    limit=limits["grad_norm_gap"]))
    diffs = {k: float(np.linalg.norm(got["grad_sample"][k] - w)
                      / np.linalg.norm(w))
             for k, w in want["grad_sample"].items()}
    k = max(diffs, key=diffs.get)
    out.append({"name": "first gradient, relative difference over sampled "
                        "blocks, worst leaf", "value": diffs[k], "leaf": k,
                "limit": limits["grad_diff"]})
    out.append(dict(worst_leaf_gap(got["update_norm"],
                                   want["update_norm"], UPDATE_NORM_SKIP),
                    name="parameter change after two steps, worst leaf gap",
                    limit=limits["update_norm_gap"]))
    return out


def check(system: System, result: Dict) -> List[Dict]:
    """Compare what the timed object produced in its first steps with the
    plain reference. Frees the program first: the reference needs the
    memory, and ``memory_peak_bytes`` has been read."""
    import jax

    cfg, seed = system.cfg, system.seed
    first, batches = system.first, system.host_batches[:2]
    kernels = system.kernels_present() \
        if jax.devices()[0].platform == "tpu" else {}
    devices = list(system.mesh.devices.flat)
    system.free()
    want = ref.TrainReference(cfg, seed, devices).run(batches)
    out = [{"name": f"kernel {k} in the compiled step", "value": int(not v),
            "limit": 0} for k, v in kernels.items()]
    system.details = {"program": {k: v for k, v in first.items()
                                  if k != "grad_sample"},
                      "reference": {k: v for k, v in want.items()
                                    if k != "grad_sample"}}
    return out + numbers(first, want, cfg["limits"])


def control(system: System, result: Dict) -> Dict:
    """The reference in the program's place, computed in the nearest
    precision below the configuration's bf16 (fp8 operands in every
    matmul): what its numbers read against the float32 reference,
    beside the program's. Run by ``tools/control.py``, never by a
    benchmark run."""
    cfg, seed = system.cfg, system.seed
    first, batches = system.first, system.host_batches[:2]
    devices = list(system.mesh.devices.flat)
    system.free()
    want = ref.TrainReference(cfg, seed, devices).run(batches)
    low = ref.TrainReference(cfg, seed, devices, "fp8").run(batches)
    return {"program": numbers(first, want, cfg["limits"]),
            "control": numbers(low, want, cfg["limits"])}

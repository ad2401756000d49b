"""Kernel autotuning: measured block-size selection with a persistent
algorithm cache.

(reference: paddle/phi/kernels/autotune/cache.h AlgorithmsCache +
switch_autotune.cc AutoTuneStatus — exhaustive-search cuDNN algo
selection keyed by shape/dtype, cached in memory for the process; here
additionally persisted to disk so later processes skip the search.)

TPU-native: the tunable is the Pallas BlockSpec tiling (block_q,
block_kv) of the flash kernels. Tuning runs EAGER side-benchmarks with
synthetic inputs — legal even while an outer jit is tracing, since
block sizes are trace-time Python values.

Off by default (tuning compiles each candidate once — seconds of
one-time cost per new shape); enable with
``paddle.set_flags({"FLAGS_use_autotune": True})``.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

__all__ = ["AlgoCache", "get_cache", "autotune"]


class AlgoCache:
    """In-memory + on-disk map: key string -> chosen config."""

    def __init__(self, path: Optional[str] = None):
        self._mem: Dict[str, list] = {}
        self._path = path
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    self._mem.update(json.load(f))
            except Exception:
                pass

    def get(self, key: str):
        v = self._mem.get(key)
        return tuple(v) if isinstance(v, list) else v

    def put(self, key: str, value) -> None:
        self._mem[key] = list(value) if isinstance(value, tuple) else value
        if self._path:
            try:
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                with open(self._path, "w") as f:
                    json.dump(self._mem, f)
            except Exception:
                pass

    def size(self) -> int:
        return len(self._mem)


_cache: Optional[AlgoCache] = None


def _default_path() -> Optional[str]:
    p = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")
    if p == "":
        return None  # explicit opt-out of persistence
    # beside the compile cache, inside the checkout: state that changes
    # which kernel is compiled does not live in the user's home
    from ..._bootstrap import CACHE_ROOT

    return p or os.path.join(CACHE_ROOT, "autotune.json")


def get_cache() -> AlgoCache:
    global _cache
    if _cache is None:
        _cache = AlgoCache(_default_path())
    return _cache


def autotune(key: str, candidates: Sequence, measure: Callable,
             cache: Optional[AlgoCache] = None):
    """Return the cached choice for ``key`` or measure all candidates
    (``measure(candidate) -> seconds``; inf/exception = infeasible) and
    cache the argmin."""
    cache = cache or get_cache()
    hit = cache.get(key)
    if hit is not None:
        return hit
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            t = measure(cand)
        except Exception:
            continue
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        raise RuntimeError(f"autotune: no feasible candidate for {key}")
    cache.put(key, best)
    return best


def measure_flash_blocks(q_shape, kv_len: int, dtype, causal: bool,
                         reps: int = 5) -> Callable:
    """Measurement closure for the flash forward kernel: compile the
    candidate blocks and time ``reps`` runs at the REAL (possibly
    rectangular) problem shape."""
    import numpy as np
    import jax.numpy as jnp

    from .flash_attention import _pallas_fa

    B, S, H, D = q_shape
    r = np.random.RandomState(0)
    q3 = jnp.asarray(r.randn(B * H, S, D), dtype)
    k3 = jnp.asarray(r.randn(B * H, kv_len, D), dtype)
    v3 = jnp.asarray(r.randn(B * H, kv_len, D), dtype)
    scale = 1.0 / np.sqrt(D)

    def measure(cand) -> float:
        bq, bkv = cand
        if S % bq or kv_len % bkv:
            return float("inf")
        out = _pallas_fa(q3, k3, v3, None, None, H, causal, scale, bq,
                         bkv, False)[0]
        out.block_until_ready()  # compile + settle
        t0 = time.perf_counter()
        for _ in range(reps):
            out = _pallas_fa(q3, k3, v3, None, None, H, causal, scale,
                             bq, bkv, False)[0]
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps

    return measure

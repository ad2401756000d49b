"""Flop accountant: model FLOPs/token + MFU from the model config.

MFU follows the PaLM/Megatron convention (PAPERS.md: Megatron-LM): a
decoder-only transformer spends ~6*N FLOPs per token (fwd 2N + bwd 4N),
optionally plus the attention term 12*L*h*S that 6N omits; recompute
FLOPs are deliberately EXCLUDED so remat lowers measured MFU honestly.
The accountant reads whatever config the model carries (GPTConfig /
LlamaConfig expose ``num_params()``); when there is no config it falls
back to summing parameter sizes, which the engine can always do.
"""
from __future__ import annotations

from typing import Optional, Tuple

__all__ = ["params_from_config", "train_flops_per_token",
           "PEAKS_BY_DEVICE_KIND", "peak_flops_per_chip", "mfu",
           "ici_bytes_per_sec", "comm_seconds_lower_bound"]

# The one peaks table, keyed by ``device_kind`` as JAX reports it:
# (dense bf16 FLOP/s, HBM bytes/s, aggregate ICI bytes/s) per chip.
# Source: Google Cloud TPU documentation, the system-architecture page
# of each generation ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600
# Gbit/s interchip interconnect; likewise "TPU v4", "TPU v5p", "TPU
# v6e"). A TPU whose kind is not here is an error, not a default: every
# MFU, roofline share and comm floor would silently be about another
# chip.
_V5E = (197e12, 0.819e12, 200e9)
_V5P = (459e12, 2.765e12, 600e9)
_V6E = (918e12, 1.64e12, 448e9)
PEAKS_BY_DEVICE_KIND = {        # both spellings jax itself matches on
    "TPU v4": (275e12, 1.2e12, 300e9),
    "TPU v5 lite": _V5E, "TPU v5e": _V5E,
    "TPU v5": _V5P, "TPU v5p": _V5P,
    "TPU v6 lite": _V6E, "TPU v6e": _V6E,
}


def _peaks(device) -> Tuple[float, float, float]:
    """Table row for a jax device. CPU is a device the table knows:
    explicit zeros (MFU, roofline and comm floors are then reported as
    0, well-defined). An unknown TPU kind raises."""
    if device.platform != "tpu":
        return (0.0, 0.0, 0.0)
    kind = device.device_kind
    if kind not in PEAKS_BY_DEVICE_KIND:
        raise KeyError(
            f"TPU device_kind {kind!r} is not in the peaks table "
            f"(paddle_tpu/observability/flops.py knows "
            f"{sorted(PEAKS_BY_DEVICE_KIND)}); add its published peaks "
            "with their source")
    return PEAKS_BY_DEVICE_KIND[kind]


def ici_bytes_per_sec(device) -> float:
    """Aggregate ICI bytes/s (all links, both directions) of a jax
    device's chip; 0.0 on CPU (no ICI). The comm floor below uses it to
    turn ledger wire bytes into a lower-bound transfer time."""
    return _peaks(device)[2]


def comm_seconds_lower_bound(wire_bytes: float, device) -> float:
    """Analytic floor for moving ``wire_bytes`` (per participant, the
    comm ledger's closed-form accounting) over ICI: bytes / aggregate
    per-chip bandwidth. The per-bucket grad-sync attribution divides a
    step's ledger bytes by this to sanity-check exposed-comm numbers:
    exposed seconds below the floor mean the collective overlapped."""
    bw = ici_bytes_per_sec(device)
    if bw <= 0:
        return 0.0
    return float(wire_bytes) / bw


def params_from_config(config) -> Optional[int]:
    """Parameter count from a model config, or None (configs across the
    model zoo expose ``num_params()``; anything else is ignored)."""
    fn = getattr(config, "num_params", None)
    if callable(fn):
        try:
            return int(fn())
        except Exception:
            return None
    return None


def train_flops_per_token(n_params: int, *, config=None,
                          with_attention: bool = True) -> float:
    """~FLOPs one training token costs: 6*N plus (when the config
    exposes layer geometry) the 12*L*h*S attention-matmul term."""
    f = 6.0 * n_params
    if with_attention and config is not None:
        L = getattr(config, "num_layers", None)
        h = getattr(config, "hidden_size", None)
        S = getattr(config, "max_position_embeddings", None)
        if L and h and S:
            f += 12.0 * L * h * S
    return f


def peak_flops_per_chip(device) -> Tuple[float, float]:
    """(peak dense bf16 FLOPs/s, HBM bytes/s) for a jax device; (0, 0)
    on CPU, where MFU is not meaningful."""
    return _peaks(device)[:2]


def mfu(n_params: int, tokens_per_sec: float, n_devices: int,
        peak_per_chip: float, *, config=None) -> float:
    """Model-FLOPs utilization of the whole slice; 0.0 when peak is
    unknown (CPU) so gauges stay well-defined everywhere."""
    denom = peak_per_chip * max(n_devices, 1)
    if denom <= 0:
        return 0.0
    return train_flops_per_token(n_params, config=config) \
        * tokens_per_sec / denom

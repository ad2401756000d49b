"""Mixture-of-Experts with expert parallelism
(reference: python/paddle/incubate/distributed/models/moe/)."""
from .gate import (BaseGate, GShardGate, NaiveGate,  # noqa: F401
                   SigmoidTopKGate, SwitchGate)
from .moe_layer import GatedMoELayer, MoELayer  # noqa: F401

__all__ = ["MoELayer", "GatedMoELayer", "BaseGate", "NaiveGate",
           "GShardGate", "SwitchGate", "SigmoidTopKGate"]

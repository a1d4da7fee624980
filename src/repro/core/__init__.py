"""VESTA core: the paper's contribution — spiking transformer compute with
unified dataflows (ZSC / SSSC / WSSL / STDP) and the Temporal-Fused LIF."""
from . import lif, spike, unified, ssa, spikformer, qkformer, engine_model  # noqa: F401

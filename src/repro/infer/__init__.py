"""Packed-bit Spikformer inference: the bridge from the float training
reference to VESTA's unified-PE datapath, behind a compile/serve split —
``compile(params, cfg, plan)`` lowers to a ``CompiledModel``,
``MicroBatchEngine`` serves it (and ``replicate_model`` places copies for
the multi-replica fleet). Every serving surface implements the
``ServeClient`` protocol with the versioned ``serve_stats`` schema. See
README.md in this directory."""
from .backends import (FloatBackend, OccupancyRecorder, PackedBackend,
                       chunk_occupancy, get_backend, spike_occupancy,
                       value_chunk_occupancy)
from .compile import (CompiledModel, ExecutionPlan,
                      calibrate_layer_occupancy, compile, fold_bn,
                      linear_layer_paths, lower, plan_route_tables,
                      quantize_weights,
                      replicate_model, strip_lut_annotations)
from .engine import (PAPER_FPS, SERVE_STATS_VERSION, MicroBatchEngine,
                     QueueDepthWatermark, Request, ServeClient,
                     batch_occupancy, serve_stats)
from .quant import quantize_folded, quantize_layer
from .registry import (BackendSpec, backend_spec, list_backends,
                       register_backend, unregister_backend)

__all__ = [
    # compile half
    "ExecutionPlan", "CompiledModel", "compile", "replicate_model",
    "fold_bn", "quantize_weights", "plan_route_tables", "lower",
    "strip_lut_annotations",
    "calibrate_layer_occupancy", "linear_layer_paths",
    # serve half
    "MicroBatchEngine", "Request", "PAPER_FPS", "batch_occupancy",
    "ServeClient", "serve_stats", "SERVE_STATS_VERSION",
    "QueueDepthWatermark",
    # backends + registry
    "FloatBackend", "PackedBackend", "OccupancyRecorder", "get_backend",
    "spike_occupancy", "chunk_occupancy", "value_chunk_occupancy",
    "BackendSpec", "register_backend", "unregister_backend",
    "backend_spec", "list_backends",
    # quantization
    "quantize_folded", "quantize_layer",
]

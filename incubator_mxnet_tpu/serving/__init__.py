"""Inference serving subsystem (ISSUE 3 + ISSUE 8): shape-bucketed
dynamic batching over pre-compiled executables, hardened for sustained
multi-tenant overload — the deploy-side counterpart of the resilient
trainer (PR 1) and the async device feed (PR 2).

    from incubator_mxnet_tpu import serving
    eng = net.inference_engine(ctx=mx.gpu())       # or serving.InferenceEngine(net)
    eng.warmup(example_shape=(3, 224, 224), wire_dtype="uint8")
    fut = eng.submit(img, lane="high", tenant="acme")  # concurrent Future
    probs = fut.result()
    eng.close()

Many models on one device pool go through the ModelRegistry (HBM
admission control from the cost registry, per-model circuit
breakers)::

    reg = serving.ModelRegistry(devices=[mx.gpu(0), mx.gpu(1)])
    reg.register("ranker", net, example_shape=(256,))
    reg.warmup("ranker")
    fut = reg.submit("ranker", x, lane="high", deadline=0.05)

Int8 tenants ride the same contract at ~1/4 the admission footprint
(ISSUE 15; see docs/quantization.md)::

    net, report = serving.quantize_for_serving(net, calib_batches)
    reg.register_quantized("ranker8", net2, calib_batches,
                           example_shape=(256,))

See docs/serving.md for lifecycle, admission math, the lane/shed
decision table and the counter reference.
"""
from .engine import (InferenceEngine, QueueFull, DeadlineExceeded,
                     EngineClosed, Shed, serve_counters)
from .registry import (ModelRegistry, AdmissionDenied, CircuitOpen,
                       UnknownModel, RegistrationTimeout,
                       project_footprint)
from .controlplane import FleetSupervisor
from .generation import (GenerationEngine, GenerationStream,
                         project_generation_footprint)
from .quantize import quantize_for_serving, param_bytes_by_dtype

__all__ = ["InferenceEngine", "QueueFull", "DeadlineExceeded",
           "EngineClosed", "Shed", "serve_counters",
           "ModelRegistry", "AdmissionDenied", "CircuitOpen",
           "UnknownModel", "RegistrationTimeout",
           "project_footprint", "FleetSupervisor",
           "GenerationEngine", "GenerationStream",
           "project_generation_footprint",
           "quantize_for_serving", "param_bytes_by_dtype"]

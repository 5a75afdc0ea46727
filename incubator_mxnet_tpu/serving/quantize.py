"""Post-training quantization as a serving compile-time transform
(ISSUE 15 tentpole).

The CNN-inference-accelerator compilation flow (PAPERS.md) frames
quantization as a GRAPH TRANSFORM applied at compile time, and that is
exactly the shape of this serving stack's zero-recompile contract: the
executable set is closed at warmup, so the right place to change the
arithmetic is BEFORE the buckets are traced, not inside them.
`quantize_for_serving` is that step:

1. **Calibrate** over N batches — ``naive`` (min/max) or ``entropy``
   (KL-divergence thresholds), both from `contrib.quantization` — so
   every quantized layer carries fixed activation ranges and the
   traced executables contain no data-dependent range reductions.
2. **Rewrite** the model in place: Dense/Conv2D children become
   `QuantizedDense`/`QuantizedConv2D` whose int8 weights are
   non-trainable PARAMETERS — they flow into the bucket executables as
   arguments (replicated once per serving device, priced by admission
   at 1 byte/element), never as per-bucket baked constants.
3. **Report**: layer count, calibration mode/wall, and the weight-byte
   split before/after — the ~4x shrink is what turns one device's HBM
   budget into ~4x the admitted tenants (`ModelRegistry`), which is
   the fleet-capacity story, not just the latency one.

The returned block then goes through the SAME `InferenceEngine` /
`ModelRegistry` paths as any f32 model: `warmup()` compiles
the power-of-two buckets, `serve.traces` stays flat under organic
traffic, and `warmup()`→`reconcile()` swaps the int8 projection for
the measured memory-analysis rows.
"""
from __future__ import annotations

import logging
import time

from .. import config as _cfg
from ..monitor import events
from ..telemetry import flightrec as _bb

__all__ = ["quantize_for_serving", "param_bytes_by_dtype"]

log = logging.getLogger(__name__)


def param_bytes_by_dtype(block):
    """``{dtype_name: bytes}`` over the block's registered parameters —
    the admission-facing weight footprint, split so a calibration
    report (or a test) can show the f32→int8 shrink explicitly."""
    from ..parallel.functional import extract_params
    out = {}
    for v in extract_params(block).values():
        k = str(v.dtype)
        out[k] = out.get(k, 0) + int(v.size) * int(v.dtype.itemsize)
    return out


def quantize_for_serving(block, calib_data=None, calib_mode=None,
                         num_calib_batches=None, exclude_layers=None,
                         logger=None):
    """Calibrate → rewrite `block` into its int8 serving form (in
    place).  Returns ``(block, report)``.

    calib_mode: 'naive' | 'entropy' | 'none' (default:
        MXNET_QUANT_CALIB_MODE).  'none' = dynamic ranges — every
        executable recomputes min/max per batch; calibrated modes bake
        fixed ranges into the traced buckets (faster, and the form the
        compile-time-transform contract wants).
    num_calib_batches: batches consumed from `calib_data` (default:
        MXNET_QUANT_CALIB_BATCHES).
    """
    from ..contrib.quantization import (quantize_net, quantized_layers,
                                        is_quantized)
    calib_mode = str(calib_mode or _cfg.get("MXNET_QUANT_CALIB_MODE"))
    if num_calib_batches is None:
        num_calib_batches = int(
            _cfg.get("MXNET_QUANT_CALIB_BATCHES")) or None
    if is_quantized(block):
        # idempotent: quantize_for_serving(...) followed by
        # register_quantized(...) on the same block is the natural
        # call sequence — the second pass must not die on "no
        # quantizable layers found" (the layers were already swapped)
        n_layers = sum(1 for _ in quantized_layers(block))
        after = param_bytes_by_dtype(block)
        return block, {
            "quantized": True, "already_quantized": True,
            "quantized_dtype": "int8",
            "quantized_layers": int(n_layers),
            "calib_mode": calib_mode, "calib_batches": None,
            "calib_wall_s": 0.0,
            "weight_bytes_after": {k: int(v)
                                   for k, v in after.items()},
            "weight_bytes_total_after": int(sum(after.values())),
        }
    before = param_bytes_by_dtype(block)
    t0 = time.perf_counter()
    quantize_net(block,
                 calib_data=calib_data if calib_mode != "none" else None,
                 calib_mode=calib_mode,
                 num_calib_batches=num_calib_batches,
                 exclude_layers=exclude_layers, logger=logger)
    wall = time.perf_counter() - t0
    after = param_bytes_by_dtype(block)
    n_layers = sum(1 for _ in quantized_layers(block))
    report = {
        "quantized": True,
        "quantized_dtype": "int8",
        "quantized_layers": int(n_layers),
        "calib_mode": calib_mode,
        "calib_batches": (int(num_calib_batches)
                          if num_calib_batches else None),
        "calib_wall_s": round(wall, 3),
        "weight_bytes_before": {k: int(v) for k, v in before.items()},
        "weight_bytes_after": {k: int(v) for k, v in after.items()},
        "weight_bytes_total_before": int(sum(before.values())),
        "weight_bytes_total_after": int(sum(after.values())),
    }
    events.incr("quant.models")
    events.incr("quant.layers", n_layers)
    events.observe_time("quant.calib_us", wall)
    _bb.record("quant", "calibrated", layers=int(n_layers),
               mode=calib_mode,
               weight_bytes_before=report["weight_bytes_total_before"],
               weight_bytes_after=report["weight_bytes_total_after"])
    return block, report

"""Serving a model_zoo ResNet through the InferenceEngine (ISSUE 3).

Runs on CPU.  Shows the full lifecycle: build → warmup (AOT
pre-compile every bucket) → concurrent mixed-size traffic → deadline
handling → counters/percentiles → drain/close.

    JAX_PLATFORMS=cpu python examples/serving.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon
from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
from incubator_mxnet_tpu.io.device_feed import normalize_transform
from incubator_mxnet_tpu.monitor import events
from incubator_mxnet_tpu.serving import DeadlineExceeded


def main():
    ctx = mx.tpu(0) if mx.num_tpus() else mx.cpu()
    net = resnet18_v1(classes=10, thumbnail=True)
    net.initialize(ctx=ctx)
    net.hybridize(static_alloc=True, static_shape=True)
    # uint8 stays the wire format; normalize+cast is traced INTO every
    # bucket executable — identical numerics to the training feed path
    net.set_input_transform(normalize_transform(127.5, 64.0, "float32"))

    eng = net.inference_engine(ctx=ctx, max_batch=16,
                               handle_sigterm=True)
    print("warming every (device, bucket) executable ...")
    info = eng.warmup(example_shape=(3, 32, 32), wire_dtype="uint8")
    print("  buckets=%s wall=%.2fs" % (info["buckets"], info["wall_s"]))

    # -- mixed-size traffic: every request lands on a warmed bucket --
    rs = np.random.RandomState(0)
    imgs = rs.randint(0, 256, (128, 3, 32, 32)).astype(np.uint8)
    traces0 = events.get("serve.traces")
    futs, i = [], 0
    t0 = time.perf_counter()
    while i < len(imgs):
        k = int(rs.choice((1, 2, 3, 5, 8)))
        k = min(k, len(imgs) - i)
        futs.append(eng.submit(imgs[i]) if k == 1
                    else eng.submit_batch(imgs[i:i + k]))
        i += k
    for f in futs:
        f.result(timeout=120)
    wall = time.perf_counter() - t0
    print("served %d images in %.2fs (%.1f img/s), %d requests, "
          "0 recompiles: %s"
          % (len(imgs), wall, len(imgs) / wall, len(futs),
             events.get("serve.traces") == traces0))

    # -- deadlines: an expiring request resolves with DeadlineExceeded
    f = eng.submit(imgs[0], deadline=1e-9)
    try:
        f.result(timeout=10)
        print("deadline: served (dispatcher beat the clock)")
    except DeadlineExceeded as e:
        print("deadline: rejected as expected —", e)

    # -- observability: counters + tail latency ----------------------
    snap = eng.stats()
    c = snap["counters"]
    fill = c.get("serve.batch_fill", 0)
    waste = c.get("serve.pad_waste", 0)
    print("batches=%d fill=%.0f%% p50/p99 e2e = %.1f/%.1f ms"
          % (c.get("serve.batches", 0),
             100.0 * fill / max(1, fill + waste),
             events.percentiles("serve.e2e_us").get("p50", 0) / 1e3,
             events.percentiles("serve.e2e_us", (99,)).get("p99", 0)
             / 1e3))

    # -- lifecycle: drain accepted work, join the dispatcher ---------
    eng.drain()
    print("closed cleanly:", eng.close())


if __name__ == "__main__":
    main()

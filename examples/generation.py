"""Generation serving tour (ISSUE 14): KV-cached decode with
continuous batching.

Run:  JAX_PLATFORMS=cpu python examples/generation.py

Walks the whole lifecycle on a small Seq2Seq NMT model: warmup (the
closed executable set — prefill per prompt bucket, one donated
decode step, one join), streaming per-token results, priority lanes
with deadlines, the KV-admission math through the ModelRegistry, and
the zero-recompile proof under mixed prompt lengths.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.models import Seq2Seq
from incubator_mxnet_tpu.monitor import events
from incubator_mxnet_tpu.serving import (GenerationEngine,
                                         ModelRegistry,
                                         AdmissionDenied)

VOCAB, BOS, EOS = 64, 1, 2


def build_model(ctx):
    mx.random.seed(0)
    net = Seq2Seq(VOCAB, VOCAB, embed_dim=32, hidden=48, num_layers=2)
    net.initialize(ctx=ctx)
    # one tiny forward gives the deferred LSTM params concrete shapes
    net(nd.array(np.ones((1, 4), np.int32), ctx=ctx),
        nd.array(np.ones((1, 1), np.int32), ctx=ctx))
    return net


def main():
    ctx = mx.tpu(0) if mx.num_tpus() else mx.cpu()
    net = build_model(ctx)

    # ---- engine lifecycle -------------------------------------------
    eng = GenerationEngine(net, bos=BOS, eos=EOS, ctx=ctx, slots=4,
                           max_len=32, prompt_buckets=(8, 16))
    warm = eng.warmup()
    print("warmup:", warm["wall_s"], "s —",
          len(warm["prompt_buckets"]), "prompt buckets,",
          warm["kv_cache"]["total"], "KV bytes for",
          warm["slots"], "slots")

    # ---- streaming: tokens as they decode ---------------------------
    rs = np.random.RandomState(7)
    stream = eng.submit(rs.randint(3, VOCAB, (6,)),
                        max_new_tokens=12, lane="high", deadline=10.0)
    print("streamed:", [t for t in stream])

    # ---- continuous batching under mixed lengths --------------------
    t0 = events.get("serve.traces")
    streams = [eng.submit(rs.randint(3, VOCAB, (int(n),)),
                          max_new_tokens=int(m))
               for n, m in zip((3, 9, 5, 14, 7, 11, 4, 16),
                               (6, 12, 4, 20, 9, 3, 15, 8))]
    done = [len(s.result(timeout=120)) for s in streams]
    print("served %d requests (token counts %s), recompiles after "
          "warmup: %d" % (len(done), done,
                          events.get("serve.traces") - t0))
    print("TTFT p50/p99 us:",
          events.percentiles("gen.ttft_us", (50, 99)))
    eng.close()

    # ---- KV-aware admission through the registry --------------------
    reg = ModelRegistry(devices=[ctx], hbm_budget=1 << 20)
    try:
        reg.register_generator("chat_big", net, BOS, EOS,
                               slots=4096, max_len=32)
    except AdmissionDenied as e:
        print("refused (KV term named):", str(e)[:160], "...")
    rec = reg.register_generator("chat", net, BOS, EOS,
                                 slots=4, max_len=32,
                                 prompt_buckets=(8, 16))
    print("admitted:", rec["footprint_bytes"], "bytes, of which KV",
          rec["detail"]["kv_bytes"])
    reg.warmup("chat")
    out = reg.generate("chat", rs.randint(3, VOCAB, (5,)),
                       max_new_tokens=8).result(timeout=120)
    print("via registry:", list(out))
    reg.close()


if __name__ == "__main__":
    main()

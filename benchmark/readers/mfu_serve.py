"""The serving step's share of the chip's peak: FLOPs the algorithm needs
(the builder's counters: prefill of each source, each output token's
decoder layers, attention over live positions, output projection) over
the window / peak.  Online: all work of the requests due in the window;
backlog: the tokens emitted inside the window and the prefills of the
requests that started in it."""
import harness


def read(spec, record, result):
    if record.get("kind") != "serve":
        return None
    b, cfg, plan = record["builder"], record["config"], record["plan"]
    peaks = harness.peaks_for(result["device"]["kind"])
    total = 0
    if record["backlog"]:
        t0, t1 = record["tokens_open"], record["tokens_close"]
        for i, n1 in t1.items():
            n0 = t0.get(i, 0)
            if n1 > n0:
                total += b.request_flops(cfg, int(plan["src_len"][i]), n1, n0)
    else:
        for i in record["measured"]:
            n = record["tokens_end"].get(i, 0)
            if n:
                total += b.request_flops(cfg, int(plan["src_len"][i]), n, 0)
    if total <= 0:
        return None
    return 100.0 * total / record["window_s"] / peaks["bf16_flops_per_s"]

"""The decode executable's share of its roofline: the least time the chip
could take for one step's needed work (weights once, K/V rows of the live
slots up to their positions, their memory rows; FLOPs of the same tokens)
over the executable's measured device time.  Counted from the work, not
from what the padded cache reads, so it reads the same whatever implements
decode.  Which of the two bounds holds is printed to stderr."""
import statistics
import sys

import harness
import trace_reduce


def read(spec, record, result):
    tr, roles = record.get("trace"), record.get("roles")
    if not tr or not roles or record.get("kind") != "serve":
        return None
    runs = [d for _, d in trace_reduce.module_runs(tr, roles["decode"])]
    if not runs:
        return None
    b, cfg, plan = record["builder"], record["config"], record["plan"]
    peaks = harness.peaks_for(result["device"]["kind"])
    # the live slots of a typical step: every stream that was running when
    # the window closed, at the position it had reached
    t1 = record["tokens_close"] or record["tokens_end"]
    done = record["done"]
    live = [(int(plan["src_len"][i]), n) for i, n in t1.items()
            if n > 0 and done[i] != done[i]]
    if not live:
        return None
    nbytes = b.decode_weight_bytes(cfg) + sum(
        b.decode_state_bytes(cfg, s, n) for s, n in live)
    flops = sum(b.decode_flops(cfg, s, n) for s, n in live)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["bf16_flops_per_s"]
    print("decode roofline: %d live slots, %.3g bytes (%.3g s), %.3g flops "
          "(%.3g s): bound by %s" % (len(live), nbytes, t_mem, flops, t_flop,
                                     "memory" if t_mem >= t_flop else "compute"),
          file=sys.stderr)
    return 100.0 * max(t_mem, t_flop) / statistics.median(runs)

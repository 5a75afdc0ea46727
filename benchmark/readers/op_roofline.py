"""A kernel's share of its roofline, found by the kernel's NAME among the
device ops of the executables whose name contains `module`: the least time
the chip could take for the work those runs needed (the builder's `bytes`
and `flops` functions; the larger of the two bounds holds) over the device
time of the ops whose name contains `op`.

`work` says what the builder's functions are asked about:

- `live_slots`: one call a run of the executable, with the number of streams
  that were live when the window closed (a backlog keeps every slot taken).
- `prompts`: one call a prompt prefilled in the traced span, with its length:
  the program's `gen.prefill` rows carry it in `n`, and the rows taken are
  the latest that start before the trace's end, as many as the executables
  ran (as `prefill_roofline` takes them).

Nothing to read (None, the metric is left out) where no op bears the name,
as on a commit whose program has no such kernel, where the builder lacks the
functions, or where the span holds no run."""
import harness
import trace_reduce


def _ops(trace, module, op):
    """(runs of the modules named, seconds of the ops named inside them) on
    the first device."""
    dev = trace["devices"][sorted(trace["devices"])[0]]
    runs = sum(len(r) for m, r in dev["modules"].items() if module in m)
    seconds = sum(d for name, evs in dev["ops"].items() if op in name
                  for _, d, owner in evs if owner and module in owner)
    return runs, seconds


def _live_slots(record):
    last = record["tokens_close"] or record["tokens_end"]
    done = record["done"]
    return sum(1 for i, n in last.items() if n > 0 and done[i] != done[i])


def _prompts(record, runs):
    tr = record["trace"]
    t_open, t_close = record["t_open"], record["t_close"]
    end = t_open + 0.3 * (t_close - t_open) + tr["window_s"]
    named = harness.load_module("readers", "phase_rows").named
    rows = [r[5] for r in named("gen.prefill", t_open, end) if r[5] > 0]
    return rows[-runs:] if len(rows) >= runs else None


def read(spec, record, result):
    tr, b = record.get("trace"), record.get("builder")
    if not tr or record.get("kind") != "serve" or \
            not hasattr(b, spec["bytes"]):
        return None
    runs, seconds = _ops(tr, spec["module"], spec["op"])
    if not runs or seconds <= 0:
        return None
    if spec["work"] == "live_slots":
        calls = [_live_slots(record)] * runs
    else:
        calls = _prompts(record, runs)
    if not calls or not calls[0]:
        return None
    cfg = record["config"]
    peaks = harness.peaks_for(result["device"]["kind"])
    flops = getattr(b, spec.get("flops", ""), None)
    need = sum(max(getattr(b, spec["bytes"])(cfg, n) / peaks["hbm_bytes_per_s"],
                   flops(cfg, n) / peaks["bf16_flops_per_s"] if flops else 0.0)
               for n in calls)
    return 100.0 * need / seconds

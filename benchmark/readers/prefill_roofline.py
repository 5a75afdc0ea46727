"""The prefill executables' share of their roofline: the least time the chip
could take for the prompts prefilled in the traced span (the builder's
`prefill_flops` and `prefill_bytes` of each prompt's length, the larger of
the two bounds a prompt) over the device time of the modules whose name
contains `contains` there.

Which prompts: the program's `gen.prefill` rows carry the prompt's tokens in
`n`; the trace starts three tenths into the window and lasts `window_s` of
the trace, and the device runs a prefill within a tick of its row, so the
rows taken are the latest that start before the trace's end, as many as the
trace has prefill runs.  Nothing to read when the span holds no prefill, or
where the rows carry no lengths."""
import harness
import trace_reduce


def read(spec, record, result):
    tr, b = record.get("trace"), record.get("builder")
    if not tr or record.get("kind") != "serve" or \
            not hasattr(b, "prefill_bytes"):
        return None
    runs = [d for m in trace_reduce.module_counts(tr) if spec["contains"] in m
            for _, d in trace_reduce.module_runs(tr, m)]
    if not runs:
        return None
    t_open, t_close = record["t_open"], record["t_close"]
    end = t_open + 0.3 * (t_close - t_open) + tr["window_s"]
    named = harness.load_module("readers", "phase_rows").named
    rows = [r for r in named("gen.prefill", t_open, end) if r[5] > 0]
    rows = rows[-len(runs):]
    if len(rows) < len(runs):
        return None
    cfg = record["config"]
    peaks = harness.peaks_for(result["device"]["kind"])
    need = sum(max(b.prefill_flops(cfg, r[5]) / peaks["bf16_flops_per_s"],
                   b.prefill_bytes(cfg, r[5]) / peaks["hbm_bytes_per_s"])
               for r in rows)
    return 100.0 * need / sum(runs)

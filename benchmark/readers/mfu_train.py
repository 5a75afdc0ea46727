"""The whole step's share of the chips' peak: FLOPs the algorithm needs for
one step (the builder's counter, from shapes; recomputation not counted)
x steps completed / window / (chips x peak)."""
import harness


def read(spec, record, result):
    if record.get("kind") != "train" or not record.get("steps"):
        return None
    peaks = harness.peaks_for(result["device"]["kind"])
    flops = record["builder"].step_flops(record["config"], record["traffic"])
    rate = flops * record["steps"] / record["window_s"]
    return 100.0 * rate / (record["chips"] * peaks["bf16_flops_per_s"])

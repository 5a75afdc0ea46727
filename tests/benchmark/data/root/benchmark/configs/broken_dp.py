"""The data-parallel step with the exchange between chips left out: chip 0
normalises and differentiates its own shard of the batch and nothing is
summed across chips.  A whole run over it must come out not correct."""
import harness


def build(config, traffic, weights, batch, devices, ctx):
    real = harness.load_module("configs", config["real_builder"])
    n = len(devices)
    own = dict(batch, images=batch["images"][:batch["images"].shape[0] // n],
               labels=batch["labels"][:batch["labels"].shape[0] // n])
    solo = dict(config, training=dict(config["training"], mesh=[1]))
    return real.build(solo, traffic, weights, own, devices[:1], ctx)


def reference_place(weights, batch, devices):
    return harness.load_module("configs", "resnet50_v1b").reference_place(
        weights, batch, devices)

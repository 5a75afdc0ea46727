"""Builders with the timed path broken underneath, one fault each: the
tests drive a whole run over them and must see `correct` come out false.
A configuration names one of them as its `builder`
(`"builder": "broken"`, `"fault": "<name>"`)."""
import numpy as np

import harness


def _real(config):
    return harness.load_module("configs", config["real_builder"])


class _AlteredStream:
    """A stream whose third token is altered where the client reads it."""

    def __init__(self, inner, vocab):
        self._inner, self._vocab = inner, vocab
        self.future = inner.future

    def tokens(self):
        t = self._inner.tokens()
        if len(t) > 2:
            t[2] = 3 + (t[2] + 1 - 3) % (self._vocab - 3)
        return t

    def done(self):
        return self._inner.done()


def build(config, *args):
    fault = config["fault"]
    real = _real(config)
    if fault == "token_altered":
        system = real.build(config, *args)
        submit = system.submit
        system.submit = lambda p, n: _AlteredStream(submit(p, n),
                                                    config["vocab_size"])
        return system
    traffic, weights, batch, devices, ctx = args
    if fault == "half_batch":
        # half of the rows left out, the mean taken over the rest
        B = batch["tokens"].shape[0]
        K = batch["positions"].shape[0] // B
        half = dict(batch, tokens=batch["tokens"][:B // 2],
                    positions=batch["positions"][:B // 2 * K],
                    labels=batch["labels"][:B // 2 * K])
        return real.build(config, traffic, weights, half, devices, ctx)
    system = real.build(config, traffic, weights, batch, devices, ctx)
    if fault == "state_unchanged":
        # a step that returns its state unchanged
        def step():
            from incubator_mxnet_tpu import autograd as ag
            tokens, positions, labels = system.feed
            with ag.record():
                return system.loss(system.net(tokens), positions, labels)
        system.step = step
        system.first_gradients = lambda: {
            k: v * 0 for k, v in system.params().items()}
        return system
    raise ValueError("unknown fault %r" % fault)


def reference_place(weights, batch, devices):
    return harness.load_module("configs", "bert_base").reference_place(
        weights, batch, devices)

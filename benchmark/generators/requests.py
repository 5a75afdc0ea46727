"""The one generator of request traffic.  A mix is a data file under
`traffic/`; this file reads its parameters and knows no mix by name.

Every seed gets the **same multiset** of lengths and gaps in another
order: with n requests due in a block of `block_s` seconds, source
lengths are the n quantiles (i - 1/2)/n of the mix's distribution and the
gaps between arrivals the n quantiles of an exponential, scaled to fill
the block exactly; a window is a row of such blocks.  `--seed` permutes lengths and gaps independently and draws the
token ids.  So every run offers the same work at the same load, and the
run-to-run spread is the system's, not the draw's.

Phases of an open-loop plan, all from the same rule:
`lead` (due before the window opens, unmeasured, so that the window
opens at steady occupancy), `window` (measured: every request due in
it), `tail` (unmeasured arrivals that keep the load on until the last
measured request has finished).  A backlog plan has one phase, all due
before the window.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

PHASES = ("lead", "window", "tail")


def _mid_quantiles(n):
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def source_lengths(spec, n):
    """The n stratified lengths of `spec` (sorted)."""
    q = _mid_quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + (spec["max"] - spec["min"]) * q
    elif spec["dist"] == "fixed":
        v = np.full(n, float(spec["value"]))
    else:
        raise ValueError("unknown length distribution %r" % spec["dist"])
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def new_tokens(spec, src):
    return np.clip(np.rint(spec["ratio"] * src), spec["min"],
                   spec["max"]).astype(np.int64)


def gaps(spec, n, span_s):
    """n gaps that sum to `span_s`: stratified quantiles of the named
    inter-arrival law (sorted)."""
    q = _mid_quantiles(n)
    if spec["dist"] == "exponential":
        g = -np.log1p(-q)
    elif spec["dist"] == "gamma":
        # cv > 1 bursts: a two-point mixture of exponentials with the
        # asked coefficient of variation, by quantiles of each branch
        cv2 = float(spec["cv"]) ** 2
        p = 0.5 * (1.0 - math.sqrt((cv2 - 1.0) / (cv2 + 1.0)))
        n_long = max(int(round(p * n)), 1)
        g = np.sort(np.concatenate([
            -np.log1p(-_mid_quantiles(n - n_long)) / (2.0 * (1.0 - p)),
            -np.log1p(-_mid_quantiles(n_long)) / (2.0 * p)]))
    elif spec["dist"] == "constant":
        g = np.ones(n)
    else:
        raise ValueError("unknown gap distribution %r" % spec["dist"])
    return g * (span_s / g.sum())


def _block(traffic, rng, n, t0, span_s):
    """n requests that fill [t0, t0 + span_s): the stratified lengths and
    gaps, each permuted on its own."""
    src = source_lengths(traffic["source_len"], n)[rng.permutation(n)]
    gp = gaps(traffic["gaps"], n, span_s)[rng.permutation(n)]
    # each request is due at the middle of its own gap, so the arrivals
    # fill the span and none falls on its border
    return t0 + np.cumsum(gp) - 0.5 * gp, src


def _phase(traffic, rng, rate, t0, span_s, vocab, first_id):
    """A phase is a row of blocks of `block_s` seconds.  Every block holds
    the same multiset, permuted within the block: the load is the same in
    every few seconds of every run, and the order inside them is the
    seed's.  (Without blocks, a run's thirds differed by a tenth in their
    median latency, by where the long gaps and sources happened to fall.)"""
    block = float(traffic.get("block_s", span_s))
    dues, srcs, t = [], [], 0.0
    while t < span_s - 1e-9:
        span = min(block, span_s - t)
        n = int(round(rate * span))
        if n >= 1:
            due, src = _block(traffic, rng, n, t0 + t, span)
            dues.append(due)
            srcs.append(src)
        t += span
    due, src = np.concatenate(dues), np.concatenate(srcs)
    new = new_tokens(traffic["new_tokens"], src)
    prompts = [rng.integers(first_id, vocab, size=int(s), dtype=np.int64)
               .astype(np.int32) for s in src]
    return due, src, new, prompts


def generate(traffic, config, seed, seconds):
    """The plan of one run: parallel arrays over requests, in due order.

    `due` is in seconds from the opening of the window (negative in the
    lead-in; -inf in a backlog), `phase` indexes PHASES."""
    rng = np.random.default_rng([int(seed), 0x7EA])
    vocab = int(config["vocab_size"])
    first_id = int(traffic.get("first_token_id", 3))
    parts = []
    if traffic["arrivals"] == "backlog":
        src_spec = traffic["source_len"]
        probe = new_tokens(traffic["new_tokens"], source_lengths(src_spec, 4096))
        b = traffic["backlog"]
        n = int(math.ceil(b["headroom"] * b["expected_tokens_per_s"] * seconds
                          / float(probe.mean()))) + int(config["serving"]["slots"])
        due, src, new, prompts = _phase(traffic, rng, float(n), 0.0, 1.0, vocab,
                                        first_id)
        parts.append((np.full(n, -np.inf), src, new, prompts,
                      np.full(n, PHASES.index("window"))))
    elif traffic["arrivals"] == "open_loop":
        rate = float(traffic["rate_per_s"])
        spans = (("lead", -float(traffic["lead_in_s"]), float(traffic["lead_in_s"])),
                 ("window", 0.0, float(seconds)),
                 ("tail", float(seconds), float(traffic["tail_s"])))
        for name, t0, span in spans:
            if rate * span < 1:
                continue
            due, src, new, prompts = _phase(traffic, rng, rate, t0, span, vocab,
                                            first_id)
            parts.append((due, src, new, prompts,
                          np.full(len(due), PHASES.index(name))))
    else:
        raise ValueError("unknown arrivals %r" % traffic["arrivals"])
    return {"due": np.concatenate([p[0] for p in parts]),
            "src_len": np.concatenate([p[1] for p in parts]),
            "max_new": np.concatenate([p[2] for p in parts]),
            "prompts": [x for p in parts for x in p[3]],
            "phase": np.concatenate([p[4] for p in parts])}

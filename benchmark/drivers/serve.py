"""The run of a serving cell: an engine under a plan of requests.

One client thread sends each request when it is due and watches for
first tokens; the engine's own thread does the rest.  A request is timed
from its **due** time on the host's clock, and every request due in the
window is in the tail it reports; one that fails counts as +inf.

Open loop: the lead-in runs before the window, inside set-up, so the
window opens at steady occupancy; arrivals go on after it closes until
the last measured request has finished.  Backlog: everything is
submitted before the window, which opens once every slot has been
filled, on a step boundary, and closes on the first step boundary after
`--seconds`; the rate is taken over that whole span.
"""
from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

import harness
import roles as roles_mod
import trace_reduce
import weights as weights_mod

POLL_S = 0.0005
LATE_S = 60.0          # how long past the close a measured answer may take


class Client(threading.Thread):
    """Sends the plan and stamps first tokens and completions."""

    def __init__(self, system, plan, t_open):
        super().__init__(name="bench-client", daemon=True)
        self.system, self.plan, self.t_open = system, plan, t_open
        n = len(plan["due"])
        self.order = np.argsort(plan["due"], kind="stable")
        self.streams = [None] * n
        self.sent = np.full(n, np.nan)
        self.first = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.error = [None] * n
        self.stop_sending = threading.Event()
        self.quit = threading.Event()
        self._pending = []          # sent, no first token yet (send order)
        self.started = []           # first token seen (request indices)
        self.sent_all = threading.Event()

    def _on_done(self, i):
        def cb(fut):
            self.done[i] = time.monotonic()
            exc = fut.exception()
            if exc is not None:
                self.error[i] = repr(exc)[:200]
        return cb

    def _send(self, i):
        try:
            s = self.system.submit(self.plan["prompts"][i],
                                   int(self.plan["max_new"][i]))
        except Exception as e:      # noqa: BLE001 — a refusal is a result
            self.sent[i] = self.done[i] = time.monotonic()
            self.error[i] = repr(e)[:200]
            return
        self.sent[i] = time.monotonic()
        self.streams[i] = s
        s.future.add_done_callback(self._on_done(i))
        self._pending.append(i)

    def _poll(self):
        # admission is in arrival order: look at the head of the line only
        keep = []
        for k, i in enumerate(self._pending):
            if k >= 64:
                keep.extend(self._pending[k:])
                break
            s = self.streams[i]
            if s.tokens():
                self.first[i] = time.monotonic()
                self.started.append(i)
            elif s.done():
                pass                # failed before a token: stays nan
            else:
                keep.append(i)
        self._pending = keep

    def run(self):
        due = self.plan["due"]
        k, n = 0, len(self.order)
        while not self.quit.is_set():
            now = time.monotonic()
            while k < n and not self.stop_sending.is_set() and \
                    self.t_open + due[self.order[k]] <= now:
                self._send(int(self.order[k]))
                k += 1
            if k >= n or self.stop_sending.is_set():
                self.sent_all.set()
            self._poll()
            nxt = POLL_S
            if k < n and not self.stop_sending.is_set():
                nxt = min(nxt, max(0.0, self.t_open + due[self.order[k]]
                                   - time.monotonic()))
            time.sleep(nxt)

    # -- token counts, from the streams the client holds ------------------
    def tokens_now(self):
        """{request: tokens emitted so far} over every stream sent."""
        return {i: len(s.tokens()) for i, s in enumerate(self.streams)
                if s is not None}

    def await_step(self, timeout=30.0):
        """Return just after the engine's next step: the moment the token
        count of a live stream moves."""
        watch = [self.streams[i] for i in self.started[-8:]
                 if not self.streams[i].done()]
        if not watch:
            return time.monotonic()
        base = [len(s.tokens()) for s in watch]
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if any(len(s.tokens()) != b or s.done()
                   for s, b in zip(watch, base)):
                break
            time.sleep(0.0002)
        return time.monotonic()


def _counters(names):
    from incubator_mxnet_tpu.monitor import events
    return {n: events.get(n) for n in names}


COUNTERS = ("serve.traces", "gen.steps", "gen.tokens", "gen.prefills",
            "gen.joins", "gen.retires", "gen.failed", "gen.donation_copy",
            "gen.step_us", "gen.prefill_us")


def _probe_roles(system, config, logdir):
    """Learn the module names of prefill, join and decode (see roles.py)."""
    buckets = list(config["serving"]["prompt_buckets"])[:3]
    k = len(buckets) + 4
    trace_reduce.start(logdir)
    try:
        streams = [system.submit(np.full(b, 5, np.int32), k) for b in buckets]
        for s in streams:
            s.result(timeout=120)
    finally:
        tr = trace_reduce.stop(logdir)
    counts = trace_reduce.module_counts(tr)
    # an early EOS would end a probe stream short of k steps: then no
    # module ran k times and by_counts raises
    found = roles_mod.by_counts(counts, {"prefill": 1, "join": len(buckets),
                                         "decode": k},
                                config.get("executable_prefix", ""))
    if len(found["join"]) != 1 or len(found["decode"]) != 1:
        raise harness.BenchError("probe cannot tell the modules apart: %s"
                                 % counts)
    return {"prefill": found["prefill"], "join": found["join"][0],
            "decode": found["decode"][0]}


def check_outputs(ctx, client, measured, done, error, control=None):
    """Compare a sample of finished requests with the plain reference."""
    import jax
    import jax.numpy as jnp

    cfg, traffic, plan = ctx.config, ctx.traffic, ctx.plan
    chk = dict(cfg.get("check", {}), **traffic.get("check", {}))
    limits = dict(cfg.get("limits", {}), **traffic.get("limits", {}))
    ref = harness.load_module("reference", cfg["reference"], ctx.base)
    eos = cfg["eos_token_id"]

    toks = {i: np.asarray(client.streams[i].tokens(), np.int32)
            for i in measured if client.streams[i] is not None}
    ok = [i for i in measured if error[i] is None
          and not np.isnan(done[i]) and len(toks.get(i, ())) > 0]
    wrong_len = 0
    for i in ok:
        n, cap = len(toks[i]), int(plan["max_new"][i])
        if n > cap or (n < cap and toks[i][-1] != eos):
            wrong_len += 1
    compared = {"length_faults": {"value": wrong_len, "limit": 0}}
    correct = wrong_len == 0 and len(ok) == len(measured)
    if not ok:
        return False, compared

    rng = np.random.default_rng([int(ctx.seed), 0xC4EC])
    k = min(int(chk.get("requests", 8)), len(ok))
    longest = max(ok, key=lambda i: (len(toks[i]), -i))
    rest = [i for i in ok if i != longest]
    picks = [longest] + [rest[j] for j in rng.permutation(len(rest))[:k - 1]]
    Ts = int(max(cfg["serving"]["prompt_buckets"]))
    Tt = int(cfg["serving"]["max_len"])
    src = np.zeros((len(picks), Ts), np.int32)
    src_len = np.zeros(len(picks), np.int32)
    tgt_in = np.full((len(picks), Tt), eos, np.int32)
    served = np.zeros((len(picks), Tt), np.int32)
    n_served = np.zeros(len(picks), np.int32)
    for r, i in enumerate(picks):
        p, t = plan["prompts"][i], toks[i]
        src[r, :len(p)] = p
        src_len[r] = len(p)
        tgt_in[r, 0] = cfg["bos_token_id"]
        tgt_in[r, 1:len(t)] = t[:-1]
        served[r, :len(t)] = t
        n_served[r] = len(t)

    dev = ctx.devices[0]
    w = weights_mod.widen(weights_mod.make(ref.spec(cfg), ctx.seed,
                                           jnp.dtype(cfg["dtype"]), dev))
    args = [jax.device_put(a, dev) for a in (src, src_len, tgt_in, served,
                                             n_served)]

    def read(quant):
        fn = jax.jit(lambda w_, *a: ref.served_gaps(w_, cfg, *a, quant=quant))
        # in blocks of rows, so that the float32 logits fit beside the
        # weights; the last block is padded with copies of its first row
        out, blk = [], int(chk.get("rows_per_call", 8))
        for lo in range(0, len(picks), blk):
            part = [a[lo:lo + blk] for a in args]
            short = blk - part[0].shape[0]
            if short:
                part = [jnp.concatenate([a, jnp.repeat(a[:1], short, 0)])
                        for a in part]
            out.append(np.asarray(fn(w, *part))[:blk - short])
        g = np.concatenate(out)
        total = float(n_served.sum())
        return float(g.max()), float(g.sum() / total)

    gmax, gmean = read(None)
    compared["gap_max"] = {"value": gmax, "limit": limits["gap_max"]}
    compared["gap_mean"] = {"value": gmean, "limit": limits["gap_mean"]}
    compared["tokens_compared"] = {"value": int(n_served.sum()), "limit": None}
    correct = correct and gmax <= limits["gap_max"] and gmean <= limits["gap_mean"]
    if control:
        cmax, cmean = read(control)
        compared["control.gap_max"] = {"value": cmax, "limit": limits["gap_max"]}
        compared["control.gap_mean"] = {"value": cmean,
                                        "limit": limits["gap_mean"]}
    return correct, compared


def run(ctx):
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx

    cfg, traffic = ctx.config, ctx.traffic
    builder = harness.load_module("configs", cfg["builder"], ctx.base)
    ref = harness.load_module("reference", cfg["reference"], ctx.base)
    gen = harness.load_module("generators", traffic["generator"], ctx.base)
    dev = ctx.devices[0]
    mxctx = mx.tpu(0) if dev.platform == "tpu" else mx.cpu(0)

    w = weights_mod.make(ref.spec(cfg), ctx.seed, jnp.dtype(cfg["dtype"]), dev)
    system = builder.build(cfg, w, mxctx)
    del w
    system.warmup()
    ctx.note("warm-up done")
    role = None
    if ctx.trace:
        role = _probe_roles(system, cfg, ctx.scratch("probe"))
        ctx.note("roles %s" % role)
    plan = gen.generate(traffic, cfg, ctx.seed, ctx.seconds)
    ctx.plan = plan
    backlog = traffic["arrivals"] == "backlog"
    n = len(plan["due"])
    window_phase = gen.PHASES.index("window")
    measured = [i for i in range(n) if plan["phase"][i] == window_phase]

    lead = 0.0 if backlog else float(traffic["lead_in_s"])
    client = Client(system, plan, time.monotonic() + lead + 0.05)
    gc.collect()
    gc.freeze()
    client.start()
    slots = int(cfg["serving"]["slots"])
    if backlog:
        client.sent_all.wait()
        while len(client.started) < slots:      # every slot filled once
            time.sleep(0.01)
        client.t_open = client.await_step()
    else:
        time.sleep(max(0.0, client.t_open - time.monotonic()))
    t_open = client.t_open
    tok_open = client.tokens_now() if backlog else {}
    c_open = _counters(COUNTERS)
    setup_s = t_open - ctx.t_start
    ctx.note("window open, setup_s %.2f" % setup_s)

    trace = None
    if ctx.trace:
        tl = min(float(traffic.get("trace_s", 3.0)), ctx.seconds * 0.5)
        time.sleep(max(0.0, t_open + 0.3 * ctx.seconds - time.monotonic()))
        logdir = ctx.scratch("trace")
        trace_reduce.start(logdir)
        ta = time.monotonic()
        time.sleep(tl)
        trace = trace_reduce.stop(logdir, time.monotonic() - ta)
    time.sleep(max(0.0, t_open + ctx.seconds - time.monotonic()))
    if backlog:
        t_close = client.await_step()
        tok_close = client.tokens_now()
    else:
        t_close = t_open + ctx.seconds
        tok_close = {}
    c_close = _counters(COUNTERS)
    window_s = t_close - t_open

    # wait for every measured answer: late is late, not wrong
    if backlog:
        client.stop_sending.set()
        done_by = t_close
    else:
        end = t_close + LATE_S
        while time.monotonic() < end and any(
                np.isnan(client.done[i]) for i in measured):
            time.sleep(0.05)
        client.stop_sending.set()
        done_by = time.monotonic()
    device = harness.device_facts(ctx.devices, ctx.rehearse)
    client.quit.set()
    client.join(timeout=10)
    # what the client saw, fixed before the engine is shut (shutting it
    # fails whatever unmeasured request is still queued or running)
    tokens_end = client.tokens_now()
    done, error = client.done.copy(), list(client.error)
    info = dict(system.info)
    system.close()
    gc.unfreeze()

    # ---- end-to-end metrics: from the client's own clock ---------------
    if backlog:
        tokens_window = sum(tok_close.values()) - sum(tok_open.values())
        e2e = {"serve_tokens_per_s": tokens_window / window_s}
        sample = [i for i in measured if not np.isnan(done[i])
                  and error[i] is None and done[i] <= done_by]
        attempted = len(client.started)
        failed = sum(1 for i in client.started if error[i] is not None)
    else:
        ttft, tpot = [], []
        failed = 0
        for i in measured:
            due_abs = t_open + plan["due"][i]
            ntok = tokens_end.get(i, 0)
            bad = error[i] is not None or np.isnan(done[i])
            if bad:
                failed += 1
            if np.isnan(client.first[i]):
                ttft.append(math.inf)
            else:
                ttft.append((client.first[i] - due_abs) * 1e3)
            if bad:
                tpot.append(math.inf)
            elif ntok >= 2:
                tpot.append((done[i] - client.first[i]) / (ntok - 1) * 1e3)
        e2e = {"ttft_p50_ms": harness.quantile(ttft, 0.5),
               "tpot_p95_ms": harness.quantile(tpot, 0.95)}
        sample = measured
        attempted = len(measured)
        tokens_window = None
        ctx.record["ttft_ms"] = ttft
        ctx.record["tpot_ms"] = tpot
        third = max(len(ttft) // 3, 1)      # does the queue grow?
        ctx.note("ttft p50 by thirds of the window: %s; p95 %.1f; n %d; "
                 "lifetime p95 %.2f s" % (
                     [round(harness.quantile(ttft[k:k + third], 0.5), 1)
                      for k in (0, third, 2 * third)],
                     harness.quantile(ttft, 0.95), len(ttft),
                     harness.quantile([done[i] - (t_open + plan["due"][i])
                                       for i in measured], 0.95)))
    e2e["setup_s"] = setup_s

    ctx.record.update({
        "kind": "serve", "backlog": backlog, "window_s": window_s,
        "counters_open": c_open, "counters_close": c_close,
        "client": client, "plan": plan, "measured": measured,
        "t_open": t_open, "t_close": t_close, "trace": trace, "roles": role,
        "system_info": info, "tokens_window": tokens_window,
        "tokens_open": tok_open, "tokens_close": tok_close,
        "tokens_end": tokens_end, "done": done, "error": error,
        "builder": builder})
    correct, compared = check_outputs(ctx, client, sample, done, error,
                                      ctx.control)
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "end_to_end": e2e, "device": device, "compared": compared}

"""History-trained autotuner (ISSUE 18 tentpole part 1).

The durable history (telemetry/history.py) persists two things this
module can train on, across runs:

- ``kind="cost"`` rows — per-executable flops / bytes_accessed /
  compile_wall_s / memory-analysis bytes, one row per executable per
  exporter tick that saw invocations (the measured substrate ROADMAP
  item 2 names), and
- ``kind="autotune"`` ``probe`` rows — explicit (knob, label, value,
  measured-score) points written by whoever ran a candidate config
  (bench sweeps, tests, a trainer probing caps), via
  :func:`note_probe`.

Every ``suggest_*`` resolves a knob through the same ladder of
evidence, strongest first:

1. **measured** — probe rows for (knob, label) cover >= 2 distinct
   candidate values: pick the argmin of the per-value mean score.
2. **modeled**  — no probes, but cross-run cost rows exist for the
   label family: score candidates analytically against the measured
   flops/bytes (e.g. the bucket cap from measured per-step traffic
   rather than param bytes).
3. **heuristic** — history is cold: fall back to the pre-ISSUE-18
   one-shot heuristic (`costs.suggest_bucket_mb` for the bucket cap),
   which now warns once per label that it was the DECIDING input.

Every decision emits a typed, durable ``autotune/decision`` record:
a flight-recorder ring event, a history row (so the NEXT run can see
what this one chose and why), and an entry in the process-local
decision log that `dump_blackbox` embeds as the ``autotune`` block —
naming the chosen value, the source tier, the heuristic's answer for
the tuned-vs-heuristic delta, and the measured rows that justified
the choice.  ``MXNET_AUTOTUNE=0`` reduces every ``suggest_*`` to its
fallback with no records written.
"""
from __future__ import annotations

import threading
import time

from .. import config as _cfg
from ..telemetry import costs as _costs
from ..telemetry import flightrec as _bb
from ..telemetry import history as _hist

__all__ = ["enabled", "note_probe", "measured_candidates", "suggest",
           "suggest_bucket_cap", "suggest_batch_size",
           "suggest_serve_buckets", "suggest_donate", "suggest_remat",
           "decisions", "block", "reset", "BUCKET_CAP_LADDER",
           "SEARCH_SPACE", "invalidate", "invalidated",
           "prior_decision", "drift_evidence", "DRIFT_FACTOR"]

#: contradiction factor for the cost-drift alert (ISSUE 19 satellite):
#: a new run's measured evidence more than this factor away from what
#: a prior decision recorded (in either direction) means the decision
#: no longer rests on reality
DRIFT_FACTOR = 2.0

#: candidate ZeRO bucket caps in MB (the MXNET_ZERO_BUCKET_MB clamp
#: range [1, 16], log-spaced — the granularity the probe sweeps walk)
BUCKET_CAP_LADDER = (1.0, 2.0, 4.0, 8.0, 16.0)

#: the knobs the tuner searches, for docs/tools — knob name ->
#: (what it shapes, default candidate source)
SEARCH_SPACE = {
    "zero_bucket_mb": "ZeRO-2/3 gradient-bucket cap (parallel/"
                      "zero.py BucketPlan); ladder %s MB"
                      % (BUCKET_CAP_LADDER,),
    "batch_size": "per-replica train/bench batch; ladder from caller",
    "serve_buckets": "serving/gen padding-bucket ladder "
                     "(MXNET_SERVE_BUCKETS)",
    "donate": "donate_argnums on the step/infer executables",
    "remat": "rematerialization of the layer stack (recompute vs "
             "hold activations)",
}

_LOCK = threading.Lock()
_DECISIONS = []                 # process-local decision log (blackbox)
_INVALIDATED = set()            # (knob, label) flagged by a fired
                                # cost-drift rule: the next suggest for
                                # the key re-resolves from THIS run's
                                # evidence only


def enabled() -> bool:
    return bool(_cfg.get("MXNET_AUTOTUNE"))


def _current_run():
    """This process's history run id (None when history is off)."""
    if not _hist.enabled():
        return None
    try:
        return _hist.get_writer().run
    except Exception:           # noqa: BLE001
        return None


def invalidate(knob, label):
    """Flag (knob, label): its prior evidence contradicted a new run's
    measurements (the cost-drift rule fired) — the next ``suggest``
    for the key must re-resolve from current-run evidence and record
    the flip as a ``*-refresh`` decision."""
    with _LOCK:
        _INVALIDATED.add((str(knob), str(label or "")))


def invalidated(knob=None, label=None):
    """With arguments: is (knob, label) flagged?  Without: the sorted
    list of flagged (knob, label) pairs."""
    with _LOCK:
        if knob is None:
            return sorted(_INVALIDATED)
        return (str(knob), str(label or "")) in _INVALIDATED


def _clear_invalidated(knob, label):
    with _LOCK:
        _INVALIDATED.discard((str(knob), str(label or "")))


# -- probes (the measured tier's input) --------------------------------
def note_probe(knob, label, value, score_us, **fields):
    """Record ONE measured candidate: running ``label`` with ``knob``
    set to ``value`` scored ``score_us`` (lower is better; step wall,
    p99, whatever the caller optimizes — just be consistent per knob).
    Durable: a probe written by this run is evidence for every later
    run's tuner.  No-op when history is disabled."""
    return _hist.record("autotune", "probe", float(score_us),
                        labels={"knob": str(knob), "label": str(label),
                                "value": str(value)}, **fields)


def measured_candidates(knob, label, run=None):
    """Probe evidence for (knob, label) across every run in the
    history dir (``run=`` restricts to one run — the drift-refresh
    path trusts only current-run rows):
    ``{value_str: {"mean_us", "n", "runs"}}``."""
    rows = _hist.query(name="probe", kind="autotune",
                       labels={"knob": str(knob), "label": str(label)},
                       run=run)
    out = {}
    for r in rows:
        v = (r.get("labels") or {}).get("value")
        if v is None:
            continue
        agg = out.setdefault(v, {"sum": 0.0, "n": 0, "runs": set()})
        agg["sum"] += float(r.get("v", 0.0))
        agg["n"] += 1
        agg["runs"].add(r.get("run", "?"))
    return {v: {"mean_us": a["sum"] / a["n"], "n": a["n"],
                "runs": sorted(a["runs"])}
            for v, a in out.items() if a["n"]}


# -- the decision record -----------------------------------------------
def _decide(knob, label, chosen, source, heuristic=None, evidence=None):
    """Emit the typed decision everywhere it must be visible: ring
    event (this process's timeline), history row (the next run's
    evidence), and the process-local log the blackbox embeds."""
    dec = {"ts": time.time(), "knob": str(knob),
           "label": str(label or ""), "chosen": chosen,
           "source": str(source)}
    if heuristic is not None:
        dec["heuristic"] = heuristic
        try:
            dec["delta_vs_heuristic"] = float(chosen) - float(heuristic)
        except (TypeError, ValueError):
            pass
    if evidence:
        dec["evidence"] = evidence
    with _LOCK:
        _DECISIONS.append(dec)
    _bb.record("autotune", "decision", knob=dec["knob"],
               label=dec["label"], chosen=str(chosen), source=source,
               heuristic=str(heuristic) if heuristic is not None
               else "", rows=int((evidence or {}).get("rows", 0)))
    try:
        v = float(chosen)
    except (TypeError, ValueError):
        v = 1.0
    # evidence BASIS rides on the durable row (ISSUE 19 satellite):
    # the next run's cost-drift rule compares its own measurements
    # against what THIS decision rested on — without these fields the
    # contradiction would be undetectable across processes
    extra = {}
    ev = evidence or {}
    if "basis_bytes" in ev:
        extra["basis_bytes"] = int(ev["basis_bytes"])
    cand = ev.get("candidates") or {}
    if str(chosen) in cand:
        extra["best_us"] = float(cand[str(chosen)])
    if ev.get("drift_refresh"):
        extra["drift_refresh"] = True
    _hist.record("autotune", "decision", v,
                 labels={"knob": dec["knob"], "label": dec["label"],
                         "source": dec["source"]},
                 chosen=str(chosen),
                 heuristic=str(heuristic) if heuristic is not None
                 else None,
                 rows=int(ev.get("rows", 0)), **extra)
    return chosen


def suggest(knob, label, candidates, fallback, heuristic=None):
    """Generic resolver: measured probe argmin over >= 2 distinct
    candidate values, else ``fallback() -> (value, source, evidence)``.
    ``candidates`` restricts the measured tier to values the caller
    considers legal (None = any probed value); ``heuristic`` rides on
    the decision record for the tuned-vs-heuristic delta."""
    if not enabled():
        value, _src, _ev = fallback()
        return value
    # a fired cost-drift rule invalidated this key: prior-run evidence
    # contradicted reality, so re-resolve from THIS run's rows only
    # and mark the flip as a typed ``*-refresh`` decision
    refresh = invalidated(knob, label)
    meas = measured_candidates(
        knob, label, run=_current_run() if refresh else None)
    if candidates is not None:
        legal = {str(c) for c in candidates}
        meas = {v: m for v, m in meas.items() if v in legal}
    if len(meas) >= 2:
        best = min(meas, key=lambda v: meas[v]["mean_us"])
        evidence = {
            "rows": sum(m["n"] for m in meas.values()),
            "runs": sorted({r for m in meas.values()
                            for r in m["runs"]}),
            "candidates": {v: round(m["mean_us"], 1)
                           for v, m in meas.items()},
        }
        if refresh:
            evidence["drift_refresh"] = True
            _clear_invalidated(knob, label)
        try:
            chosen = type(candidates[0])(best) if candidates \
                else float(best)
        except (TypeError, ValueError):
            chosen = best
        return _decide(knob, label, chosen,
                       "measured-refresh" if refresh else "measured",
                       heuristic=heuristic, evidence=evidence)
    value, source, evidence = fallback()
    if refresh:
        evidence = dict(evidence or {})
        evidence["drift_refresh"] = True
        source = "%s-refresh" % source
        _clear_invalidated(knob, label)
    return _decide(knob, label, value, source, heuristic=heuristic,
                   evidence=evidence)


# -- cost-model helpers (the modeled tier) -----------------------------
def _family_cost_rows(label, run=None):
    """Cross-run cost rows for one executable family (`label` exact or
    ``label[...]``/``label:...`` children — the bracket rule the
    registry uses, widened to the collective `:rs:`/`:ag:` rows).
    ``run=`` restricts to one run (drift judges a single run's rows)."""
    if not label:
        return []
    rows = _hist.query(name=str(label), kind="cost", run=run)
    out = []
    for r in rows:
        n = str(r.get("name", ""))
        if n == label or n.startswith(label + "[") \
                or n.startswith(label + ":"):
            out.append(r)
    return out


def _measured_step_bytes(label, run=None):
    """The family's largest measured per-step bytes_accessed across
    runs (0 when history has no resolved row) + the evidence dict."""
    rows = _family_cost_rows(label, run=run)
    basis, runs = 0.0, set()
    for r in rows:
        b = float(r.get("bytes_accessed", 0.0) or 0.0)
        if b > basis:
            basis = b
        runs.add(r.get("run", "?"))
    return basis, {"rows": len(rows), "runs": sorted(runs)}


# -- the knobs ---------------------------------------------------------
def suggest_bucket_cap(param_bytes, n_shards, label=None,
                       ladder=BUCKET_CAP_LADDER):
    """The ZeRO bucket cap in MB — the default steering for
    ``parallel/zero.py`` (replaces the one-shot
    ``costs.suggest_bucket_mb`` call; the heuristic survives as this
    function's cold-history fallback and warns once when deciding).

    measured: probe rows (knob="zero_bucket_mb") -> argmin step wall.
    modeled:  cross-run cost rows -> the 1/32 traffic rule applied to
              MEASURED per-step bytes (what suggest_bucket_mb could
              only see within one process).
    heuristic: costs.suggest_bucket_mb(param_bytes, ...) — deciding.
    """
    heuristic = _costs.suggest_bucket_mb(param_bytes, n_shards,
                                         label_prefix=label)

    def fallback():
        basis, evidence = _measured_step_bytes(label)
        if basis > 0:
            cap = float(min(16.0, max(1.0, basis / 32.0 / 1e6)))
            evidence["basis_bytes"] = int(basis)
            return cap, "modeled", evidence
        # deciding=... : when the operator disabled the tuner the
        # heuristic is a deliberate choice, not a cold-history gap —
        # the warn-once shim only fires on the latter
        cap = _costs.suggest_bucket_mb(param_bytes, n_shards,
                                       label_prefix=label,
                                       deciding=enabled())
        return cap, "heuristic", {"rows": 0}

    return suggest("zero_bucket_mb", label or "",
                   [float(c) for c in ladder], fallback,
                   heuristic=heuristic)


def suggest_batch_size(label, ladder, default=None):
    """Per-replica batch from measured probes (knob="batch_size",
    score = wall per EXAMPLE so sizes compare); cold history returns
    ``default`` (or the smallest ladder entry — the conservative
    choice until a probe exists)."""
    ladder = [int(b) for b in ladder]

    def fallback():
        chosen = int(default) if default is not None else min(ladder)
        return chosen, "default", {"rows": 0}

    return suggest("batch_size", label, ladder, fallback)


def suggest_serve_buckets(label, ladder):
    """The serve/gen padding-bucket ladder: measured probes
    (knob="serve_buckets", value = comma-joined ladder) pick among
    candidate ladders; cold history returns the ladder unchanged.
    Candidate encoding: ``"1,8,32"``."""
    enc = ",".join(str(int(b)) for b in ladder)

    def fallback():
        return enc, "default", {"rows": 0}

    chosen = suggest("serve_buckets", label, None, fallback)
    try:
        return tuple(int(b) for b in str(chosen).split(",") if b)
    except ValueError:
        return tuple(int(b) for b in ladder)


def suggest_donate(label, default=True):
    """Donate buffers for this executable family?  Evidence tier:
    any cross-run cost row showing ``donated_bytes > 0`` proves the
    aliasing engages on this backend -> True (measured); rows that
    carry memory analysis but zero donated bytes on every run mean
    donation is being silently dropped -> surface ``default``
    unchanged but say so in the decision; no rows -> default."""
    rows = _family_cost_rows(label)
    seen_mem = [r for r in rows if "donated_bytes" in r
                or "argument_bytes" in r]
    donated = any(float(r.get("donated_bytes", 0) or 0) > 0
                  for r in seen_mem)
    if not enabled():
        return bool(default)
    if donated:
        return _decide("donate", label, True, "measured",
                       evidence={"rows": len(rows)})
    if seen_mem:
        return _decide("donate", label, bool(default), "modeled",
                       evidence={"rows": len(rows),
                                 "note": "memory rows show 0 donated "
                                         "bytes — aliasing not "
                                         "engaging"})
    return _decide("donate", label, bool(default), "default",
                   evidence={"rows": 0})


def suggest_remat(label, hbm_budget_bytes, default=False):
    """Rematerialize the layer stack?  True when the family's measured
    temp bytes (activation working set) exceed the budget on any run —
    recompute is then cheaper than the spill; cold history returns
    ``default``."""
    rows = _family_cost_rows(label)
    peak = max((float(r.get("temp_bytes", 0) or 0) for r in rows),
               default=0.0)
    if not enabled():
        return bool(default)
    if peak > 0:
        over = peak > float(hbm_budget_bytes)
        return _decide("remat", label, bool(over), "measured",
                       evidence={"rows": len(rows),
                                 "temp_peak_bytes": int(peak),
                                 "budget_bytes":
                                     int(hbm_budget_bytes)})
    return _decide("remat", label, bool(default), "default",
                   evidence={"rows": 0})


# -- cost-model drift (ISSUE 19 satellite) -----------------------------
def prior_decision(knob, label):
    """The latest durable decision row for (knob, label) from a PRIOR
    run that recorded comparable evidence (``best_us`` for measured
    decisions, ``basis_bytes`` for modeled ones).  None when no such
    row exists — a decision without a recorded basis cannot be
    contradicted."""
    if not _hist.enabled():
        return None
    rows = _hist.query(name="decision", kind="autotune",
                       labels={"knob": str(knob),
                               "label": str(label or "")})
    cur = _current_run()
    for r in reversed(rows):            # query sorts oldest-first
        if "best_us" in r or "basis_bytes" in r:
            # the NEWEST evidence-bearing decision being this run's
            # own means the key was already re-resolved here (e.g. a
            # drift refresh) — nothing stale left to contradict
            return None if r.get("run") == cur else r
    return None


def drift_evidence(knob, label):
    """Judge THIS run's measured evidence against the latest prior
    run's decision for (knob, label).

    Returns None when unjudgeable (no prior decision with a recorded
    basis, or this run has produced no comparable measurement yet),
    else ``{"prior", "current", "ratio", "basis", "chosen",
    "prior_run", "drift"}`` — ratio = current/prior, ``drift`` true
    when the contradiction exceeds `DRIFT_FACTOR` in either
    direction.  The SLO layer's cost-drift rule is a thin wrapper
    around this."""
    prior = prior_decision(knob, label)
    if prior is None:
        return None
    cur_run = _current_run()
    if cur_run is None:
        return None
    if "best_us" in prior:
        chosen = str(prior.get("chosen", ""))
        m = measured_candidates(knob, label, run=cur_run).get(chosen)
        if not m:
            return None
        prior_v, cur_v, basis = \
            float(prior["best_us"]), float(m["mean_us"]), "probe_us"
    else:
        cur_bytes, _ev = _measured_step_bytes(label, run=cur_run)
        if cur_bytes <= 0:
            return None
        prior_v, cur_v, basis = \
            float(prior["basis_bytes"]), float(cur_bytes), "bytes"
    if prior_v <= 0:
        return None
    ratio = cur_v / prior_v
    return {"prior": round(prior_v, 1), "current": round(cur_v, 1),
            "ratio": round(ratio, 3), "basis": basis,
            "chosen": prior.get("chosen"),
            "prior_run": prior.get("run"),
            "drift": ratio > DRIFT_FACTOR or ratio < 1.0 / DRIFT_FACTOR}


# -- introspection (teletop / blackbox) --------------------------------
def decisions():
    """This process's decision log, oldest first."""
    with _LOCK:
        return [dict(d) for d in _DECISIONS]


def block():
    """The blackbox ``autotune`` block: the decisions (None when
    nothing happened — dump_blackbox drops empty blocks)."""
    decs = decisions()
    if not decs:
        return None
    return {"decisions": decs}


def reset():
    """Tests: drop the process-local decision log + drift flags."""
    with _LOCK:
        del _DECISIONS[:]
        _INVALIDATED.clear()

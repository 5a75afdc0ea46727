"""Compile loop (ISSUE 18 / ROADMAP item 2): act on what the cost
telemetry measures.

The repo measures everything about its executables — per-executable
flops/bytes/compile-wall in the cost registry (ISSUE 5), persisted
across runs by the durable history (ISSUE 12) — and this package is
where those measurements steer compilation instead of just describing
it.  Two cooperating parts:

- :mod:`~incubator_mxnet_tpu.compile.autotune` — a search over the
  knobs that shape executables (ZeRO bucket cap, batch size,
  serve/gen bucket ladders, donation, remat), scored by measured
  ``kind="autotune"`` probe rows and ``kind="cost"`` executable rows
  read from the cross-run history, with `costs.suggest_bucket_mb` as
  the cold-history fallback.  Every choice emits a typed, durable
  ``autotune/decision`` record (ring event + history row + blackbox
  block) naming the measured rows that justified it.
- :mod:`~incubator_mxnet_tpu.compile.stacking` — collapse N
  structurally-identical per-layer executables into ONE via
  ``lax.scan`` over stacked parameters, with a bit-parity oracle
  against the unstacked path and measured compile-wall/dispatch
  deltas.
"""
from __future__ import annotations

from . import autotune, stacking  # noqa: F401

__all__ = ["autotune", "stacking"]

"""Test config: run the whole corpus on a virtual 8-device CPU mesh.

Mirrors the reference strategy (SURVEY §4): one op-test corpus, re-run
per backend; distributed tests fake multi-chip as 8 virtual host devices
(the analogue of multi-node-as-multi-process ps-lite tests).  The corpus
builds its arrays on mx.cpu(), so it never runs on a chip; the chip
check is chip_smoke.py.
"""
import os
import tempfile

# black-box dumps from fault-injection/backstop tests are real (the
# triggers fire for real) — they must land in a scratch dir, not the
# repo checkout the corpus runs from (mkdtemp only when the operator
# hasn't pointed the dir somewhere already)
if "MXNET_BLACKBOX_DIR" not in os.environ:
    os.environ["MXNET_BLACKBOX_DIR"] = \
        tempfile.mkdtemp(prefix="mxtpu-blackbox-")

# durable-telemetry history shards (ISSUE 12): same reasoning — tests
# that enable history (or trainers that checkpoint with it on) must
# write their history-*.jsonl shards into scratch, never the checkout
if "MXNET_HISTORY_DIR" not in os.environ:
    os.environ["MXNET_HISTORY_DIR"] = \
        tempfile.mkdtemp(prefix="mxtpu-history-")

# must happen before jax backend initialisation
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")

import numpy as _np
import pytest


@pytest.fixture(autouse=True)
def _seed_everything(request):
    """Reproducible-but-varied seeds (ref: @with_seed() in
    tests/python/unittest/common.py)."""
    seed = abs(hash(request.node.nodeid)) % (2 ** 31)
    _np.random.seed(seed)
    import incubator_mxnet_tpu as mx
    mx.random.seed(seed)
    yield


def pytest_configure(config):
    # the resilience suite is CPU-fast and runs in tier-1 by default;
    # the marker exists so fault-injection tests can be selected or
    # excluded explicitly (pytest -m fault / -m 'not fault')
    config.addinivalue_line(
        "markers", "fault: fault-injection resilience tests (CPU-fast, "
        "run in tier-1 by default)")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    # the serving suite is CPU-fast and runs in tier-1 by default; the
    # marker lets the inference-engine tests be selected or excluded
    # explicitly (pytest -m serve / -m 'not serve')
    config.addinivalue_line(
        "markers", "serve: inference-serving engine tests (CPU-fast, "
        "run in tier-1 by default)")
    # the telemetry suite (spans/exporter/StepTelemetry/teletop) is
    # CPU-fast and runs in tier-1 by default; the marker lets it be
    # selected or excluded explicitly (pytest -m telemetry)
    config.addinivalue_line(
        "markers", "telemetry: observability-layer tests (CPU-fast, "
        "run in tier-1 by default)")
    # the flight-recorder / black-box suite (ring, dump triggers, cost
    # registry, blackbox CLI) is CPU-fast and runs in tier-1 by
    # default; the marker lets it be selected or excluded explicitly
    # (pytest -m blackbox)
    config.addinivalue_line(
        "markers", "blackbox: flight-recorder forensics tests "
        "(CPU-fast, run in tier-1 by default)")
    # the input-pipeline suite (multi-process decode service, shard
    # partitioning, shared-memory ring, device feed) is CPU-fast and
    # runs in tier-1 by default; the marker lets it be selected or
    # excluded explicitly (pytest -m io / -m 'not io')
    config.addinivalue_line(
        "markers", "io: input-pipeline / decode-service tests "
        "(CPU-fast, run in tier-1 by default)")
    # the elastic-mesh suite (heartbeat health, membership epochs,
    # shrink/re-admission on the virtual mesh) is CPU-fast and runs in
    # tier-1 by default; the marker lets it be selected or excluded
    # explicitly (pytest -m elastic)
    config.addinivalue_line(
        "markers", "elastic: elastic-mesh replica loss/re-admission "
        "tests (CPU-fast, run in tier-1 by default)")
    # the integrity suite (checkpoint manifests + salvage, corrupt-
    # record quarantine, cross-replica SDC audit) is CPU-fast and
    # runs in tier-1 by default; the marker lets it be selected or
    # excluded explicitly (pytest -m integrity)
    config.addinivalue_line(
        "markers", "integrity: corruption-detection/recovery tests "
        "(CPU-fast, run in tier-1 by default)")
    # ZeRO-2/3 sharding + overlap-first collective tests (ISSUE 10);
    # the check_scaling gate itself is slow-marked
    config.addinivalue_line(
        "markers", "scaling: ZeRO sharding / weak-scaling tests "
        "(CPU-fast, run in tier-1 by default)")
    # fleet observability (ISSUE 11): cross-process trace propagation,
    # kvstore-aggregated per-replica telemetry, straggler detection
    config.addinivalue_line(
        "markers", "fleet: fleet-observability tests (CPU-fast, run "
        "in tier-1 by default)")
    # durable telemetry (ISSUE 12): on-disk metrics history, SLO /
    # burn-rate alerting, cross-run trend tooling
    config.addinivalue_line(
        "markers", "slo: durable-telemetry history + SLO alerting "
        "tests (CPU-fast, run in tier-1 by default)")
    # generation serving (ISSUE 14): KV-cached decode, continuous
    # batching, greedy-parity oracle, KV-aware admission
    config.addinivalue_line(
        "markers", "gen: generation-serving (KV-cached decode / "
        "continuous batching) tests (CPU-fast, run in tier-1 by "
        "default)")
    # int8 serving + AMP training (ISSUE 15): PTQ calibration/parity,
    # int8 admission footprints, AMP trajectories and the
    # LossScaler→NaN-guard handoff
    config.addinivalue_line(
        "markers", "quant: int8 quantized-serving + AMP training "
        "tests (CPU-fast, run in tier-1 by default)")
    # fleet control plane (ISSUE 16): FleetSupervisor autoscaling
    # hysteresis, canary ramp/promote/rollback, registration timeouts
    # and ledger-release invariants
    config.addinivalue_line(
        "markers", "controlplane: SLO-driven fleet-supervisor "
        "(autoscaling / canary deploy / rollback) tests (CPU-fast, "
        "run in tier-1 by default)")
    # the compile loop (ISSUE 18): history-trained autotuner,
    # lax.scan layer-stacking parity, pre-warm manifest replay; the
    # check_compile gate wrapper itself is slow-marked
    config.addinivalue_line(
        "markers", "compile: compile-loop (autotuner / stacking / "
        "pre-warm manifest) tests (CPU-fast, run in tier-1 by "
        "default)")
    # request-level tail tracing (ISSUE 19): per-phase latency
    # journals, exemplar promotion, alert-attached autopsies, the
    # cost-drift rule
    config.addinivalue_line(
        "markers", "reqtrace: request-journal / exemplar / autopsy "
        "tests (CPU-fast, run in tier-1 by default)")
    # memory observatory (ISSUE 20): sampled HBM watermarks, tenant
    # attribution join, drift rule, OOM forensics / memautopsy
    config.addinivalue_line(
        "markers", "memwatch: memory-observatory (watermark / "
        "attribution / drift / OOM-autopsy) tests (CPU-fast, run in "
        "tier-1 by default)")


@pytest.fixture(autouse=True)
def _clean_fault_registry():
    """No armed fault may leak across tests (determinism of the whole
    corpus); cheap no-op when the registry is empty."""
    import incubator_mxnet_tpu.fault as fault
    fault.clear()
    yield
    fault.clear()

"""Runtime feature introspection (ref: python/mxnet/runtime.py +
src/libinfo.cc — mx.runtime.Features()).

Reports the TPU build's capabilities: backend platform, chip generation,
device count, pallas availability, distributed initialisation state.
"""
from __future__ import annotations

from collections import namedtuple

Feature = namedtuple("Feature", ["name", "enabled"])

__all__ = ["Features", "feature_list"]


def feature_list():
    import jax
    feats = []

    def add(name, enabled):
        feats.append(Feature(name, bool(enabled)))

    backend = jax.default_backend()
    add("TPU", backend == "tpu")
    add("CPU", True)
    add("CUDA", False)                      # by design: no GPU path
    add("CUDNN", False)
    add("MKLDNN", False)
    add("XLA", True)
    add("PALLAS", _has_pallas())
    add("BF16", True)
    add("INT64_TENSOR_SIZE", True)
    add("DIST_KVSTORE", True)
    add("SIGNAL_HANDLER", False)
    add("PROFILER", True)
    add("OPENCV", _has_module("cv2"))
    add("PIL", _has_module("PIL"))
    add("MULTIHOST", jax.process_count() > 1)
    return feats


def _has_pallas():
    try:
        from jax.experimental import pallas    # noqa: F401
        return True
    except Exception:
        return False


def _has_module(name):
    import importlib.util
    return importlib.util.find_spec(name) is not None


class Features(dict):
    """ref: mx.runtime.Features — dict-like with is_enabled."""

    def __init__(self):
        super().__init__([(f.name, f) for f in feature_list()])

    def is_enabled(self, name):
        return self[name].enabled

    def __repr__(self):
        return "[%s]" % ", ".join(
            "✔ %s" % n if f.enabled else "✖ %s" % n
            for n, f in self.items())

"""Large-tensor smoke: indexing past 2**31 elements must use 64-bit
arithmetic end to end (ref: tests/nightly/test_large_array.py, the
int64 "large tensor support" tier).

Like the reference, large-tensor support is an opt-in flag —
``MXNET_INT64_TENSOR_SIZE=1`` (ref: the USE_INT64_TENSOR_SIZE build
flag) — because 64-bit index math costs speed/memory on every gather.
The flag is honored at import time, so the checks run in a fresh
subprocess with it set; without it, 32-bit gather indices silently
wrap past 2**31 (verified: that is exactly the failure this tier
exists to catch).  Arrays are int8 to keep the footprint ~2.2 GB per
live tensor; guarded by free host memory.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

_SCRIPT = r"""
import jax
# host-only tier (same recipe as tests/conftest.py): the TPU-side
# compiler rejects x64-index HLO
jax.config.update("jax_platforms", "cpu")
import numpy as np
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd

LARGE = 2 ** 31 + 64

# -- 1-D: create / far-end write / read / reduce / take ------------
a = nd.zeros((LARGE,), dtype="int8")
assert a.size == LARGE > 2 ** 31
a[LARGE - 4:] = 3
assert int(a[2 ** 31 + 61].asscalar()) == 3, "far-end read wrapped"
assert int(a.sum().asscalar()) == 12, "reduction lost far-end elements"
idx = nd.array(np.array([0, LARGE - 1], np.int64), dtype="int64")
got = nd.take(a, idx).asnumpy()
np.testing.assert_array_equal(got, np.array([0, 3], np.int8))
del a, idx, got

# -- 2-D: row count * cols crosses the boundary --------------------
rows = 2 ** 21 + 1
b = nd.zeros((rows, 1024), dtype="int8")
assert b.size > 2 ** 31
b[rows - 1, 1023:] = 5
assert int(b[rows - 1, 1023].asscalar()) == 5
assert int(b.sum().asscalar()) == 5
# flat argmax past 2**31: dtype='int64' (the reference's large-tensor
# pattern — float32 index returns lose precision past 2**24)
flat = b.reshape((-1,))
pos = int(nd.argmax(flat, axis=0, dtype="int64").asscalar())
assert pos == rows * 1024 - 1, "argmax position truncated: %d" % pos
print("LARGE_OK")
"""


def _available_gb():
    try:
        return (os.sysconf("SC_AVPHYS_PAGES") *
                os.sysconf("SC_PAGE_SIZE")) / 2 ** 30
    except (ValueError, OSError):
        return 0.0


@pytest.mark.slow
@pytest.mark.skipif(_available_gb() < 16,
                    reason="large-tensor tier needs >=16 GB free host "
                           "memory")
def test_int64_indexing_with_flag():
    # slow-marked: ~190s of multi-GB allocations is the nightly tier
    # this directory is named for — inside the 870s tier-1 cap it was
    # starving the tail of the corpus (the fast flag-registration
    # check below stays in tier-1)
    env = dict(os.environ)
    env.update({"MXNET_INT64_TENSOR_SIZE": "1", "JAX_PLATFORMS": "cpu"})
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "LARGE_OK" in res.stdout


def test_flag_registered_and_off_by_default():
    from incubator_mxnet_tpu import config
    assert config.get("MXNET_INT64_TENSOR_SIZE") in (False, True)
    assert "MXNET_INT64_TENSOR_SIZE" in config.describe()

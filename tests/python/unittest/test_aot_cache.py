"""Executable AOT cache (aot_cache.py): store / reload / corruption
fallback."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture
def cache_dir(tmp_path):
    from incubator_mxnet_tpu import config as _cfg
    prev = _cfg.get("MXNET_AOT_CACHE_DIR")
    _cfg.set("MXNET_AOT_CACHE_DIR", str(tmp_path))
    yield str(tmp_path)
    _cfg.set("MXNET_AOT_CACHE_DIR", prev or "")


def _fwd(a, b):
    return jax.vjp(lambda x, y: (x * y).sum(), a, b)


def test_store_reload_and_vjp_roundtrip(cache_dir):
    from incubator_mxnet_tpu.aot_cache import aot_jit, _AotJitted

    x = jnp.ones((8, 8))
    y = jnp.full((8, 8), 2.0)
    j1 = aot_jit(_fwd)
    assert isinstance(j1, _AotJitted)
    out1, vjp1 = j1(x, y)
    blobs = [f for f in os.listdir(cache_dir) if f.endswith(".pjrtx")]
    assert len(blobs) == 1, blobs

    # a FRESH wrapper (as a fresh process would build) must reload the
    # serialized executable and produce identical results, including
    # through the vjp closure
    j2 = aot_jit(_fwd)
    out2, vjp2 = j2(x, y)
    assert float(out1) == float(out2) == 128.0
    g1 = vjp1(jnp.ones(()))
    g2 = vjp2(jnp.ones(()))
    np.testing.assert_array_equal(np.asarray(g1[0]), np.asarray(g2[0]))
    # no second blob was written for the same program
    assert len([f for f in os.listdir(cache_dir)
                if f.endswith(".pjrtx")]) == 1


def test_corrupt_blob_falls_back_to_compile(cache_dir):
    from incubator_mxnet_tpu.aot_cache import aot_jit

    x = jnp.arange(16.0).reshape(4, 4)
    j1 = aot_jit(lambda a: a * 3.0)
    np.testing.assert_allclose(np.asarray(j1(x)), np.asarray(x) * 3.0)
    blobs = [f for f in os.listdir(cache_dir) if f.endswith(".pjrtx")]
    assert blobs
    with open(os.path.join(cache_dir, blobs[0]), "wb") as f:
        f.write(b"not an executable")
    # stale/corrupt entry: clean fallback to compile, entry overwritten
    j2 = aot_jit(lambda a: a * 3.0)
    np.testing.assert_allclose(np.asarray(j2(x)), np.asarray(x) * 3.0)
    with open(os.path.join(
            cache_dir,
            [f for f in os.listdir(cache_dir)
             if f.endswith(".pjrtx")][0]), "rb") as f:
        assert f.read(16) != b"not an executabl"


def test_weak_type_resolves_own_executable(cache_dir):
    """weak-type-only signature differences must NOT share one compiled
    executable (jax.jit recompiles on them; sharing would let dtype
    promotion diverge from the fallback path — ADVICE r5)."""
    from incubator_mxnet_tpu.aot_cache import aot_jit

    j = aot_jit(lambda a: a * 2)
    committed = jnp.asarray(np.float32(3.0))      # strong f32
    weak = jnp.asarray(3.0)                       # weak-typed f32 scalar
    assert not committed.weak_type and weak.weak_type
    assert float(j(committed)) == float(j(weak)) == 6.0
    sigs = set(j._compiled)
    assert len(sigs) == 2, "weak_type missing from the signature"


def test_key_for_uses_argument_device(cache_dir):
    """The cache key's device kind/platform must come from the device
    the executable is pinned to (_args_device), not jax.devices()[0]
    (heterogeneous-process stale-key risk — ADVICE r5)."""
    import inspect
    from incubator_mxnet_tpu import aot_cache

    sig = inspect.signature(aot_cache._key_for)
    assert "dev" in sig.parameters     # caller passes _args_device(args)
    # same device → stable key
    j = aot_cache.aot_jit(lambda a: a + 1)
    x = jax.device_put(jnp.ones(4), jax.devices()[0])
    lowered = j.lower(x)
    k0 = aot_cache._key_for(lowered, jax.devices()[0])
    assert k0 == aot_cache._key_for(lowered, jax.devices()[0])


def test_disabled_without_cache_dir():
    from incubator_mxnet_tpu import config as _cfg
    prev = _cfg.get("MXNET_AOT_CACHE_DIR")
    _cfg.set("MXNET_AOT_CACHE_DIR", "")
    try:
        from incubator_mxnet_tpu.aot_cache import aot_jit, _AotJitted
        j = aot_jit(lambda a: a + 1)
        assert not isinstance(j, _AotJitted)   # plain jax.jit passthrough
    finally:
        _cfg.set("MXNET_AOT_CACHE_DIR", prev or "")


def _blobs(d):
    import os as _os
    return {f for f in _os.listdir(d) if f.endswith(".pjrtx")}


def test_cache_eviction_keeps_newest_by_mtime(cache_dir):
    """MXNET_AOT_CACHE_MAX bounds the on-disk cache: after each store,
    oldest-mtime entries beyond K are evicted — keep-K LRU, so a
    long-lived serving host's cache dir cannot grow without limit."""
    from incubator_mxnet_tpu import config as _cfg
    from incubator_mxnet_tpu.aot_cache import aot_jit

    _cfg.set("MXNET_AOT_CACHE_MAX", "2")
    try:
        j = aot_jit(lambda a: a * 2.0)
        now = os.path.getmtime(cache_dir)
        j(jnp.ones((2,)))                       # blob A
        (a,) = _blobs(cache_dir)
        os.utime(os.path.join(cache_dir, a), (now - 100, now - 100))
        j(jnp.ones((3,)))                       # blob B
        (b,) = _blobs(cache_dir) - {a}
        os.utime(os.path.join(cache_dir, b), (now - 50, now - 50))
        j(jnp.ones((4,)))                       # blob C → trim to 2
        left = _blobs(cache_dir)
        assert len(left) == 2
        assert a not in left, "oldest-mtime entry must be evicted first"
        assert b in left
    finally:
        _cfg.unset("MXNET_AOT_CACHE_MAX")


def test_cache_hit_refreshes_eviction_order(cache_dir):
    """A deserialize HIT refreshes the entry's mtime, so
    recently-SERVED executables survive eviction (LRU, not FIFO)."""
    from incubator_mxnet_tpu import config as _cfg
    from incubator_mxnet_tpu.aot_cache import aot_jit

    _cfg.set("MXNET_AOT_CACHE_MAX", "2")
    try:
        j = aot_jit(lambda a: a * 3.0)
        now = os.path.getmtime(cache_dir)
        j(jnp.ones((2,)))                       # blob A
        (a,) = _blobs(cache_dir)
        os.utime(os.path.join(cache_dir, a), (now - 100, now - 100))
        j(jnp.ones((3,)))                       # blob B
        (b,) = _blobs(cache_dir) - {a}
        os.utime(os.path.join(cache_dir, b), (now - 50, now - 50))
        # fresh wrapper HITS blob A → its mtime refreshes past B's
        j2 = aot_jit(lambda a: a * 3.0)
        np.testing.assert_allclose(np.asarray(j2(jnp.ones((2,)))),
                                   np.full((2,), 3.0))
        assert os.path.getmtime(os.path.join(cache_dir, a)) > \
            os.path.getmtime(os.path.join(cache_dir, b))
        j(jnp.ones((4,)))                       # blob C → trim evicts B
        left = _blobs(cache_dir)
        assert len(left) == 2
        assert a in left and b not in left
    finally:
        _cfg.unset("MXNET_AOT_CACHE_MAX")


def test_cache_unbounded_by_default(cache_dir):
    from incubator_mxnet_tpu.aot_cache import aot_jit, trim_cache

    j = aot_jit(lambda a: a - 1.0)
    for n in (2, 3, 4):
        j(jnp.ones((n,)))
    assert len(_blobs(cache_dir)) == 3          # MXNET_AOT_CACHE_MAX=0
    assert trim_cache() == 0


@pytest.fixture
def load_breaker_state():
    """Save/restore the process-wide disk-load breaker (ISSUE 14
    satellite) so breaker tests can trip it without poisoning the
    rest of the corpus."""
    from incubator_mxnet_tpu import aot_cache as ac
    saved = (ac._LOAD_FAILS[0], ac._LOADS_DISABLED[0],
             ac._SELF_VERIFIED[0])
    yield ac
    (ac._LOAD_FAILS[0], ac._LOADS_DISABLED[0],
     ac._SELF_VERIFIED[0]) = saved


def test_load_breaker_trips_on_repeated_deserialize_errors(
        cache_dir, load_breaker_state):
    """A backend whose deserialize fails DETERMINISTICALLY (the
    BENCH_serve deserialize_error:6 smoking gun) trips the load
    breaker after 2 consecutive failures: remaining executables skip
    the doomed load (aot.load_skipped) behind ONE classified
    aot.load_disabled verdict, instead of a per-executable stale
    storm."""
    import warnings
    from incubator_mxnet_tpu.monitor import events
    ac = load_breaker_state
    ac._LOAD_FAILS[0], ac._LOADS_DISABLED[0] = 0, None

    x = jnp.ones((4,))
    fns = [ac.aot_jit(lambda a, k=k: a * float(k), label="brk%d" % k)
           for k in range(3)]
    for f in fns:
        f(x)                                    # populate blobs
    stale0 = events.get("aot.stale")
    skip0 = events.get("aot.load_skipped")
    # the staticmethod OBJECT, not the unwrapped function — restoring
    # a bare function would rebind it as an instance method
    orig = ac._AotJitted.__dict__["_deserialize"]
    ac._AotJitted._deserialize = staticmethod(
        lambda blob, it, ot, dev: (_ for _ in ()).throw(
            RuntimeError("UNIMPLEMENTED: deserialize_executable")))
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for k in range(3):      # fresh wrappers = a fresh process
                f = ac.aot_jit(lambda a, k=k: a * float(k),
                               label="brk%d" % k)
                np.testing.assert_allclose(np.asarray(f(x)),
                                           np.asarray(x) * k)
    finally:
        ac._AotJitted._deserialize = orig
    assert events.get("aot.stale") - stale0 == 2        # breaker at 2
    assert events.get("aot.load_skipped") - skip0 == 1  # 3rd skipped
    assert ac._LOADS_DISABLED[0] is not None
    assert any("disk-load path disabled" in str(m.message) for m in w)


def test_post_store_self_verify_disables_broken_backend(
        tmp_path, load_breaker_state):
    """The self-verify half: a backend that cannot load its OWN
    serialization is caught in the run that WRITES the cache — loads
    disabled with reason self_verify, no warm-run stale storm."""
    from incubator_mxnet_tpu import config as _cfg
    from incubator_mxnet_tpu.monitor import events
    ac = load_breaker_state
    ac._LOAD_FAILS[0], ac._LOADS_DISABLED[0] = 0, None
    ac._SELF_VERIFIED[0] = False
    prev = _cfg.get("MXNET_AOT_CACHE_DIR")
    _cfg.set("MXNET_AOT_CACHE_DIR", str(tmp_path))
    orig = ac._AotJitted.__dict__["_deserialize"]
    ac._AotJitted._deserialize = staticmethod(
        lambda blob, it, ot, dev: (_ for _ in ()).throw(
            RuntimeError("UNIMPLEMENTED: deserialize_executable")))
    try:
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            f = ac.aot_jit(lambda a: a + 1.0, label="sv")
            np.testing.assert_allclose(np.asarray(f(jnp.ones((2,)))),
                                       np.full((2,), 2.0))
        assert ac._LOADS_DISABLED[0] == "self_verify"
        assert events.get("aot.selfcheck_failed") >= 1
    finally:
        ac._AotJitted._deserialize = orig
        _cfg.set("MXNET_AOT_CACHE_DIR", prev or "")

"""Compiles for a described TPU v5e, without the chip: what the chip's own
compiler makes of the engine's donating executables at the real widths.
Nothing runs, so nothing here is a time.  All such tests live in this one
file: only one process may hold the TPU's compiler (see the
on-chip-measurement guide), so the topology is described inside a fixture
and never while a module is imported."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.serving import GenerationEngine

pytestmark = pytest.mark.gen


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_sparse_decoder_step_writes_its_cache_in_place(one_chip):
    """The decode step and the join of a SparseDecoder at the published
    widths (2 layers, 4 slots of 4096 rows): the donated cache comes back
    aliased, and the step's temporaries stay far below one cache leaf, so
    there is no cache-sized copy, gather or slice in the compiled program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    from incubator_mxnet_tpu.models.sparse_decoder import SparseDecoder

    S, L, layers = 4, 4096, 2
    net = SparseDecoder(1024, 2048, layers, 32, 4, 128, 768, 128, 8, 16, 64,
                        2048, first_held=0, experts_held=2)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    net.cast("bfloat16")
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=S,
                           max_len=L, prompt_buckets=(2048,), queue_cap=4)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = {n: sds(v) for n, v in eng._params.items()}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        pre = eng._prefill._jit.lower(
            params, sds(jnp.zeros((1, 2048), jnp.int32)),
            sds(jnp.zeros((1,), jnp.int32)))
        row = jax.tree_util.tree_map(sds, pre.out_info)
        cache = {"m": {k: jax.ShapeDtypeStruct((S,) + v.shape[1:], v.dtype,
                                               sharding=one_chip)
                       for k, v in row["m"].items()},
                 "tok": sds(jnp.zeros((S,), jnp.int32)),
                 "pos": sds(jnp.zeros((S,), jnp.int32)),
                 "out": sds(jnp.zeros((S, L), jnp.int32))}
        leaf = S * layers * 4 * L * 128 * 2              # k, or v: 33.5 MB
        total = 2 * leaf + S * layers * L * 64 * 2
        step = eng._decode._jit.lower(params, cache).compile() \
            .memory_analysis()
        join = eng._join._jit.lower(
            cache, row, sds(jnp.zeros((), jnp.int32))).compile() \
            .memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert step.alias_size_in_bytes >= total
    assert step.temp_size_in_bytes < leaf // 4, step.temp_size_in_bytes
    assert join.alias_size_in_bytes >= total
    assert join.temp_size_in_bytes < leaf // 4, join.temp_size_in_bytes

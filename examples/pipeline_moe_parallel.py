#!/usr/bin/env python
"""Pipeline + expert parallelism on a device mesh (beyond-reference
axes; runs on the virtual 8-device CPU mesh).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python examples/pipeline_moe_parallel.py
"""
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"

import numpy as np
import jax

# the demo wants >= 8 devices: the virtual CPU mesh (same recipe as
# tests/conftest.py)
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from incubator_mxnet_tpu import parallel


def main():
    n = min(8, len(jax.devices()))
    devs = np.array(jax.devices()[:n])
    d = 32

    # ---- pipeline: n stages, each one tanh(x @ w) ----
    rs = np.random.RandomState(0)
    stages = [{"w": jnp.asarray(rs.randn(d, d) / np.sqrt(d),
                                jnp.float32)} for _ in range(n)]
    stacked = parallel.stack_stage_params(stages)
    x = jnp.asarray(rs.randn(32, d), jnp.float32)
    x_mb = parallel.split_microbatches(x, 8)

    mesh = Mesh(devs, ("pipe",))
    piped = jax.jit(shard_map(
        functools.partial(parallel.pipeline_apply,
                          lambda p, h: jnp.tanh(h @ p["w"]),
                          axis_name="pipe"),
        mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P()))
    out = piped(stacked, x_mb)
    print("pipeline: %d stages, 8 microbatches -> %s" % (n, out.shape))

    # ---- switch MoE: n experts, tokens sharded on the same axis ----
    emesh = Mesh(devs, ("expert",))
    params, expert_fn = parallel.moe_ffn(d, 64, n)
    xt = jnp.asarray(rs.randn(64, d), jnp.float32)
    router_w = jnp.asarray(rs.randn(d, n) * 0.5, jnp.float32)
    y, aux = jax.jit(shard_map(
        lambda xs, rw, ps: parallel.moe_apply(
            xs, rw, expert_fn, ps, axis_name="expert",
            capacity_factor=2.0),
        mesh=emesh, in_specs=(P("expert"), P(), P("expert")),
        out_specs=(P("expert"), P())))(xt, router_w, params)
    print("moe: %d experts, 64 tokens -> %s, aux loss %.3f"
          % (n, y.shape, float(aux)))


if __name__ == "__main__":
    main()

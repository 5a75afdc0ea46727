"""Weights from `--seed`, made on the device in one jitted call.

A reference publishes its parameter `spec`: an ordered list of
`(name, shape, kind)`.  `make(spec, seed, dtype)` draws every leaf
inside one jit, in the type the configuration serves or trains in.  The
program is loaded from these arrays and the plain reference gets the
same values widened to float32, so neither takes anything the other
has made.

Kinds: `matrix` N(0, 1/fan_in) with fan_in the last axis (Dense weights
are (out, in)); `embed:<std>`; `gamma` 1 + 0.1 N; `gamma:<m>` m (1 + 0.1 N);
`beta` 0.1 N;
`bias` 0.02 N; `conv` N(0, 2/fan_in) over (in, kh, kw); `zeros`;
`ones`.  Nothing is left at a constant where a random value is
possible: a bias or a scale left out of the program then shows in
`correct`.
"""
from __future__ import annotations

import math


def _draw(key, shape, kind, jnp, jax):
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        return normal() * (1.0 / math.sqrt(shape[-1]))
    if kind == "conv":
        return normal() * math.sqrt(2.0 / math.prod(shape[1:]))
    if kind.startswith("embed:"):
        return normal() * float(kind.split(":", 1)[1])
    if kind == "gamma":
        return 1.0 + 0.1 * normal()
    if kind.startswith("gamma:"):
        return float(kind.split(":", 1)[1]) * (1.0 + 0.1 * normal())
    if kind == "beta":
        return 0.1 * normal()
    if kind == "bias":
        return 0.02 * normal()
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    raise ValueError("unknown weight kind %r" % kind)


def seed_key(seed):
    """A raw threefry key from any whole number (the driver's seeds pass
    2**31)."""
    import numpy as np
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def make(spec, seed, dtype, device=None, keep_f32=()):
    """{name: array of `dtype`} for every entry of `spec`, from one jitted
    call.  Names in `keep_f32` stay float32 (running statistics)."""
    import jax
    import jax.numpy as jnp

    spec = [(n, tuple(int(s) for s in shp), k) for n, shp, k in spec]
    keep = frozenset(keep_f32)

    def build(key):
        out = {}
        for i, (name, shape, kind) in enumerate(spec):
            v = _draw(jax.random.fold_in(key, i), shape, kind, jnp, jax)
            out[name] = v if name in keep else v.astype(dtype)
        return out

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)


def widen(weights):
    """The same values in float32, for the plain reference."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda w: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), w))(weights)

"""Bucketed one-executable row_sparse training (VERDICT r5 next #4).

The reference's sparse Wide&Deep path (ref: example/sparse/wide_deep +
src/operator/optimizer_op.cc sparse FComputeEx lazy_update) is its FAST
path: embedding gradients exist only for touched rows and the optimizer
updates only those rows.  The r4 realisation here kept those semantics
but ran eagerly — a host `np.unique` per step gave every step dynamic
shapes, so nothing could compile and the path ran ~90x slower than the
fused dense-grad route.

TPU-first fix: make the SHAPES static and the whole step ONE XLA
executable per unique-row bucket.

- `jnp.unique(..., size=K, fill_value=sentinel)` runs ON DEVICE with a
  static output size.  Default bucket: K = B·F — always safe, ZERO
  host syncs (one executable per batch shape).  For skewed workloads
  (few hot features) the caller passes `bucket_rows` to shrink K;
  a step whose true unique count exceeds it is SKIPPED on device
  (state preserved, the previous finite loss returned — see step()'s
  NaN-free contract) and counted in `overflow_steps`, read lazily —
  no step ever blocks on the host.
- Both embedding tables are padded with ONE sentinel row (row `vocab`);
  padded bucket slots gather from and scatter into that garbage row, so
  no masking is needed anywhere and real rows keep exact lazy_update
  semantics (touched rows — and only touched rows — see wd/momentum
  decay, bit-matching the eager `sparse_adam_update`/`sparse_sgd_update`
  kernels in ndarray/sparse.py).
- The forward takes the K GATHERED rows as differentiable inputs, so
  the weight cotangent is a (K, dim) segment-sum — the vocab-sized
  dense gradient never exists, which is what lets this scale to
  million-row vocabularies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ..ndarray.ndarray import NDArray

__all__ = ["BucketedSparseTrainer"]


def _nunique_fn(flat):
    s = jnp.sort(flat)
    return 1 + jnp.sum(s[1:] != s[:-1])


class BucketedSparseTrainer:
    """Jitted lazy-update training for WideDeep-shaped nets.

    net: a `models.wide_deep.WideDeep` (attributes `wide`, `deep_embed`,
    `mlp`, `out`; forward contract `(indices, values) → logits`) with
    initialized params.  optimizer: "adam" | "sgd" (dense params and
    embedding rows use the same rule; rows are lazy).

    step(indices (B, F) int, values (B, F), labels (B,)) → loss (the
    per-step executable is cached per (bucket, batch-shape) key).
    `sync_to_net()` writes the trained tables/params back into the
    Gluon block for save_parameters/export parity.
    """

    def __init__(self, net, optimizer="adam", lr=None, wd=0.0,
                 beta1=0.9, beta2=0.999, epsilon=1e-8,
                 bucket_rows=None):
        from ..parallel.functional import functionalize
        self._net = net
        self._opt = optimizer
        self._lr = float(lr if lr is not None
                         else (1e-3 if optimizer == "adam" else 0.01))
        self._wd = float(wd)
        self._b1, self._b2, self._eps = beta1, beta2, epsilon
        self._wide_name = net.wide.weight.name
        self._deep_name = net.deep_embed.weight.name
        self._vocab = int(net.wide.weight.shape[0])
        pd = net.collect_params()
        # one sentinel row at index `vocab`: padded bucket slots target it
        tables = {}
        dense = {}
        for n, p in pd.items():
            if p._data is None and p._deferred_init:
                p._finish_deferred_init()
            if p._data is None:
                raise ValueError(
                    "BucketedSparseTrainer: parameter %s has no shape "
                    "yet — run one forward pass first" % n)
            v = p.data()._data
            if n in (self._wide_name, self._deep_name):
                tables[n] = jnp.pad(v, ((0, 1), (0, 0)))
            else:
                dense[n] = v
        self._state = {
            "tables": tables,
            "dense": dense,
            "t": jnp.zeros((), jnp.int32),
        }
        if optimizer == "adam":
            self._state["m"] = jax.tree_util.tree_map(
                lambda w: jnp.zeros(w.shape, jnp.float32),
                {**tables, **dense})
            self._state["v"] = jax.tree_util.tree_map(
                lambda w: jnp.zeros(w.shape, jnp.float32),
                {**tables, **dense})
        elif optimizer != "sgd":
            raise ValueError("optimizer must be 'adam' or 'sgd'")
        self._mlp = functionalize(net.mlp, training=True)
        self._out = functionalize(net.out, training=True)
        self._mlp_names = set(net.mlp.collect_params())
        self._out_names = set(net.out.collect_params())
        # bucket policy: K = B·F (always safe, ZERO host syncs — built
        # around a per-step nunique D2H of ~100 ms on an earlier
        # setup; not re-measured on this chip) unless the caller passes `bucket_rows` for skewed
        # workloads (classic recsys: few hot features); then overflow
        # is counted ON DEVICE into the state and surfaced lazily via
        # `overflow_steps` — no step ever blocks on the host.
        self._bucket = int(bucket_rows) if bucket_rows else None
        self._state["overflow"] = jnp.zeros((), jnp.int32)
        # last finite loss, carried in-state: an overflowed (skipped)
        # step returns THIS instead of NaN (see step()'s contract)
        self._state["loss"] = jnp.zeros((), jnp.float32)
        self._steps = {}

    # ------------------------------------------------------------------
    def _lr_t(self, t):
        """Per-step learning rate with MXNet Adam's folded bias
        correction (optimizer.py Adam.update) — exact eager parity."""
        if self._opt != "adam":
            return self._lr
        tf = t.astype(jnp.float32)
        return self._lr * jnp.sqrt(1.0 - self._b2 ** tf) / \
            (1.0 - self._b1 ** tf)

    def _upd(self, w, g, m, v, lr):
        """One MXNet-semantics update; w may be rows or a dense leaf."""
        g = g.astype(jnp.float32) + self._wd * w.astype(jnp.float32)
        if self._opt == "sgd":
            return (w.astype(jnp.float32) - lr * g).astype(w.dtype), m, v
        nm = self._b1 * m + (1 - self._b1) * g
        nv = self._b2 * v + (1 - self._b2) * jnp.square(g)
        nw = w.astype(jnp.float32) - lr * nm / (jnp.sqrt(nv) + self._eps)
        return nw.astype(w.dtype), nm, nv

    def _make_step(self, K, B, F):
        wide_n, deep_n = self._wide_name, self._deep_name
        sentinel = self._vocab

        def step(state, idx, vals, y):
            tables, dense, t = state["tables"], state["dense"], state["t"]
            flat = idx.reshape(-1).astype(jnp.int32)
            uniq, inv = jnp.unique(flat, size=K, fill_value=sentinel,
                                   return_inverse=True)
            overflow = state["overflow"]
            ovf_now = None
            if K < B * F:
                # caller-provided bucket: a step whose true unique
                # count exceeds it has truncated/garbage inverse
                # indices — count it (no host block) and SKIP its
                # update below so one bad batch cannot poison training
                ovf_now = _nunique_fn(flat) > K
                overflow = overflow + ovf_now
            uniq = uniq.astype(jnp.int32)
            inv = inv.reshape(-1).astype(jnp.int32)
            gw = jnp.take(tables[wide_n], uniq, axis=0)      # (K, 1)
            gd = jnp.take(tables[deep_n], uniq, axis=0)      # (K, E)
            E = gd.shape[1]
            mlp_p = {n: dense[n] for n in self._mlp_names}
            out_p = {n: dense[n] for n in self._out_names}
            v3 = vals[..., None]

            def fwd(gw_, gd_, mlp_p_, out_p_):
                w_rows = jnp.take(gw_, inv, axis=0).reshape(B, F, 1)
                d_rows = jnp.take(gd_, inv, axis=0).reshape(B, F, E)
                wide_term = jnp.sum(w_rows * v3, axis=1)     # (B, 1)
                deep_in = (d_rows * v3).reshape(B, F * E)
                h, _ = self._mlp(mlp_p_, deep_in)
                o, _ = self._out(out_p_, h)
                logits = o + wide_term
                logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                          axis=-1)
                picked = jnp.take_along_axis(
                    logp, y[:, None].astype(jnp.int32), axis=1)
                return -jnp.mean(picked)

            loss, (g_gw, g_gd, g_mlp, g_out) = jax.value_and_grad(
                fwd, argnums=(0, 1, 2, 3))(gw, gd, mlp_p, out_p)

            t = t + 1
            lr = self._lr_t(t)
            new = {"tables": dict(tables), "dense": dict(dense), "t": t,
                   "overflow": overflow}
            if self._opt == "adam":
                new["m"] = dict(state["m"])
                new["v"] = dict(state["v"])
            # lazy row updates: only the K bucket rows are touched (the
            # sentinel row absorbs padded slots)
            for name, rows_g in ((wide_n, g_gw), (deep_n, g_gd)):
                w = tables[name]
                wr = jnp.take(w, uniq, axis=0)
                if self._opt == "adam":
                    mr = jnp.take(state["m"][name], uniq, axis=0)
                    vr = jnp.take(state["v"][name], uniq, axis=0)
                else:
                    mr = vr = None
                nw, nmr, nvr = self._upd(wr, rows_g, mr, vr, lr)
                new["tables"][name] = w.at[uniq].set(nw)
                if self._opt == "adam":
                    new["m"][name] = state["m"][name].at[uniq].set(nmr)
                    new["v"][name] = state["v"][name].at[uniq].set(nvr)
            # dense updates
            for name, g in (list(g_mlp.items()) + list(g_out.items())):
                if self._opt == "adam":
                    nw, nm, nv = self._upd(dense[name], g,
                                           state["m"][name],
                                           state["v"][name], lr)
                    new["m"][name], new["v"][name] = nm, nv
                else:
                    nw, _, _ = self._upd(dense[name], g, None, None, lr)
                new["dense"][name] = nw
            new["loss"] = loss.astype(jnp.float32)
            if ovf_now is not None:
                # overflowed step: keep the old state (the overflow
                # counter above is the only field that advances) —
                # including "loss", so the step returns the PREVIOUS
                # finite loss instead of NaN (the NaN-free contract on
                # step(); overflow_steps is the skip signal)
                keep = jax.tree_util.tree_map(
                    lambda old, nw_: jnp.where(ovf_now, old, nw_),
                    {k: state[k] for k in new if k != "overflow"},
                    {k: new[k] for k in new if k != "overflow"})
                keep["overflow"] = overflow
                new = keep
            return new, new["loss"]

        return jax.jit(step, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def step(self, indices, values, labels):
        """One jitted lazy-update step; returns the loss (device scalar).

        Loss contract (NaN-free): a step whose unique-row count
        overflows `bucket_rows` is SKIPPED on device — state untouched
        — and returns the PREVIOUS finite loss (0.0 if no step has
        succeeded yet), so naive per-step loss accumulation/averaging
        stays finite.  `overflow_steps` is the sole skip signal; check
        it at epoch boundaries (reading it is a device sync)."""
        idx = indices._data if isinstance(indices, NDArray) \
            else jnp.asarray(indices)
        vals = values._data if isinstance(values, NDArray) \
            else jnp.asarray(values)
        y = labels._data if isinstance(labels, NDArray) \
            else jnp.asarray(labels)
        B, F = idx.shape
        K = min(self._bucket, B * F) if self._bucket else B * F
        key = (K, B, F)
        if key not in self._steps:
            self._steps[key] = self._make_step(K, B, F)
        self._state, loss = self._steps[key](self._state, idx, vals, y)
        # the loss value is ALSO carried inside the (donated) state —
        # hand the caller a detached copy so the next step's state
        # donation can never invalidate a held loss array
        return NDArray(jnp.copy(loss))

    @property
    def bucket_keys(self):
        return sorted(self._steps)

    @property
    def overflow_steps(self):
        """Steps whose true unique-row count exceeded `bucket_rows`.
        Those steps were SKIPPED (state untouched, previous finite
        loss returned) — raise the bucket if this is nonzero.  Reading
        this is a device sync; check at epoch boundaries."""
        return int(_np.asarray(self._state["overflow"]))

    def sync_to_net(self):
        """Write trained values back into the Gluon block (drops the
        sentinel rows)."""
        from ..parallel.functional import load_params
        merged = dict(self._state["dense"])
        for n, v in self._state["tables"].items():
            merged[n] = v[:-1]
        load_params(self._net, merged)

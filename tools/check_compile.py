"""check_compile — CI gate for the compile loop (ISSUE 18).

One claim, measured in fresh child processes (the check_scaling
discipline: best-of-k trials, all-errored SKIP rc 0, gate_report
artifact):

**Layer-stacking** (compile/stacking.py): ONE lax.scan executable
beats N structurally-identical per-layer executables on cold compile
wall AND per-forward dispatch, with the bit-parity oracle green and
the executable count reduced N -> 1.

Inconclusive (never a FAIL): single-core hosts (dispatch timing is
meaningless under full serialization — SKIP up front).  Wired as a
slow+compile test in tests/python/unittest/test_compile.py so tier-1
skips it but CI can run it.

    python tools/check_compile.py
    python tools/check_compile.py --trials 3 --layers 8 --dim 256
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_CHILD_MARK = "_CHECK_COMPILE_CHILD"


def _child_stack(layers, dim):
    """Stacking child: measure N per-layer executables vs one scanned
    one on a dense tanh stack; print one JSON line."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    from incubator_mxnet_tpu.compile import stacking

    def layer(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    rng = np.random.RandomState(7)
    params = [{"w": jnp.asarray(rng.randn(dim, dim)
                                .astype(np.float32) * 0.05),
               "b": jnp.zeros((dim,), jnp.float32)}
              for _ in range(layers)]
    x = jnp.ones((8, dim), jnp.float32)
    print(json.dumps(stacking.measure(layer, params, x, calls=20,
                                      label="check_compile")))


def _run_child(args_list, timeout_s=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env[_CHILD_MARK] = "1"
    env.setdefault("MXNET_BLACKBOX_DIR", "/tmp")
    cmd = [sys.executable, os.path.abspath(__file__)] + args_list
    res = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=timeout_s, env=env, cwd=_ROOT)
    for line in reversed((res.stdout or "").strip().splitlines()
                         or [""]):
        if line.startswith("{"):
            return json.loads(line)
    tail = (res.stderr or res.stdout or "").strip().splitlines()
    raise RuntimeError("gate child failed (rc=%d): %s"
                       % (res.returncode,
                          tail[-1] if tail else "no output"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--dispatch-slack", type=float, default=1.05,
                    help="stacked dispatch must be <= unstacked * "
                    "this (timing noise headroom; the compile-wall "
                    "bar has none)")
    args = ap.parse_args(argv)

    from gate_report import write_report
    params = {"trials": args.trials, "layers": args.layers,
              "dim": args.dim, "dispatch_slack": args.dispatch_slack}
    cores = os.cpu_count() or 1
    if cores < 2:
        print("SKIP: single-core host (dispatch timing under full "
              "serialization judges the scheduler, not the stacking)")
        write_report("check_compile", "skip", [], rc=0, params=params,
                     extra={"skip_reason": "single-core host"})
        return 0

    verdicts = []
    trial_rows = []
    for trial in range(args.trials):
        try:
            stack = _run_child(
                ["--child", "stack", str(args.layers), str(args.dim)])
        except Exception as e:          # noqa: BLE001
            print("trial %d: ERROR %s" % (trial, e))
            verdicts.append(None)
            trial_rows.append({"trial": trial, "verdict": "error",
                               "error": str(e)[:200]})
            continue

        ok = bool(stack["parity_ok"]
                  and stack["executables_stacked"]
                  < stack["executables_unstacked"]
                  and stack["compile_wall_stacked_s"]
                  < stack["compile_wall_unstacked_s"]
                  and stack["dispatch_stacked_us"]
                  <= stack["dispatch_unstacked_us"]
                  * args.dispatch_slack)
        verdicts.append(ok)
        trial_rows.append({"trial": trial, "stack": stack,
                           "verdict": "pass" if ok else "fail"})
        print("trial %d: stack compile %.3fs->%.3fs dispatch "
              "%dus->%dus exec %d->%d parity=%s -> %s"
              % (trial, stack["compile_wall_unstacked_s"],
                 stack["compile_wall_stacked_s"],
                 stack["dispatch_unstacked_us"],
                 stack["dispatch_stacked_us"],
                 stack["executables_unstacked"],
                 stack["executables_stacked"], stack["parity_ok"],
                 "PASS" if ok else "fail"))
        if ok:
            print("PASS: one scanned executable beats %d per-layer "
                  "ones" % args.layers)
            write_report("check_compile", "pass", trial_rows, rc=0,
                         params=params)
            return 0
    if all(v is None for v in verdicts):
        print("SKIP: no trial produced a usable measurement on this "
              "host")
        write_report("check_compile", "skip", trial_rows, rc=0,
                     params=params,
                     extra={"skip_reason": "no usable measurement"})
        return 0
    print("FAIL: the compile loop did not demonstrate its wins in %d "
          "trials" % args.trials)
    write_report("check_compile", "fail", trial_rows, rc=1,
                 params=params)
    return 1


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        if sys.argv[2] == "stack":
            _child_stack(int(sys.argv[3]), int(sys.argv[4]))
        sys.exit(0)
    sys.exit(main())

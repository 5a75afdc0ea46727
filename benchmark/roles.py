"""Which executable is which, learned during warm-up.

The program names every executable `jit__traced(<fingerprint>)`, so a
name says nothing.  A driver runs a short **probe** under the profiler
in which it knows how often each role runs, and the roles are read off
the run counts — never off sizes or times:

- serving: R requests of R distinct prompt buckets, each of `k` new
  tokens, into an idle engine: every `prefill` module runs once, `join`
  runs R times, `decode_step` runs k times (R and k chosen distinct and
  above 1);
- training: k steps: the step module runs k times.

`by_counts` returns {role: [module names]} and raises where the counts
do not single a role out, so a changed program is noticed, not misread.
"""
from __future__ import annotations


def by_counts(counts, expect, prefix=""):
    """`counts` {module: runs}; `expect` {role: runs}.  Roles whose count
    is shared by several modules are returned as lists; a role with no
    module raises.  `prefix` (the configuration's `executable_prefix`)
    keeps JAX's own small helper programs, which run once a step too, out
    of the count."""
    out = {}
    for role, n in expect.items():
        mods = sorted(m for m, c in counts.items()
                      if c == n and m.startswith(prefix))
        if not mods:
            raise ValueError("no module ran %d times for role %r: %s"
                             % (n, role, counts))
        out[role] = mods
    return out

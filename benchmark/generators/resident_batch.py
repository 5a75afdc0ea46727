"""The one generator of training jobs.  A job is a data file under
`traffic/`: batch, shapes and what counts as an item.  `--seed` draws
the one resident batch; every row differs.

`generate` returns host arrays; the builder places them (on one device,
or sharded over the mesh).
"""
from __future__ import annotations

import numpy as np


def generate(traffic, config, seed, seconds):
    rng = np.random.default_rng([int(seed), 0xBA7C])
    B = int(traffic["batch"])
    out = {"batch": B}
    if "seq_len" in traffic:                     # token sequences + MLM
        T, K = int(traffic["seq_len"]), int(traffic["mlm_positions"])
        V = int(config["vocab_size"])
        lo = int(traffic.get("first_token_id", 0))
        out["tokens"] = rng.integers(lo, V, size=(B, T)).astype(np.int32)
        # K distinct positions in each row, as flat indices into (B*T)
        pos = np.stack([np.sort(rng.permutation(T)[:K]) for _ in range(B)])
        out["positions"] = (pos + T * np.arange(B)[:, None]).reshape(-1) \
            .astype(np.int32)
        out["labels"] = rng.integers(lo, V, size=B * K).astype(np.int32)
        out["items_per_step"] = B * T
    else:                                        # images + classes
        side, C = int(traffic["image_side"]), int(config["num_classes"])
        out["images"] = rng.standard_normal((B, 3, side, side), np.float32)
        out["labels"] = rng.integers(0, C, size=B).astype(np.int32)
        out["items_per_step"] = B
    return out

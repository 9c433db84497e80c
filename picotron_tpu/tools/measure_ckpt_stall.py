"""Measure the training-step stall caused by a checkpoint save.

The round-3 VERDICT flagged synchronous orbax saves (weak item 6): at
7B-proxy scale every ``save_frequency`` boundary stalled training for the
full serialization. ``CheckpointManager`` now defaults to async saves —
``save()`` returns after the device-to-host copy and the disk write happens
in a background thread. This tool measures both modes on the same tree:

    python -m picotron_tpu.tools.measure_ckpt_stall [n_params_millions]

Prints one JSON line: {"n_params", "sync_save_s", "async_return_s",
"async_drain_s", "stall_reduction"} where *_return_s is the time train()
is blocked and drain is the background completion (paid only at exit).
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time


def measure(n_million: int = 200) -> dict:
    import jax
    import jax.numpy as jnp

    from picotron_tpu.checkpoint import CheckpointManager

    n = n_million * 1_000_000
    # a handful of large leaves, like a real layer-stacked param tree
    leaf = n // 8
    params = {f"w{i}": jnp.arange(leaf, dtype=jnp.float32) / leaf
              for i in range(8)}
    opt_state = {f"m{i}": jnp.zeros(leaf // 4, jnp.float32) for i in range(8)}
    jax.block_until_ready(params)

    out = {"n_params": n}
    for mode in ("sync", "async"):
        d = tempfile.mkdtemp(prefix=f"ckpt_stall_{mode}_")
        try:
            mgr = CheckpointManager(d, async_save=(mode == "async"))
            t0 = time.perf_counter()
            mgr.save(1, params, opt_state, trained_tokens=0)
            t_return = time.perf_counter() - t0
            mgr.wait_until_finished()
            t_drain = time.perf_counter() - t0 - t_return
            mgr.close()
        finally:
            shutil.rmtree(d, ignore_errors=True)
        if mode == "sync":
            out["sync_save_s"] = round(t_return, 3)
        else:
            out["async_return_s"] = round(t_return, 3)
            out["async_drain_s"] = round(t_drain, 3)
    out["stall_reduction"] = round(
        out["sync_save_s"] / max(out["async_return_s"], 1e-9), 1)
    return out


if __name__ == "__main__":
    nm = int(sys.argv[1]) if len(sys.argv) > 1 else 200
    print(json.dumps(measure(nm)))

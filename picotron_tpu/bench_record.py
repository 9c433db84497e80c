"""Names of the one-line JSON records the bench scripts print.

The benches (`bench.py`, `bench_7b.py`, `bench_decode.py`) print exactly
one line starting ``{"metric"`` per run, naming the device it was measured
on. The metric names live here so a rename cannot desynchronize the three
scripts (each touches jax at module top; this module does not).
"""

from __future__ import annotations

# the ON-TPU metric each bench script publishes, keyed by script name
BENCH_METRICS = {
    "bench": "smollm_1.7b_mfu_1chip",
    "bench_7b": "llama2_7b_proxy_mfu_1chip",
    "bench_decode": "smollm_1.7b_decode_toks_s_chip",
}

"""K/V's share of the bytes the two mixers' caches cost a decode step, from
the counters' deltas between the window's two scrapes: keys read
(``picotron_attn_keys_read_total``) x a token's K and V in one layer
(``opcount_falcon.layer_kv_bytes_per_token``, 2,048 B) against state updates
(``picotron_ssm_state_updates_total``) x a slot's float32 state in one layer
read and written (2 x ``opcount_falcon.layer_state_bytes``, 8.39 MB): 100 x
K/V / (K/V + state). It reads 50 where the live slots hold 4,096 tokens each,
less below and more above: which of the two kernels a step's cache traffic
leans on. The conv tail (30 KB a slot and layer) is left out. A program
without the counters reads as nothing."""

from benchmarks import opcount_falcon, phases


def read(run):
    if "metrics_after" not in run:
        return None
    keys = phases.delta(run, "picotron_attn_keys_read_total")
    updates = phases.delta(run, "picotron_ssm_state_updates_total")
    if keys <= 0 or updates <= 0:
        return None
    config = run["config"]
    kv = keys * opcount_falcon.layer_kv_bytes_per_token(config)
    state = 2 * updates * opcount_falcon.layer_state_bytes(config)
    return 100.0 * kv / (kv + state)

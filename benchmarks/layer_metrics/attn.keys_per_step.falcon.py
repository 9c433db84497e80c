"""Live keys a decode step's attend covered, a layer: delta
``picotron_attn_keys_read_total`` / delta ``picotron_attn_layer_steps_total``
between the window's two scrapes: the sum of the live slots' contexts, the
fresh row's in (64 slots at 2,500 tokens read 160,000). It is the K/V a
layer's ``flash_decode_attention`` call must read, in tokens; over
``ssm.state_updates_per_step`` it is the mean context of a live slot. A
program without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_attn_layer_steps_total")
    if layer_steps <= 0:
        return None
    return phases.delta(run, "picotron_attn_keys_read_total") / layer_steps

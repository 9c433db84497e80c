"""Live slots whose delta-rule state a decode step advanced, a KDA layer:
delta ``picotron_kda_state_updates_total`` / delta
``picotron_kda_layer_steps_total`` between the window's two scrapes. With
every slot streaming it reads the slot count (64); slots that wait for an
admission, or ride a block out of budget, bring it down, and a parked slot
advanced would read high: the batch the state's 2 x 4.2 MB a slot and layer
are moved for. A program without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_kda_layer_steps_total")
    if layer_steps <= 0:
        return None
    return phases.delta(run, "picotron_kda_state_updates_total") / layer_steps

"""Live slots whose lightning state a decode step advanced, a lightning
layer: delta ``picotron_lightning_state_updates_total`` / delta
``picotron_lightning_layer_steps_total`` between the window's two scrapes.
With every slot streaming it reads the slot count (8); a slot that rides a
block out of budget brings it down: the batch the state's 2 x 18.9 MB a slot
are moved for. A program without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_lightning_layer_steps_total")
    if layer_steps <= 0:
        return None
    return phases.delta(
        run, "picotron_lightning_state_updates_total") / layer_steps

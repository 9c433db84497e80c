"""Token-expert assignments that landed on an expert held here, per program
step (a decode step, or a prefill bucket or chunk), expert layer and held
expert: delta ``picotron_moe_assignments_total`` / delta
``picotron_moe_layer_steps_total`` between the window's two scrapes, over
``n_routed_experts``. A decode step of 32 live slots with 8 experts a token
and 8 of 256 held gives 32 x 8 / 256 = 1.0, where the deployment's 32 chips'
slots would give 32. A router that drops tokens, or stops sending any here,
moves it. A program without the block's counters
(``picotron_swa_layer_steps_total``) reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_moe_layer_steps_total")
    if layer_steps <= 0 \
            or phases.delta(run, "picotron_swa_layer_steps_total") <= 0:
        return None
    return (phases.delta(run, "picotron_moe_assignments_total") / layer_steps
            / run["config"]["n_routed_experts"])

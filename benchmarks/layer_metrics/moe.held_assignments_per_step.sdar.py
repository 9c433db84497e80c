"""Token-expert assignments that landed on an expert held here, per program
step (a forward of a round of blocks, or a prefill bucket or chunk), layer
and held expert: delta ``picotron_moe_assignments_total`` / delta
``picotron_moe_layer_steps_total`` between the window's two scrapes, over
``num_experts``. A forward of 32 live slots x 4 rows with 8 experts a token
and 16 of 128 held gives 128 x 8 / 128 = 8, where the deployment's eight
chips' slots would give 64. A program without the block's counters
(``picotron_diffusion_forwards_total``) reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_moe_layer_steps_total")
    if layer_steps <= 0 \
            or phases.delta(run, "picotron_diffusion_forwards_total") <= 0:
        return None
    return (phases.delta(run, "picotron_moe_assignments_total") / layer_steps
            / run["config"]["num_experts"])

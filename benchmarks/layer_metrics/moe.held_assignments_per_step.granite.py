"""Token-expert assignments that landed on an expert held here, per program
step (a decode step, or a prefill bucket or chunk), layer and held expert:
``moe.held_assignments_per_step``'s quotient, delta
``picotron_moe_assignments_total`` / delta ``picotron_moe_layer_steps_total``
between the window's two scrapes, over ``num_local_experts``. A decode step
of 64 live slots with 10 experts a token and 36 of 72 held gives 64 x 10 / 72
= 8.9, half the deployment's 17.8 (the second chip's 64 slots are absent);
a prefill chunk of 512 tokens gives 71, so with admissions in the window it
reads between the two. A router that drops tokens, or stops sending any
here, moves it. A program without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_moe_layer_steps_total")
    if layer_steps <= 0:
        return None
    return (phases.delta(run, "picotron_moe_assignments_total") / layer_steps
            / run["config"]["num_local_experts"])

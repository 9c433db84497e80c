"""Token-expert assignments that landed on an expert held here, per decode
or prefill step, expert layer and held expert: delta
``picotron_moe_assignments_total`` / delta ``picotron_moe_layer_steps_total``
/ ``n_routed_experts`` between the window's two scrapes. With 8 slots, 8
experts a token and 8 of 256 experts held it reads 8 x 8 / 256 = 0.25; the
deployment's 32 x 8 slots would give a held expert 8. A router that drops
tokens, or stops sending any here, moves it. A program without the counters
reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_moe_layer_steps_total")
    if layer_steps <= 0:
        return None
    return (phases.delta(run, "picotron_moe_assignments_total") / layer_steps
            / run["config"]["n_routed_experts"])

"""Held experts a step's rows chose, of those held: 100 x delta
``picotron_moe_experts_hit_total`` / (delta ``picotron_moe_layer_steps_total``
x ``n_routed_experts``) between the window's two scrapes. With 128 slots, 22
experts a token and 128 of 512 held a row misses a held expert with
probability 490 / 512, all 128 rows with 0.957^128 = 0.4 %: it reads ~99.6.
It is the share of the held experts' bytes the router asked for: the pass
below the ridge reads every held expert whatever it reads here, so a cut
that fills fewer slots, or a router that collapses, shows as bytes moved for
nothing. A program without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_moe_layer_steps_total")
    if layer_steps <= 0:
        return None
    return (100.0 * phases.delta(run, "picotron_moe_experts_hit_total")
            / (layer_steps * run["config"]["n_routed_experts"]))

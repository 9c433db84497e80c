"""Held experts a step's rows chose, of those held:
``moe.experts_hit_pct.nemotron``'s reader over this configuration's
``n_routed_experts``. With 64 slots, 8 experts a token and 20 of 320 held a
row misses a held expert with probability 312 / 320, all 64 rows with
0.975^64 = 20 %: it reads ~80 where the rows route apart. It is the share of
the held experts' bytes the router asked for: the loop below the ridge reads
every held expert whatever it reads here (the deployment's 16 x 64 rows reach
every one), so a cut that fills fewer slots, or a router that collapses,
shows as bytes moved for nothing."""

from benchmarks import common

read = common.load_file("layer_metrics", "moe.experts_hit_pct.nemotron").read

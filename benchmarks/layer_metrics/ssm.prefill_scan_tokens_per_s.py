"""Prompt tokens through the prefill's chunked scan, a second of the
window: delta ``picotron_ssm_tokens_scanned_total`` between the window's
two scrapes / the Mamba layers held (each counts every live token once) /
the window's seconds. Pad rows of a bucket or a chunk are not counted. What
the admissions ask of the scan, beside the decode steps; a program without
the counter reads as nothing."""

from benchmarks import opcount_granite, phases


def read(run):
    if "metrics_after" not in run or not run.get("window_s"):
        return None
    scanned = phases.delta(run, "picotron_ssm_tokens_scanned_total")
    n_mamba, _ = opcount_granite.kind_counts(run["config"])
    if scanned <= 0 or not n_mamba:
        return None
    return scanned / n_mamba / run["window_s"]

"""``serve_out_tokens_per_s`` (output tokens the clients received inside the
window, over its seconds: the same reader) for the closed loop over long
documents, under a bound of its own. Which round admits a client's next
request, behind whose prefills, differs from run to run (one request's time
to its first token differs by 0.12-0.5 s between two runs of one seed), so
two runs of one seed differ there by 1-2 % and the cell's runs spread by
1.5-2 %, three times what ``smollm-1.7b.serve-batch``'s do, whose bound stays
as tight as it was (PERF.md)."""

from benchmarks import common


def read(run):
    return common.load_file("end_to_end", "serve_out_tokens_per_s").read(run)

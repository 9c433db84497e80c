"""The load generator: the schedule from a seed is reproducible, every seed
holds the same work, and lateness is reported."""

import hashlib
import json
import os
import threading
from collections import Counter
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from benchmarks import loadgen

OPEN = {"loop": "open", "rate_rps": 40, "shape_seed": 7,
        "prompt_len": {"dist": "log_uniform", "min": 4, "max": 64},
        "output_len": {"dist": "uniform", "min": 2, "max": 5},
        "drain_seconds": 5}
CLOSED = dict(OPEN, loop="closed", clients=3, shapes=6)
DOCS = dict(CLOSED, shapes=40,
            prompt_len={"dist": "log_uniform", "min": 34, "max": 70},
            documents={"asks": 3,
                       "doc_len": {"dist": "log_uniform", "min": 30,
                                   "max": 60},
                       "question_len": {"dist": "uniform", "min": 4,
                                        "max": 10},
                       "reask_arrivals": {"min": 3, "max": 9}})
TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")

# sha256 of the parent's schedule (PR 27, taken before build_schedule learned
# documents): prompts' tokens, lengths and dues, at the cell's span and vocab
PARENT_SCHEDULES = {
    ("chat-open", 32768, 1): "aecb8cf740b37251",
    ("chat-open", 32768, 77): "30c03e2f43e18f82",
    ("chat-open", 32768, 3000000001): "c51e4816486bd328",
    ("batch-closed", 49152, 1): "cac28099bb8ee0fd",
    ("batch-closed", 49152, 77): "3760a08746934bd2",
    ("batch-closed", 49152, 3000000001): "6f4756340f729291",
}


def shape(s):
    return [(len(r["prompt"]), r["max_new_tokens"]) for r in s]


def gaps(s):
    return sorted(round(y["due"] - x["due"], 9) for x, y in zip(s, s[1:]))


def test_schedule_is_reproducible_and_same_work_for_every_seed():
    a = loadgen.build_schedule(OPEN, 3000000001, 2.0, 100)
    b = loadgen.build_schedule(OPEN, 3000000001, 2.0, 100)
    c = loadgen.build_schedule(OPEN, 12, 2.0, 100)
    assert a == b
    assert len(a) == len(c) == 80
    assert shape(a) != shape(c) and sorted(shape(a)) == sorted(shape(c))
    assert a[0]["due"] == 0.0 and a[-1]["due"] < 2.0
    # the same multiset of gaps, but for the one that falls off the end
    assert len(set(gaps(a)) & set(gaps(c))) >= 70
    assert all(1 <= t < 100 for r in a for t in r["prompt"])


@pytest.mark.parametrize("mix,vocab,seed", sorted(PARENT_SCHEDULES))
def test_a_mix_without_documents_keeps_the_parents_schedule(mix, vocab, seed):
    with open(os.path.join(TRAFFIC, mix + ".json")) as f:
        traffic = json.load(f)
    s = loadgen.build_schedule(traffic, seed,
                               30 + traffic["lead_in_seconds"], vocab)
    digest = hashlib.sha256(json.dumps(s, sort_keys=True).encode())
    assert digest.hexdigest()[:16] == PARENT_SCHEDULES[mix, vocab, seed]


def test_documents_are_asked_again_with_their_leading_tokens():
    a = loadgen.build_schedule(DOCS, 3000000001, 2.0, 100)
    c = loadgen.build_schedule(DOCS, 12, 2.0, 100)
    assert a == loadgen.build_schedule(DOCS, 3000000001, 2.0, 100)
    n = 40
    assert len(a) == len(c) == n and all(r["due"] is None for r in a)
    by_doc = {}
    for pos, r in enumerate(a):
        by_doc.setdefault(r["doc"], []).append((pos, r))
    lo, hi = 3, 9
    for doc, asks in by_doc.items():
        assert [r["ask"] for _, r in asks] == list(range(len(asks))) \
            and len(asks) <= 3
        # consecutive asks of a document lie min..max requests apart
        assert all(lo <= q - p <= hi
                   for (p, _), (q, _) in zip(asks, asks[1:]))
        # exactly doc_len leading tokens are shared: the next one differs
        prompts = [r["prompt"] for _, r in asks]
        if len(prompts) == 1:
            continue
        shared = os.path.commonprefix(prompts)
        assert 30 <= len(shared) <= 60
        assert len({tuple(p[:len(shared) + 1]) for p in prompts}) \
            == len(prompts)
        assert all(4 <= len(p) - len(shared) <= 10 for p in prompts)
    # most documents are whole; the last ones are cut off by the end
    assert Counter(len(v) for v in by_doc.values())[3] >= n // 3 - 6
    assert all(34 <= len(r["prompt"]) <= 70 for r in a)
    # no prompt comes twice
    assert len({tuple(r["prompt"]) for r in a}) == n
    # another seed: the plan and the documents' lengths are shape_seed's
    assert [(r["doc"], r["ask"]) for r in a] == \
        [(r["doc"], r["ask"]) for r in c]
    assert a[0]["prompt"] != c[0]["prompt"]
    assert shape(a) != shape(c)


def test_every_block_of_a_document_mix_holds_the_same_work_for_every_seed():
    mix = dict(DOCS, shapes=48, clients=4,
               output_len={"dist": "uniform", "min": 16, "max": 63})
    a = loadgen.build_schedule(mix, 3000000001, 2.0, 100)
    c = loadgen.build_schedule(mix, 12, 2.0, 100)
    for i in range(0, 48, 4):
        outs = sorted(r["max_new_tokens"] for r in a[i:i + 4])
        # one answer from each quarter of 16..63, in the seed's order
        assert [(o - 16) // 12 for o in outs] == [0, 1, 2, 3]
        assert outs == sorted(r["max_new_tokens"] for r in c[i:i + 4])
        assert sum(len(r["prompt"]) for r in a[i:i + 4]) == \
            sum(len(r["prompt"]) for r in c[i:i + 4])
    assert [r["max_new_tokens"] for r in a] != \
        [r["max_new_tokens"] for r in c]


def test_documents_in_an_open_loop_are_refused_by_name():
    with pytest.raises(ValueError, match="documents with loop: open"):
        loadgen.build_schedule(dict(DOCS, loop="open"), 1, 2.0, 100)


def test_documents_outside_the_prompt_envelope_are_refused():
    bad = dict(DOCS, prompt_len={"dist": "log_uniform", "min": 34, "max": 60})
    with pytest.raises(ValueError, match="envelope"):
        loadgen.build_schedule(bad, 1, 2.0, 100)


class _Stub(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def do_POST(self):
        spec = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        n = spec["max_new_tokens"]
        self.send_response(200)
        self.end_headers()
        for i in range(n):
            self.wfile.write((json.dumps({"event": "token", "token": i})
                              + "\n").encode())
            self.wfile.flush()
        self.wfile.write((json.dumps({
            "event": "done", "tokens": list(range(n)),
            "finish_reason": "length", "ttft_s": 0.001,
            "queue_wait_s": 0.0}) + "\n").encode())


def _with_stub(fn):
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    try:
        return fn(httpd.server_address[1])
    finally:
        httpd.shutdown()
        th.join(5)
        httpd.server_close()


def test_open_loop_reports_lateness_and_every_request():
    sched = loadgen.build_schedule(OPEN, 5, 0.5, 100)
    out = _with_stub(lambda port: loadgen.run_load(port, OPEN, sched, 0.5))
    assert out["sent"] == len(sched) == 20 and out["unfinished"] == 0
    assert all(loadgen.request_ok(r) for r in out["requests"])
    late = [r["sent"] - r["due"] for r in out["requests"]]
    assert all(0 <= x < 0.5 for x in late)
    assert all("doc" not in r for r in out["requests"])


def test_a_request_of_a_document_mix_names_its_document_and_ask():
    sched = loadgen.build_schedule(DOCS, 5, 0.5, 100)
    out = _with_stub(lambda port: loadgen.run_load(port, DOCS, sched, 0.2))
    assert out["sent"] > 3  # the clients took the plan in order
    assert [(r["doc"], r["ask"]) for r in out["requests"]] == \
        [(sched[r["i"] % 40]["doc"], sched[r["i"] % 40]["ask"])
         for r in out["requests"]]
    assert all(loadgen.request_ok(r) for r in out["requests"])


def test_closed_loop_keeps_clients_busy_until_the_end():
    sched = loadgen.build_schedule(CLOSED, 5, 0.3, 100)
    assert len(sched) == 6
    out = _with_stub(lambda port: loadgen.run_load(port, CLOSED, sched, 0.3))
    assert out["sent"] > 6 and out["unfinished"] == 0  # the shapes cycle
    assert all(loadgen.request_ok(r) for r in out["requests"])


def test_lead_in_comes_before_the_window():
    mix = dict(OPEN, lead_in_seconds=0.2)
    sched = loadgen.build_schedule(mix, 5, 0.5, 100)
    assert len(sched) == 20  # the caller passes lead-in + window as span
    out = _with_stub(lambda port: loadgen.run_load(port, mix, sched, 0.3))
    first = min(r["due"] for r in out["requests"])
    assert out["t0"] - first == __import__("pytest").approx(0.2, abs=0.02)
    assert out["seconds"] == 0.3


def test_a_refused_request_is_not_ok():
    rec = loadgen.one_request(1, [1, 2, 3], 4, timeout=1)  # nothing listens
    assert rec["error"] and not loadgen.request_ok(rec)

"""The load generator: the schedule from a seed is reproducible, every seed
holds the same work, and lateness is reported."""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from benchmarks import loadgen

OPEN = {"loop": "open", "rate_rps": 40, "shape_seed": 7,
        "prompt_len": {"dist": "log_uniform", "min": 4, "max": 64},
        "output_len": {"dist": "uniform", "min": 2, "max": 5},
        "drain_seconds": 5}
CLOSED = dict(OPEN, loop="closed", clients=3, shapes=6)


def shape(s):
    return [(len(r["prompt"]), r["max_new_tokens"]) for r in s]


def test_schedule_is_reproducible_and_same_work_for_every_seed():
    a = loadgen.build_schedule(OPEN, 3000000001, 2.0, 100)
    b = loadgen.build_schedule(OPEN, 3000000001, 2.0, 100)
    c = loadgen.build_schedule(OPEN, 12, 2.0, 100)
    assert a == b
    assert len(a) == len(c) == 80
    assert shape(a) != shape(c) and sorted(shape(a)) == sorted(shape(c))
    gaps = lambda s: sorted(round(y["due"] - x["due"], 9)
                            for x, y in zip(s, s[1:]))
    assert a[0]["due"] == 0.0 and a[-1]["due"] < 2.0
    # the same multiset of gaps, but for the one that falls off the end
    assert len(set(gaps(a)) & set(gaps(c))) >= 70
    assert all(1 <= t < 100 for r in a for t in r["prompt"])


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

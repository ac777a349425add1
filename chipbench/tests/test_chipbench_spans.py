"""The readers of the program's spans: hand counts on synthetic traces,
and a traced whole run of each cell on the CPU at a small size."""
import json
import time
from pathlib import Path

import pytest

from chipbench import harness, program_spans, trace
from chipbench.tests.small import small_config, small_mix

BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
SPAN_METRICS = [m for m in BENCH["per_layer"] if m["source"] == "program_span"]
NEW = {"read_ms.cold", "ring_wait_ms.cold", "put_ms.cold", "resolve_ms.cold",
       "dispatch_ms.warm", "node_ms.warm"}
MS = 1_000_000  # ns


def _run(host, requests, lo=0, hi=1000 * MS):
    t = trace.Trace(device=[], host=[("main", trace.WINDOW_SPAN, lo, hi), *host],
                    lo=lo, hi=hi)
    return harness.Run(cell={}, config={}, mix={}, requests=requests, window_s=1.0,
                       setup_s=0.0, flops_per_request=0.0, device_kind="TPU v5 lite",
                       trace=t)


def _req(s, e):
    return ("main", program_spans.REQUEST_SPAN, s * MS, e * MS)


def _ev(name, s, e, thread="reader"):
    return (thread, name, s * MS, e * MS)


COLD = [harness.Request(0, 0, 0.1, cold=True), harness.Request(1, 0, 0.1, cold=True)]


def test_union_of_overlapping_spans_clipped_to_each_request():
    host = [_req(0, 100), _req(200, 300),
            # request 1: 10-50 (two overlapping spans) and 90-100 of one
            # straddling its end: 50 ms
            _ev("spice.read", 10, 30), _ev("spice.read", 20, 50),
            _ev("spice.read", 90, 120),
            # request 2: 200-210 of one straddling its start, 250-260: 20 ms
            _ev("spice.read", 150, 210), _ev("spice.read", 250, 260)]
    run = _run(host, COLD)
    assert program_spans.span_ms(run, "cold", ["spice.read"]) == pytest.approx(35.0)
    assert harness.reader("read_ms.cold")(run) == pytest.approx(35.0)


def test_only_requests_of_the_kind_count():
    reqs = [harness.Request(0, 0, 0.1, cold=True),
            harness.Request(1, 0, 0.1, cold=True, joined=True),
            harness.Request(0, 1, 0.1, error="boom"),
            harness.Request(1, 1, 0.1, cold=True)]
    host = [_req(0, 100), _req(100, 200), _req(200, 300), _req(300, 400),
            _ev("serve.resolve", 0, 10, "main"), _ev("serve.resolve", 100, 190, "main"),
            _ev("serve.resolve", 200, 290, "main"), _ev("serve.resolve", 300, 330, "main")]
    run = _run(host, reqs)
    assert harness.reader("resolve_ms.cold")(run) == pytest.approx(20.0)  # of 10, 30
    assert program_spans.span_ms(run, "warm", ["serve.resolve"]) is None


def test_names_are_joined_into_one_union():
    host = [_req(0, 100), _ev("spice.read", 0, 40), _ev("spice.ring_wait", 30, 60)]
    run = _run(host, COLD[:1])
    assert program_spans.span_ms(run, "cold", ["spice.read", "spice.ring_wait"]) == \
        pytest.approx(60.0)
    assert harness.reader("ring_wait_ms.cold")(run) == pytest.approx(30.0)


def test_node_time_is_the_request_less_generation():
    warm = [harness.Request(0, 0, 0.01), harness.Request(1, 0, 0.01),
            harness.Request(0, 1, 0.01)]
    host = [_req(0, 10), _req(20, 30), _req(40, 50),
            _ev("serve.generate", 2, 9, "worker"),
            # two spans that overlap, and one past the request's end
            _ev("serve.generate", 21, 25, "worker"), _ev("serve.generate", 24, 29, "worker"),
            _ev("serve.generate", 49, 60, "worker"),
            _ev("serve.dispatch", 3, 4, "worker"), _ev("serve.dispatch", 5, 7, "worker"),
            _ev("serve.dispatch", 49, 51, "worker")]
    run = _run(host, warm)
    # 10 - 7, 10 - 8, 10 - 1
    assert harness.reader("node_ms.warm")(run) == pytest.approx(3.0)
    # 3, 0 (no dispatch span), 1
    assert harness.reader("dispatch_ms.warm")(run) == pytest.approx(1.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_no_such_span_reads_none(name):
    kind = name.split(".")[1]
    reqs = [harness.Request(0, 0, 0.1, cold=kind == "cold")]
    # the harness's own spans and a runtime event, as from a program that
    # records none of its own
    run = _run([_req(0, 100), _ev("DevicePut", 10, 20)], reqs)
    assert harness.reader(name)(run) is None
    run.trace = None
    assert harness.reader(name)(run) is None


def test_requests_that_do_not_pair_with_their_spans_read_none():
    host = [_req(0, 100), _ev("spice.upload.put", 10, 20, "uploader")]
    assert harness.reader("put_ms.cold")(_run(host, COLD[:1])) == pytest.approx(10.0)
    assert harness.reader("put_ms.cold")(_run(host, COLD)) is None


def test_new_metrics_read_program_spans_of_their_cells():
    assert {m["name"] for m in SPAN_METRICS} >= NEW
    for m in SPAN_METRICS:
        if m["name"] in NEW:
            kind = m["name"].split(".")[1]
            assert m["workloads"] == [f"qwen1.5-0.5b.{kind}"]
            assert m["moves"] == f"{kind}_ttft_p50_ms"


@pytest.mark.parametrize("kind", ["cold", "warm"])
def test_traced_run_reports_the_program_spans(kind):
    # a traced small run of the cell, reading the spans' metrics and the
    # cell's median time to first token beside them
    cell = f"qwen1.5-0.5b.{kind}"
    p50 = {"name": f"{kind}_ttft_p50_ms", "unit": "ms", "moves": f"{kind}_ttft_p50_ms",
           "workloads": [cell]}
    bench = dict(BENCH, per_layer=[*SPAN_METRICS, p50])
    mix = small_mix(harness.load_json(harness.HERE / "traffic" / f"{kind}.json"))
    out = harness.run_cell(cell, 2**31 + 19, 1.0, True, t_process=time.perf_counter(),
                           require_tpu=False, bench=bench, config=small_config(), mix=mix)
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert {n for n in NEW if n.endswith(kind)} <= set(got)
    assert all(v > 0 for k, v in got.items() if k != "ring_wait_ms.cold")
    if kind == "cold":
        assert got["put_ms.cold"] <= got["upload_ms.cold"]
        assert got["resolve_ms.cold"] < got["cold_ttft_p50_ms"]
        assert got["read_ms.cold"] < got["restore_ms.cold"]
    else:
        assert got["dispatch_ms.warm"] + got["node_ms.warm"] < got["warm_ttft_p50_ms"]

"""The upload ring's landing reader, ``land_ms.cold``, on synthetic traces."""
import pytest

from chipbench import harness
from chipbench.tests.test_chipbench_spans import BENCH, COLD, _ev, _req, _run


def test_land_time_is_the_union_of_the_lander_waits_in_each_request():
    host = [_req(0, 100), _req(200, 300),
            # request 1: 10-40 of two overlapping waits and 95-100 of one
            # straddling its end: 35 ms; request 2: 210-225: 15 ms
            _ev("spice.upload.land", 10, 30, "lander"), _ev("spice.upload.land", 20, 40, "lander"),
            _ev("spice.upload.land", 95, 110, "lander"), _ev("spice.upload.land", 210, 225, "lander"),
            # the issuer's puts are not landing time
            _ev("spice.upload.put", 40, 90, "issuer")]
    assert harness.reader("land_ms.cold")(_run(host, COLD)) == pytest.approx(25.0)


def test_no_land_span_reads_none():
    host = [_req(0, 100), _ev("spice.upload.put", 10, 20, "uploader")]
    assert harness.reader("land_ms.cold")(_run(host, COLD[:1])) is None
    run = _run(host, COLD[:1])
    run.trace = None
    assert harness.reader("land_ms.cold")(run) is None


def test_land_metric_reads_the_cold_cell():
    m = next(m for m in BENCH["per_layer"] if m["name"] == "land_ms.cold")
    assert m["source"] == "program_span" and m["layer"] == "upload ring"
    assert m["workloads"] == ["qwen1.5-0.5b.cold"] and m["moves"] == "cold_ttft_p50_ms"

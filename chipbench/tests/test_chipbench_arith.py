"""Percentile and window arithmetic, the overlay page-plan byte
count, the traffic generator, and the trace reduction on a synthetic
trace, each against hand counts."""
import numpy as np
import pytest

from chipbench import costs, stats, trace
from chipbench.traffic import Traffic, seed_key

MIX = {"clients": 1, "prompt_len": 8, "prompt_pool": 4, "max_new_tokens": 1,
       "qos": "latency", "functions": 2, "keep_alive_s": 0,
       "warm_in_setup": False, "expect": "cold"}


@pytest.mark.parametrize("q, want", [(0, 1.0), (50, 5.5), (90, 9.1), (95, 9.55), (100, 10.0)])
def test_percentile_interpolates_between_ranks(q, want):
    xs = list(range(10, 0, -1))  # 10..1, unsorted on purpose
    assert stats.percentile(xs, q) == pytest.approx(want)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_tail_needs_ten_samples_beyond_it():
    assert stats.supported_percentile(list(range(99)), 90) is None
    assert stats.supported_percentile(list(range(100)), 90) == pytest.approx(89.1)
    assert stats.supported_percentile(list(range(199)), 95) is None
    assert stats.supported_percentile(list(range(200)), 95) is not None


def test_rate():
    assert stats.rate(150, 30.0) == 5.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_overlay_bytes_from_the_page_plan():
    Z, B, P = costs.KIND_ZERO, costs.KIND_BASE, costs.KIND_PRIVATE
    kinds = np.array([B, B, P, Z, P, B], np.int32)
    # 3 base reads + 2 private reads + 6 writes, 64 KiB each
    assert costs.overlay_bytes(kinds, 65536) == (3 + 2 + 6) * 65536


def test_overlay_bytes_of_a_qwen_layer_matrix():
    # w_gate of a fine-tuned qwen1.5-0.5b layer: 1024 x 2816 float32 is
    # 11,534,336 B = 176 pages of 64 KiB, all private after the bump
    kinds = np.full(176, costs.KIND_PRIVATE, np.int32)
    assert costs.overlay_bytes(kinds, 65536) == 2 * 11_534_336


def test_peaks_by_device_kind():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v99")


def test_traffic_is_seeded_and_round_robin():
    a, b = Traffic(MIX, 2**31 + 11, 1000), Traffic(MIX, 2**31 + 11, 1000)
    np.testing.assert_array_equal(a.prompts, b.prompts)
    ra, rb = a.requests(), b.requests()
    pairs = [next(ra) for _ in range(8)]
    assert pairs == [next(rb) for _ in range(8)]
    assert [f for f, _ in pairs] == [0, 1] * 4
    # each pass over the pool sends every prompt once
    assert sorted(p for _, p in pairs[:4]) == [0, 1, 2, 3]
    c = Traffic(MIX, 5, 1000)
    assert c.prompts.shape == a.prompts.shape
    assert not np.array_equal(c.prompts, a.prompts)
    assert 0 <= seed_key(2**40) < 2**31


def test_traffic_refuses_a_cold_mix_that_keeps_warm():
    with pytest.raises(ValueError):
        Traffic(dict(MIX, keep_alive_s=60), 0, 1000)


def _synthetic():
    # window 0..100 ns; ops overlap at 10..30 and 25..40, one op at 60..70,
    # one straddling the window's end
    dev = [("fusion.1", 10, 30), ("overlay_patch.3", 25, 40),
           ("overlay_patch.3", 60, 70), ("fusion.2", 95, 120)]
    host = [("main", trace.WINDOW_SPAN, 0, 100),
            ("main", "chipbench.request", 0, 100),
            ("uploader", "pread", 42, 58),
            ("main", "wait", 70, 95)]
    return trace.Trace(device=dev, host=host, lo=0, hi=100)


def test_busy_and_gaps():
    t = _synthetic()
    assert trace.busy_ns(t.device, t.lo, t.hi) == 30 + 10 + 5
    assert trace.idle_gaps(t.device, t.lo, t.hi) == [(0, 10), (40, 60), (70, 95)]


def test_kernel_time_and_top_ops():
    t = _synthetic()
    assert trace.kernel_ns(t.device, "overlay_patch", t.lo, t.hi) == (25, 2)
    top = trace.top_ops(t.device, t.lo, t.hi)
    assert top[0] == ["overlay_patch.3", 25e-9] and top[1] == ["fusion.1", 20e-9]


def test_gaps_are_named_by_the_host():
    t = _synthetic()
    gaps = trace.longest_gaps(t)
    assert gaps == [["main/wait", 25e-9], ["uploader/pread", 20e-9],
                    ["main/chipbench.request", 10e-9]]
    s = trace.summary(t)
    assert s["busy_s"] == 45e-9 and s["window_s"] == 100e-9
    assert s["breakdown"]["idle_gaps"] == gaps


def test_op_names_are_cut_to_name_and_shape():
    hlo = ("%fusion.9 = f32[1024,2816]{1,0:T(8,128)S(1)} fusion(f32[1024,2816]"
           "{1,0:T(8,128)S(1)} %copy-done), kind=kOutput")
    assert trace.short_name(hlo) == "%fusion.9 = f32[1024,2816]"
    assert trace.short_name("overlay_patch") == "overlay_patch"

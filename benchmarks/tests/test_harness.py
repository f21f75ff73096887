"""Arithmetic the yardstick rests on: percentiles and rates, seeded
generation, the work counts, the trace reduction on a recorded trace."""
import os

import numpy as np
import pytest

from benchmarks.harness import generators as gen
from benchmarks.harness import peaks, reference_tensor, stats, trace, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _calls(latencies, t_start=0.0, nbytes=1000):
    t, out = t_start, []
    for lat in latencies:
        out.append({"t_issue": t, "t_done": t + lat, "ok": True,
                    "bytes": nbytes})
        t += lat
    return out


def test_percentile_matches_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_goodput_counts_only_completed_ok_calls_over_the_whole_window():
    calls = _calls([1.0] * 10, nbytes=2_000_000_000)
    assert stats.goodput_gbps(calls, 0.0, 10.0) == pytest.approx(2.0)
    calls[3]["ok"] = False
    assert stats.goodput_gbps(calls, 0.0, 10.0) == pytest.approx(1.8)
    # a call that ends after the close counts nothing
    assert stats.goodput_gbps(calls, 0.0, 9.5) == pytest.approx(
        8 * 2.0 / 9.5)


def test_a_stall_moves_both_end_to_end_numbers():
    steady = _calls([0.01] * 1000)
    stalled = _calls([0.01] * 500 + [3.0] + [0.01] * 500)
    t1 = 13.0
    assert stats.goodput_gbps(stalled, 0, t1) == \
        pytest.approx(stats.goodput_gbps(steady, 0, t1), rel=0.01)
    # closed loop: the same window holds fewer calls once one stalls
    window = 8.0
    assert stats.goodput_gbps(stalled, 0, window) < \
        0.8 * stats.goodput_gbps(steady, 0, window)
    many = _calls([0.01] * 10 + [3.0] * 2 + [0.01] * 10)
    assert stats.latency_p95_ms(many, 0, 100) > 1000.0
    failed = _calls([0.01] * 10)
    for c in failed[:2]:
        c["ok"] = False
    assert stats.latency_p95_ms(failed, 0, 100) >= 3_000_000.0


def test_token_gaps_and_rate():
    streams = [[0.0, 0.1, 0.2, 1.2], [0.5, 0.6]]
    gaps = stats.token_gaps_ms(streams, 0.0, 2.0)
    assert sorted(round(g) for g in gaps) == [100, 100, 100, 1000]
    assert stats.tokens_per_s(streams, 0.0, 2.0) == pytest.approx(3.0)
    assert stats.token_gaps_ms(streams, 0.15, 1.0) == pytest.approx(
        [100.0, 100.0])


def test_iqr_share_is_the_contracts_spread():
    vals = [10.0, 10.2, 9.9, 10.1, 10.0, 10.3]
    import statistics
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))


def test_generators_reproduce_and_differ_and_keep_the_mix():
    a = gen.block_permutations(range(6), 60, gen.rng_for(2**31 + 5, 1, 0))
    b = gen.block_permutations(range(6), 60, gen.rng_for(2**31 + 5, 1, 0))
    c = gen.block_permutations(range(6), 60, gen.rng_for(2**31 + 6, 1, 0))
    assert a == b and a != c
    assert sorted(a) == sorted(c)           # same multiset, other order
    assert gen.fold_seed(2**33 + 3) != gen.fold_seed(3)
    fixed1 = gen.lognormal_lengths(64, 48, 0.6, 16, 128,
                                   np.random.default_rng(1))
    fixed2 = gen.lognormal_lengths(64, 48, 0.6, 16, 128,
                                   np.random.default_rng(1))
    assert fixed1 == fixed2 and min(fixed1) >= 16 and max(fixed1) <= 128


def test_closed_loop_runs_callers_and_stops():
    seen = []

    def call(caller, i):
        seen.append((caller, i))
        return {"t_issue": 0.0, "t_done": 0.0, "ok": True, "bytes": 1}
    loop = gen.ClosedLoop(3, call)
    t0, t1 = loop.run(0.05)
    assert t1 - t0 >= 0.05 and loop.stuck == 0
    assert {c for c, _ in seen} == {0, 1, 2}
    assert len(loop.all_records()) == len(seen)


def test_payload_reference_is_a_function_of_seed_and_id():
    a = reference_tensor.payload_numpy(7, 3, 1024)
    assert a.dtype == np.uint32 and a.shape == (1024,)
    assert np.array_equal(a, reference_tensor.payload_numpy(7, 3, 1024))
    assert not np.array_equal(a, reference_tensor.payload_numpy(7, 4, 1024))
    assert not np.array_equal(a, reference_tensor.payload_numpy(8, 3, 1024))
    assert reference_tensor.mismatched_words(a, 7, 3, 1024) == 0
    b = a.copy()
    b[5] += 1
    assert reference_tensor.mismatched_words(b, 7, 3, 1024) == 1
    assert reference_tensor.mismatched_words(a[:512], 7, 3, 1024) == 1024


def test_device_payload_equals_the_numpy_reference():
    """The jitted maker of the resident pool and the plain numpy
    reference are two writings of one function."""
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import loader
    make = loader.load_driver("tensor_rail").device_payload
    key = reference_tensor.payload_key(123456789, 42)
    got = np.asarray(jax.jit(make, static_argnums=1)(jnp.uint32(key), 4096))
    assert np.array_equal(got,
                          reference_tensor.payload_numpy(123456789, 42, 4096))


FULL = {"vocab": 50304, "d_model": 2048, "n_layers": 16, "n_heads": 16,
        "n_kv_heads": 16, "head_dim": 128, "d_ff": 8192}


def test_work_counts_against_hand_worked_numbers():
    assert work.echo_hbm_bytes(1 << 20) == 4 << 20
    assert work.echo_link_bytes(1 << 20) == 2 << 20
    # per layer: 4 x 2048x2048 attention matrices + 2 x 2048x8192 MLP
    per_layer = 4 * 2048 * 2048 + 2 * 2048 * 8192
    assert per_layer == 50_331_648
    assert work.transformer_params(FULL) == 50304 * 2048 + 16 * per_layer
    assert work.transformer_params(FULL) == 908_328_960
    # one token, nothing cached yet: 2 FLOPs per weight (tied head too)
    assert work.decode_step_flops(FULL, 1, 0) == 2.0 * 908_328_960
    # attention: 16 layers x (q.k + p.v) x 16 heads x 128 x 2 FLOPs
    assert work.decode_step_flops(FULL, 1, 100) - \
        work.decode_step_flops(FULL, 1, 0) == 16 * 2 * 2 * 16 * 128 * 100
    assert work.kv_bytes_per_token(FULL) == 16 * 2 * 16 * 128 * 4 == 262_144
    assert work.decode_step_bytes(FULL, 8, 1000) == \
        908_328_960 * 4 + 262_144 * 1000
    assert work.paged_attention_bytes(FULL, 1000) == 262_144_000
    v5e = peaks.peaks_for("TPU v5 lite")
    s, bound = work.roofline_seconds(
        work.decode_step_flops(FULL, 8, 1000),
        work.decode_step_bytes(FULL, 8, 1000), v5e)
    assert bound == "memory" and s == pytest.approx(3_895_459_840 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")


def test_merge_and_gap_attribution():
    assert trace.merge_intervals([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]
    merged = [[0, 10], [110, 120], [130, 140]]
    spans = [("bench.a", 5, 100), ("bench.b", 100, 60)]
    gaps = trace.attribute_gaps(merged, spans)
    assert gaps["bench.a"] == pytest.approx(100e-9)   # 10..110: a covers 95
    assert gaps["bench.b"] == pytest.approx(10e-9)    # 120..130


def test_reduce_recorded_trace():
    """tests/data/small.xplane.pb: three ``bench.call`` spans, each one
    launch of ``jit__lambda`` (a 512x512 matmul + tanh) on the CPU."""
    planes = trace.read_planes(os.path.join(DATA, "small.xplane.pb"))
    assert [s[0] for s in planes["spans"]] == ["bench.call"] * 3
    red = trace.reduce_planes(planes)
    assert red["n_devices"] == 1
    assert 0.0 < red["busy_s"] == red["busy_s_max"]
    span_total = sum(d for _n, _s, d in planes["spans"]) / 1e9
    assert red["busy_s"] <= span_total * 1.05
    assert set(red["programs"]) == {"jit__lambda"}
    assert any(name.startswith("dot_general") for name, _ in red["device_ops"])
    assert sum(s for _n, s in red["idle_gaps"]) > 0.0
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_short_op_name():
    long = "%copy.1 = u32[16777216]{0:T(1024)} copy(u32[16777216]{0:T(1024)} %a.1)"
    assert trace.short_op_name(long) == "copy.1 u32[16777216]"
    assert trace.short_op_name("dot_general.1") == "dot_general.1"

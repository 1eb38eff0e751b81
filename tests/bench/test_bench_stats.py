"""The arithmetic of the metrics: percentiles over every attempted request,
failed requests as missing the limit, the window, tokens per second."""
import pytest

from bench import harness, runner, stats

DIMS = harness.model("dense_gqa").Dims(d_model=8, n_layers=1, n_heads=2, n_kv_heads=1, head_dim=4, d_ff=16,
            vocab=32, tied=True, qkv_bias=False, rope_theta=1e4, norm_eps=1e-5)


def _req(rid, due, tokens=(), admitted=None, ctx=100, q=10, matched=0, finished=True, began=None):
    return harness.WinReq(rid=rid, due=due, submitted=due, ctx_len=ctx, q_len=q, max_new=len(tokens),
                          admit_began=began, admitted=admitted, token_times=list(tokens),
                          tokens=[0] * len(tokens), matched=matched, finished=finished)


def _view(reqs, start=0.0, end=10.0, run_end=70.0, setup=12.5, carried=()):
    w = harness.Window(start=start, end=end, reqs=reqs, steps=[], generator_late=[],
                       queue_depth=[], run_end=run_end, carried=list(carried))
    return runner.RunView(window=w, dims=DIMS, peak=None, setup_s=setup)


def read(name, view):
    return runner.reader(name)(view)


def seen(name, view):
    """A number of the run's ``latency`` line."""
    return runner.latency(view.window)[name]


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 99) == 99
    assert stats.percentile([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_over_all_attempted_failed_count_as_late():
    # 9 served at 0.1 s, one never served: p90 is the ninth, p100 the failed one
    reqs = [_req(i, float(i), tokens=(i + 0.1,)) for i in range(9)] + [_req(9, 9.0)]
    assert seen("ttft_p50_s", _view(reqs)) == pytest.approx(0.1)
    assert seen("ttft_mean_s", _view(reqs)) == pytest.approx((9 * 0.1 + 61.0) / 10)
    ttft = stats.ttfts([r.due for r in reqs], [r.first_token for r in reqs], 70.0)
    assert stats.percentile(ttft, 90) == pytest.approx(0.1)
    assert stats.percentile(ttft, 100) == pytest.approx(61.0)
    # six failed of ten: the median reaches one of them
    reqs = reqs[:4] + [_req(i, float(i)) for i in range(4, 10)]
    assert seen("ttft_p50_s", _view(reqs)) == pytest.approx(70.0 - 9.0)


def test_token_gaps_inside_the_window_only():
    reqs = [_req(0, 0.0, tokens=(1.0, 1.5, 3.0, 11.0)), _req(1, 2.0, tokens=(2.5, 2.6))]
    gaps = stats.token_gaps([r.token_times for r in reqs], 0.0, 10.0)
    assert sorted(gaps) == pytest.approx([0.1, 0.5, 1.5])
    assert seen("gap_p99_ms", _view(reqs)) == pytest.approx(1500.0)
    assert read("token_gap_p50_ms", _view(reqs)) == pytest.approx(500.0)
    assert seen("gap_p50_ms", _view(reqs)) == read("token_gap_p50_ms", _view(reqs))
    assert seen("gap_mean_ms", _view(reqs)) == pytest.approx(700.0)


def test_lead_in_tokens_count_its_requests_are_not_attempted():
    """A lead-in request's tokens inside the window are part of the window's
    work; its own first-token time and queue wait are not attempted ones."""
    lead = _req(-1, -5.0, tokens=(-1.0, 0.5, 1.0), began=-4.0, admitted=-3.0, ctx=1000,
                matched=0)
    lead.lead = True
    reqs = [_req(0, 2.0, tokens=(2.4, 2.6), began=2.1, admitted=2.2, ctx=1000, matched=1000)]
    v = _view(reqs, carried=[lead])
    assert v.window.served == [lead] + reqs and set(v.window.by_rid()) == {-1, 0}
    assert seen("output_tokens_per_s", v) == pytest.approx(4 / 10)
    assert seen("gap_p99_ms", v) == pytest.approx(1500.0)
    assert read("token_gap_p50_ms", v) == pytest.approx(500.0)
    assert seen("ttft_p50_s", v) == pytest.approx(0.4)
    assert seen("queue_wait_p90_s", v) == pytest.approx(0.1)
    # admitted before the window opened: not in the window's reuse share
    assert runner.reused_share(v.window) == pytest.approx(100.0)


def test_tokens_per_s_all_tokens_over_whole_window():
    reqs = [_req(0, 0.0, tokens=(1.0, 2.0, 12.0)), _req(1, 5.0, tokens=(9.0,))]
    assert seen("output_tokens_per_s", _view(reqs)) == pytest.approx(3 / 10)


def test_queue_wait_and_reused_share():
    reqs = [_req(i, float(i), tokens=(i + 0.5,), began=i + 0.2, admitted=i + 0.4, ctx=1000,
                 matched=992) for i in range(10)]
    # admitted after the window
    reqs.append(_req(10, 9.5, began=10.5, admitted=11.0, ctx=1000, matched=1000))
    v = _view(reqs)
    assert seen("queue_wait_p90_s", v) == pytest.approx(0.2)
    assert seen("queue_wait_p90_s", _view(reqs + [_req(11, 9.9)] * 3)) == pytest.approx(70.0 - 9.9)
    assert runner.reused_share(v.window) == pytest.approx(99.2)
    assert runner.reused_share(_view([_req(0, 0.0)]).window) is None
    assert read("setup_s", v) == 12.5


def test_trace_readers_find_nothing_without_a_trace():
    v = _view([_req(0, 0.0, tokens=(0.5,))])
    for name in ("prefill_mfu", "decode_mfu", "packed_prefill_roofline",
                 "decode_attention_roofline"):
        assert read(name, v) is None


def test_spread_and_bound():
    from bench import spread

    a = [{"m": v} for v in (10.0, 10.2, 9.8, 10.1, 9.9, 10.0)]
    b = [{"m": v} for v in (10.0, 10.4, 9.6, 10.2, 9.8, 13.0)]
    row = spread.table(a, b)["m"]
    q1, q2, q3 = __import__("statistics").quantiles([10.0, 10.4, 9.6, 10.2, 9.8, 13.0], n=4)
    assert row["spread2"] == pytest.approx((q3 - q1) / q2)
    assert row["widest"] == max(row["spread1"], row["spread2"])
    assert row["bound"] == pytest.approx(max(0.01, 5 * row["widest"]))
    assert row["median_shift"] == pytest.approx(10.1 / 10.0 - 1)

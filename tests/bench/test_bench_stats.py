"""Rate and tail arithmetic over a whole window."""
import numpy as np
import pytest

from bench import stats as S


def _window(records, t0=0.0, t1=10.0, prefill=0):
    return S.Window(t_start=t0, t_stop=t1, records=records,
                    prefill_tokens=prefill)


def _steady(n_req=20, gap=0.02, n_tok=50, stall_at=None, stall=0.0):
    """n_req requests due every 0.5 s, first token 0.1 s after due, then
    a token every ``gap`` s; optionally the whole server stalls once."""
    recs = []
    for i in range(n_req):
        due = 0.5 * i
        t, toks = due + 0.1, []
        for _ in range(n_tok):
            if stall_at is not None and t >= stall_at and \
                    (not toks or toks[-1] < stall_at):
                t += stall
            toks.append(t)
            t += gap
        recs.append(S.ReqRecord(index=i, due=due, submit=due,
                                admit=due + 0.05, tokens=toks,
                                max_new=n_tok))
    return recs


def test_percentile_matches_numpy():
    xs = np.random.default_rng(0).random(101)
    for q in (0, 5, 50, 95, 100):
        assert S.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert S.percentile([], 95) is None


def test_rates_count_all_work_over_all_time():
    recs = _steady()
    w = _window(recs, 0.0, 10.0, prefill=1234)
    inside = sum(1 for r in recs for t in r.tokens if t <= 10.0)
    assert S.output_tokens_per_s(w) == pytest.approx(inside / 10.0)
    assert S.prompt_tokens_per_s(w) == pytest.approx(123.4)


def test_tails_of_a_steady_window():
    w = _window(_steady())
    assert S.percentile(S.itls_s(w), 95) == pytest.approx(0.02)
    assert S.percentile(S.ttfts_s(w), 95) == pytest.approx(0.1)
    assert S.percentile(S.waits_s(w, "admit"), 95) == pytest.approx(0.05)


def test_a_stall_inside_the_window_moves_the_tail():
    """A 1.5 s stall at t=5 delays every request in flight then: the ITL
    tail and the rate must both show it."""
    base = _window(_steady())
    stalled = _window(_steady(stall_at=5.0, stall=1.5))
    m0 = S.end_to_end(base, ["itl_p95_ms", "output_tokens_per_s"])
    m1 = S.end_to_end(stalled, ["itl_p95_ms", "output_tokens_per_s"])
    assert m1["output_tokens_per_s"] < m0["output_tokens_per_s"]
    itl = S.itls_s(stalled)
    assert max(itl) == pytest.approx(1.52)
    # the stall hits about 10% of gaps' worth of requests: p99 sees it
    assert S.percentile(itl, 99.9) > 1.0


def test_a_request_without_a_first_token_counts_at_its_age():
    recs = [S.ReqRecord(index=0, due=1.0, tokens=[1.2, 1.3], max_new=2),
            S.ReqRecord(index=1, due=2.0, tokens=[], max_new=4),
            S.ReqRecord(index=2, due=12.0, tokens=[], max_new=4)]
    w = _window(recs, 0.0, 10.0)
    # request 2 is due after the close: not in this window
    assert sorted(S.ttfts_s(w)) == pytest.approx([0.2, 8.0])
    assert sorted(S.waits_s(w, "admit")) == pytest.approx([8.0, 9.0])
    assert S.percentile(S.ttfts_s(w), 95) == pytest.approx(
        0.2 + 0.95 * (8.0 - 0.2))


def test_tokens_outside_the_window_are_not_counted():
    recs = [S.ReqRecord(index=0, due=0.0, tokens=[0.5, 1.0, 11.0],
                        max_new=3)]
    w = _window(recs, 0.0, 10.0)
    assert w.output_tokens() == 2
    assert S.itls_s(w) == pytest.approx([0.5])

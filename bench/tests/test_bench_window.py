"""Window arithmetic: tokens/s, inter-token gaps and time to first token,
with requests in flight at the window's edges."""

import pytest

from bench import window

W0, W1 = 10.0, 20.0
TIMES = {
    0: [9.0, 10.0, 12.0, 15.0],   # started before the window
    1: [19.0, 21.0],              # its second token falls after the window
    2: [],                        # arrived, no token yet
    3: [11.5],
}
ARRIVALS = {0: 8.0, 1: 18.0, 2: 16.0, 3: 11.0, 4: 25.0}


def test_tokens_count_inside_the_window_edges_included():
    assert window.tokens(TIMES, W0, W1) == 5
    assert window.tok_s(TIMES, W0, W1) == pytest.approx(0.5)


def test_gaps_pair_consecutive_tokens_of_one_request_inside_the_window():
    assert sorted(window.gaps(TIMES, W0, W1)) == [2.0, 3.0]


def test_ttft_counts_a_request_still_waiting_at_its_wait_so_far():
    # 0 arrived before the window; 4 after it; 2 never got a token;
    # 1's first token came at 19
    assert sorted(window.ttfts(ARRIVALS, TIMES, W0, W1)) == \
        pytest.approx([0.5, 1.0, 4.0])


def test_closed_loop_requests_have_no_ttft():
    assert window.ttfts({0: None}, TIMES, W0, W1) == []


def test_percentile_and_spans():
    assert window.percentile([], 95) is None
    assert window.percentile([1.0, 2.0, 3.0], 50) == 2.0
    spans = [("monitor", 9.0, 9.5), ("monitor", 10.0, 10.25),
             ("commit", 11.0, 11.5)]
    assert window.spans(spans, "monitor", W0, W1) == [0.25]

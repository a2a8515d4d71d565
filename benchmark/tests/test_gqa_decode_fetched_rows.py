"""``gqa_decode_fetched_rows_pct`` on ``test_trinity_metrics``'s
hand-made record: nothing where the program does not count the rows it
fetches (the parent of the PR that added the counters), else 100 x rows
fetched / rows attended over the window's calls."""

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark import harness
from test_trinity_metrics import W, read, record

NAME = "gqa_decode_fetched_rows_pct"


def test_a_record_without_the_counter_reads_nothing():
    rec = record()
    assert read(NAME, rec) is None
    assert harness.metric_value({"name": NAME}, rec, traced=True) is None
    del rec["counters"]["calls"]
    assert read(NAME, rec) is None
    rec["driver"] = "transcribe_lm"
    assert read(NAME, rec) is None


@pytest.mark.parametrize("fetched_global, steps", [
    # the plain form: all 6,784 rows of 16 streams every step
    (6784, 1400),
    # the kernel: a stream's reach 5,001 .. 6,400 up to the next 512
    (None, 1400)])
def test_fetched_over_attended(fetched_global, steps):
    rec = record()
    reach = [5001 + j for j in range(steps)]
    moved = [fetched_global or -(-r // 512) * 512 for r in reach]
    for c in rec["counters"]["calls"]:
        c["rows_fetched_window"] = 4 * 16 * steps * W
        c["rows_fetched_global"] = 16 * sum(moved)
    want = 100 * (4 * steps * W + sum(moved)) / (4 * steps * W + sum(reach))
    assert read(NAME, rec) == pytest.approx(want)
    assert 100 < read(NAME, rec) < (120 if fetched_global else 102)
    assert harness.metric_value({"name": NAME}, rec, traced=True) \
        == pytest.approx(want)
    # a call that lacks the counter is left out, not counted as zero
    del rec["counters"]["calls"][1]["rows_fetched_window"]
    assert read(NAME, rec) == pytest.approx(want)

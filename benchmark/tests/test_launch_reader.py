"""launch_ms_per_query on hand-built span lists: two capacity queries with
known plan_upload and kernel_launch times, a launch outside any query (the
warm-up's), a churn run and an XLA-route query, which read nothing."""

import pytest

from benchmark import run as R
from benchmark.tests.test_span_readers import CHURN, query, run_of, span


def pallas_query(t0, upload_us):
    # query() has a kernel_launch of 40 us; the upload comes before it
    return query(t0) + [span("plan_upload", t0 + 550, upload_us,
                             nodes=5000, bytes=4096, transfers=1)]


CASES = {
    "capacity": (pallas_query(0, 10) + pallas_query(2000, 30)
                 + [span("kernel_launch", 1500, 100, pods=1000, chunks=2)],
                 (40 + 10 + 40 + 30) / 2 / 1e3),
    "churn": (CHURN, None),
    "xla_route": ([s for s in query(0) if s["name"] != "kernel_launch"],
                  None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_launch_ms_per_query(case):
    spans, want = CASES[case]
    got = R.reader("layers", "launch_ms_per_query")(run_of(spans))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)

"""One known failure, marked as what it is.

`test_bench_span_metrics.py::test_the_benchmark_gains_the_eleven_entries_and_nothing_else`
is PR 24's check of its own diff: it holds `BENCHMARK.json` to exactly the 12
per-layer metrics of PR 23 plus PR 24's eleven.  PR 26 adds four for the
four-chip cell, so the count cannot hold, and only a `benchmark` PR may edit
that file.  Until one does (and deletes this hook), the test is an expected
failure rather than a second standing one; strict, so that mending the test
without removing the mark is noticed.
"""

import pytest

STALE_COUNT = "test_the_benchmark_gains_the_eleven_entries_and_nothing_else"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == STALE_COUNT:
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="counts BENCHMARK.json's per_layer as of PR 24 (23); PR 26 added four",
            ))

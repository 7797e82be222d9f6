"""One excused ASSERTION, and why it is here and not a repair.

``test_zaya.py::test_the_cell_its_traffic_and_its_metrics_resolve`` (PR 42)
holds ZAYA1's cell and configuration to the LAST place of
``BENCHMARK.json``'s lists.  A later PR that adds a cell has to append it
behind them (the driver reads an entry put first or in the middle as a
change to what was there) and may edit no file the benchmark already has,
this test's file among them: so from PR 47 on that one line cannot hold,
whatever the PR does.

Nothing else of the test is excused.  The test RUNS, unmarked; a failure is
turned into an expected one only where it is an ``AssertionError`` raised by
:data:`THE_PIN` itself, in ``test_zaya.py``'s own frame, AND ZAYA1's entries
still stand at the places PR 42 gave them (:data:`PLACES`: whatever lies
behind them was appended later).  Every assertion before the pin fails the
run as it always did.  The one line behind the pin (each ``why`` of at most
200 characters) is held, for every entry, by
``test_olmo_hybrid.py::test_the_cell_its_traffic_and_its_metrics_resolve``;
``test_olmo_hybrid.py::test_only_the_pin_is_excused`` holds this file to
what it says.  A ``benchmark`` PR takes the pin out of ``test_zaya.py`` and
this file with it (PERF.md § 7, ROADMAP S0).
"""

import pytest

PINNED = "tests/benchmarks/test_zaya.py::test_the_cell_its_traffic_and_its_metrics_resolve"
THE_PIN = 'assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1] is entry'
# where PR 42 appended them: (list, index, name)
PLACES = (("configs", 6, "zaya1-8b"), ("workloads", 8, "zaya1-8b.serve-reasoning-resident"))


def only_the_pin_failed(excinfo, bench) -> bool:
    """An assertion, raised by the pin's own line in ``test_zaya.py``, over
    lists in which ZAYA1's entries are where they were put."""
    last = excinfo.traceback[-1]
    return (excinfo.errisinstance(AssertionError)
            and last.path.name == "test_zaya.py"
            and str(last.statement).strip() == THE_PIN
            and all(len(bench[key]) > at and bench[key][at]["name"] == name
                    for key, at, name in PLACES))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    report = (yield).get_result()
    if report.when == "call" and report.failed and item.nodeid.endswith(PINNED):
        from benchmarks.lib import cells
        if only_the_pin_failed(call.excinfo, cells.load_benchmark()):
            report.outcome = "skipped"
            report.wasxfail = ("pins ZAYA1's entries to the last place of BENCHMARK.json's "
                               "lists; later cells are appended behind them")

"""One test of ``test_laguna_chipbench.py`` says that Laguna's configuration,
cell and seven metrics stand LAST in ``BENCHMARK.json``'s lists.  A later
configuration's entries go to the end of the lists (the benchmark's contract:
an entry put first or in the middle reads as a change to what was there), and
no file the benchmark already has may be edited by the PR that adds one, so
that test cannot hold any more and cannot be mended here.  It is marked a
STRICT expected failure that has to raise ``AssertionError``: it cannot pass
or break otherwise unnoticed, and when a ``benchmark`` PR rewrites it this
mark fails until this file is deleted.  Everything else that test asserts
(the cell's four fields, Laguna's seven metrics in their order with the cell
as their one workload, the cell at the end of the lists it joined, the
traffic's numbers, one four-chip cell) is asserted of the entries WHERE THEY
STAND by ``test_glm47_chipbench.py::test_the_manifest_gains_the_cell_at_the_
end_of_every_list``."""
import pytest

SUPERSEDED = ("test_laguna_chipbench.py::"
              "test_the_manifest_gains_the_cell_at_the_end_of_every_list")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(SUPERSEDED):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="Laguna's entries are no longer the last: "
                       "glm47_flash stands after them"))

"""Two older tests of this directory are parametrised over every cell of
``BENCHMARK.json`` and hold each to more than their names say; a PR that
adds a cell may not edit them (ISSUE 42; ROADMAP R0 o, p). For the cell
``ssb-lineorder-flights.q-flight``, whose mix is the issue's letter for
letter, their cases are marked here, and ``test_bench_flights_cell.py``
holds the cell to what each was written to hold:

- ``test_bench_traffic.py::test_rotation_keeps_templates_in_fixed_
  proportions`` counts each client's first 24 requests and expects
  ``24 * clients // len(rotation)`` of every template, which is exact only
  where the rotation's length divides 24. The source has 13 queries. The
  case is a strict ``xfail``: the PR that counts ``lcm(24, len(rotation))``
  there turns it red until this entry goes.
- ``test_bench_terms.py::test_old_and_new_reference_agree`` hands every
  template whose terms are all ``[field, row]`` to the parent's reference
  it keeps word for word, whose joint table over dimensions and filter
  fields is 273 M cells for Q3.2 and 5.5e9 (44 GB) for Q4.3: the process
  is killed, so the three cases are skipped, not run. The mend there:
  leave out a template whose joint table passes 2^20 cells.
"""

import pytest

FLIGHTS = "ssb-lineorder-flights.q-flight"
KEPT_OFF = {
    ("test_bench_traffic.py",
     "test_rotation_keeps_templates_in_fixed_proportions"): pytest.mark.xfail(
        strict=True,
        reason="counts 24 requests a client; 13 does not divide 24 "
               "(ROADMAP R0 o)"),
    ("test_bench_terms.py",
     "test_old_and_new_reference_agree"): pytest.mark.skip(
        reason="the parent's reference tabulates Q4.3 by 5.5e9 cells "
               "(ROADMAP R0 p)"),
}


def pytest_collection_modifyitems(items):
    for item in items:
        mark = KEPT_OFF.get((item.path.name, getattr(item, "originalname",
                                                     None)))
        if mark is not None and item.callspec.params["cell"]["name"] == FLIGHTS:
            item.add_marker(mark)

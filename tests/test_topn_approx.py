"""Adversarial TopN approximation tests.

TopN's phase-1 candidate set comes from the per-fragment RANKED CACHES,
ordered by UNFILTERED row counts; phase 2 recounts candidates exactly.
Two consequences, pinned here and documented in docs/PQL.md:

- A FILTERED TopN considers only each fragment's top
  ``max(4n, n+10)`` rows by UNFILTERED count — it can miss a row
  entirely, even the true #1 under the filter, when that row's
  unfiltered count ranks below the candidate window (and below the
  cache's kept set when ``cacheSize`` overflowed). This is the
  reference's documented cache approximation.
- A fully COLD cache (crash before cache save, `recalculate-caches` not
  yet run) does not ADD error: `fragment.top()` falls back to the exact
  container-metadata scan, so unfiltered TopN stays exact; the filtered
  candidate-window bound above applies cold or warm.
- The escape hatch is always `TopN(ids=[...])` (phase 2 only, exact) —
  or `Rows(f)` + `TopN(ids=)` as the exact-but-slower oracle.
"""

import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.storage.view import VIEW_STANDARD

CACHE_SIZE = 8
N_DECOYS = 20          # rows 1..20: high unfiltered count, miss the filter
NEEDLE = 21            # row 21: low unfiltered count, IS the filtered top
NEEDLE_BITS = 30


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    f = idx.create_field(
        "f", FieldOptions.from_dict({"cacheType": "ranked",
                                     "cacheSize": CACHE_SIZE}))
    g = idx.create_field("g")
    frag = f.view(VIEW_STANDARD, create=True).fragment(0, create=True)
    # decoys: 100 bits each in columns 0..1999 (outside the filter)
    for row in range(1, N_DECOYS + 1):
        frag.bulk_import(np.full(100, row, np.uint64),
                         np.arange(100, dtype=np.uint64) * 20 + row)
    # the needle: NEEDLE_BITS bits, all inside the filter region
    needle_cols = 10_000 + np.arange(NEEDLE_BITS, dtype=np.uint64)
    frag.bulk_import(np.full(NEEDLE_BITS, NEEDLE, np.uint64), needle_cols)
    # filter row g=1 covers exactly the needle's columns
    gfrag = g.view(VIEW_STANDARD, create=True).fragment(0, create=True)
    gfrag.bulk_import(np.full(NEEDLE_BITS, 1, np.uint64), needle_cols)
    ex = Executor(holder)
    yield holder, ex, frag
    holder.close()


def exact_filtered_topn(ex, n):
    """Oracle: Rows() enumeration + exact per-row recount (the ids= form
    skips phase 1 entirely), trimmed like TopN orders."""
    rows = ex.execute("i", "Rows(f)")[0]
    pairs = ex.execute(
        "i", f"TopN(f, Row(g=1), ids={list(rows)}, n=0)")[0]
    return [(p.id, p.count) for p in pairs[:n]]


def test_trimmed_cache_misses_filtered_top_row(env):
    """The adversarial bound: the cache trimmed to the top-8 unfiltered
    rows cannot supply the needle as a candidate, so the filtered TopN
    MISSES the true top row. The oracle proves the divergence."""
    holder, ex, frag = env
    got = ex.execute("i", "TopN(f, Row(g=1), n=3)")[0]
    # phase 1 trimmed the cache (lazy, on first top()) and the needle
    # fell out of rank — so the filtered TopN cannot see it
    cached = set(frag.row_cache.ids())
    assert len(cached) <= CACHE_SIZE          # trim really happened
    assert NEEDLE not in cached               # needle fell out of rank
    assert all(p.id != NEEDLE for p in got)   # the approximation, pinned
    # exact answer (Rows + recount): needle first, with all its bits
    assert exact_filtered_topn(ex, 1) == [(NEEDLE, NEEDLE_BITS)]


def test_unfiltered_topn_stays_exact_despite_trim(env):
    """Without a filter the kept top-`cacheSize` rows contain every true
    top-n for n ≤ cacheSize − overlap: the decoys tie at 100 and order
    by ascending id, exactly what phase 2 returns."""
    holder, ex, frag = env
    got = ex.execute("i", "TopN(f, n=5)")[0]
    assert [(p.id, p.count) for p in got] == [
        (r, 100) for r in range(1, 6)
    ]


def test_cold_cache_falls_back_to_exact_scan(env):
    """Evict/cold the ranked cache entirely: fragment.top() falls back
    to the exact row_counts() metadata scan. Unfiltered TopN therefore
    stays EXACT on a cold cache — but the filtered candidate-window
    bound is a property of phase 1's overfetch, not of the cache, so
    the adversarial filtered query still misses the needle (its
    unfiltered rank stays below the window)."""
    holder, ex, frag = env
    frag.row_cache._counts.clear()            # crash-cold cache
    got = ex.execute("i", "TopN(f, n=5)")[0]
    assert [(p.id, p.count) for p in got] == [(r, 100) for r in range(1, 6)]
    frag.row_cache._counts.clear()
    got = ex.execute("i", "TopN(f, Row(g=1), n=1)")[0]
    assert all(p.id != NEEDLE for p in got)
    # the needle ranks 21st unfiltered; a window that REACHES its rank
    # makes the filtered query exact even cold (the bound, exactly)
    frag.row_cache._counts.clear()
    got = ex.execute("i", "TopN(f, Row(g=1), n=30)")[0]
    assert [(p.id, p.count) for p in got] == [(NEEDLE, NEEDLE_BITS)]


def test_recalculate_caches_restores_the_trimmed_regime(env):
    """The repair hatch recounts AND re-trims: after recalculate, the
    cache again holds the top unfiltered rows (approximate under the
    adversarial filter, exact without one) — recalculation fixes drift,
    it does not grow the bound."""
    holder, ex, frag = env
    frag.row_cache._counts.clear()
    frag.recalculate_cache()
    cached = set(frag.row_cache.ids())
    assert len(cached) <= CACHE_SIZE and NEEDLE not in cached
    got = ex.execute("i", "TopN(f, Row(g=1), n=3)")[0]
    assert all(p.id != NEEDLE for p in got)
    got = ex.execute("i", "TopN(f, n=3)")[0]
    assert [(p.id, p.count) for p in got] == [(r, 100) for r in (1, 2, 3)]


def test_ids_form_is_always_exact(env):
    """`TopN(ids=[...])` bypasses phase 1, so it is exact regardless of
    cache state — the client-side escape hatch the docs point to."""
    holder, ex, frag = env
    got = ex.execute("i", f"TopN(f, Row(g=1), ids=[{NEEDLE}, 1], n=0)")[0]
    assert [(p.id, p.count) for p in got] == [(NEEDLE, NEEDLE_BITS)]


def test_the_mesh_adds_no_approximation(env):
    """A mesh sums the same exact per-shard counts: the DistExecutor
    matches the single-device Executor pair for pair on every TopN form,
    including the adversarial filtered shape (both share phase 1's
    candidate window, so they share its documented bound — nothing
    more), and the ids= form that bypasses phase 1."""
    holder, ex, frag = env
    from pilosa_tpu.parallel import DistExecutor, make_mesh

    mesh = DistExecutor(holder, make_mesh(2))
    for pql in ("TopN(f, n=5)",
                "TopN(f, n=3)",
                "TopN(f, Row(g=1), n=3)",
                "TopN(f, n=4, threshold=100)",
                f"TopN(f, Row(g=1), ids=[{NEEDLE}, 1], n=0)"):
        (want,) = ex.execute("i", pql)
        (got,) = mesh.execute("i", pql)
        assert [(p.id, p.count) for p in got] == \
            [(p.id, p.count) for p in want], pql

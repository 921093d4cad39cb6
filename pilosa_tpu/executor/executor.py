"""The query executor: dispatch, shard mapReduce, result reduction.

Reference: executor.go (SURVEY.md §2 #12, §3.2–3.4). Shape preserved:
``execute<CallName>`` dispatch, a map phase over shards and a reduce phase
merging partials (rows union, counts add, TopN pair-merge + exact recount,
GroupBy group-merge). TPU re-design: the map phase evaluates ONE fused
compiled kernel per query shape per shard (expr.py) against HBM-resident
rows; the single-chip path loops shards on the host, and the mesh path
(pilosa_tpu.parallel) shard_maps the same kernels with psum reduces.

BSI semantics (Sum/Min/Max/Range): values are offset-encoded against the
field base (storage.field); kernels work on stored magnitudes and the
host adds ``base·count`` back (Sum) or ``base`` (Min/Max). Predicates are
base-shifted and range-clamped at compile time so out-of-range compares
reduce to const-empty / all-existing without touching the device.
"""

from __future__ import annotations

import collections
import datetime as dt
import functools
import math
import threading
import time
import weakref
from bisect import bisect_right
from itertools import repeat
from typing import Callable

import numpy as np

from pilosa_tpu.executor import batch, expr
from pilosa_tpu.executor.result import GroupCounts, Pair, RowResult, ValCount
from pilosa_tpu.pql import Call, Condition, parse
from pilosa_tpu.pql.ast import Query
from pilosa_tpu.shardwidth import (
    SHARD_WIDTH,
    WORDS_PER_SHARD,
    next_pow2,
    position,
    shard_of,
)
from pilosa_tpu.storage import residency
from pilosa_tpu.storage.heat import global_heat
from pilosa_tpu.utils.cost import current_cost, use_node
from pilosa_tpu.utils.tracing import (
    note_groupby_level,
    note_groupby_marginal,
    note_groupby_operand_placement,
    note_groupby_pruned,
    note_groupby_range_dims,
    stage,
    staged,
)
from pilosa_tpu.storage.field import (
    BSI_EXISTS_ROW,
    TYPE_INT,
    TYPE_TIME,
)
from pilosa_tpu.storage.index import EXISTENCE_FIELD, Index
from pilosa_tpu.storage.view import VIEW_STANDARD, views_by_time_range

# TopN phase-1 candidate overfetch per shard (reference uses a similar
# superset factor before the exact recount — SURVEY.md §3.4; exact upstream
# value unverifiable, Appendix B).
TOPN_CANDIDATE_FACTOR = 4

# HBM budget (per device) for one TopN phase-2 candidate matrix chunk. A
# candidate row costs shards×2^15 words ≈ 128 MiB/candidate at 1024
# shards, so an unchunked 64-candidate matrix would be 8 GiB — larger
# than the residency budget. Chunks are power-of-two candidate counts so
# a pipelined TopN stream still buckets into shared program shapes.
TOPN_MATRIX_BUDGET_BYTES = 1 << 30

# A GroupBy of two or more dimensions is counted in a single level of
# every group (one device sync) while its cross product is at most this
# many groups AND that level is at most GROUPBY_DENSE_MAX_PROGRAMS
# programs; past either each dimension is counted alone under the
# filter and the surviving rows' prefixes are pruned a dimension at a
# time (run_pruned: one sync for the marginal round, one per dimension
# crossed after it). No level holds its group masks in HBM
# (batch.groupby_level_body), so memory does not bound either path.
GROUPBY_DENSE_MAX_GROUPS = 4096
# Two, because pruning d >= 2 dimensions dispatches at least d + 1
# programs and blocks on 1 to d - 1 readbacks before the final level,
# so a dense level of two programs never dispatches more than pruning
# would. A program is what the level
# kernel's accumulator block holds (batch.groupby_chunk_groups
# candidates: 256 with a 24-bit Sum, 8,192 count-only); count-only a
# candidate still costs its row reads, which is why the group bound
# stands beside this one.
GROUPBY_DENSE_MAX_PROGRAMS = 2

_RESERVED_ARGS = {"_field", "_col", "from", "to", "n", "limit", "offset",
                  "previous", "column", "filter", "field", "ids", "timestamp",
                  "excludeColumns", "shards", "aggregate", "columnAttrs",
                  "attrName", "attrValue", "like", "threshold", "having"}


class PQLError(ValueError):
    pass


# --------------------------------------------------------------- leaf specs


class _RowSpec:
    """Device leaf: OR of one row across a set of views (time ranges span
    multiple views; missing fragments contribute zeros)."""

    __slots__ = ("field", "views", "row")

    def __init__(self, field: str, views: tuple[str, ...], row: int):
        self.field = field
        self.views = views
        self.row = row

    def resolve(self, idx: Index, shard: int):
        field = idx.field(self.field)
        acc = None
        for vname in self.views:
            view = field.view(vname) if field else None
            frag = view.fragment(shard) if view else None
            if frag is None:
                continue
            row = frag.device_row(self.row)
            acc = row if acc is None else acc | row
        return acc if acc is not None else _zeros_words()


class _PlanesSpec:
    """Device leaf: the stacked BSI plane matrix uint32[2+depth, words].
    ``depth`` is captured at compile time so a delete_field racing the
    query resolves to correctly-shaped zeros, not a dead dereference."""

    __slots__ = ("field", "depth", "pad_rows")

    def __init__(self, field: str, depth: int, pad_rows: int = 0):
        self.field = field
        self.depth = depth
        # zero rows after the planes of the STACKED leaf (a GroupBy's
        # level kernel: batch.groupby_pad_rows); resolve() has none
        self.pad_rows = pad_rows

    def resolve(self, idx: Index, shard: int):
        # compile-time depth throughout: the node's clamped scalars were
        # built for it, so a racing delete+recreate with a different
        # range must not change the leaf shape mid-plan (the schema epoch
        # invalidates the plan for the NEXT query)
        depth = self.depth
        field = idx.field(self.field)
        view = field.view(field.bsi_view_name()) if field is not None else None
        frag = view.fragment(shard) if view else None
        if frag is None:
            return _zeros_planes(2 + depth)

        def decode():
            rows = [frag.row_words(r) for r in range(2 + depth)]
            return np.stack(rows)

        return residency.global_row_cache().get_row(
            frag.frag_id + ("__planes__", 2 + depth), decode
        )


class _ZeroSpec:
    __slots__ = ()

    def resolve(self, idx: Index, shard: int):
        return _zeros_words()


_zeros = {}


def _zeros_words():
    z = _zeros.get(WORDS_PER_SHARD)
    if z is None:
        import jax

        z = jax.device_put(np.zeros(WORDS_PER_SHARD, np.uint32))
        _zeros[WORDS_PER_SHARD] = z
    return z


def _zeros_planes(rows: int):
    key = ("planes", rows)
    z = _zeros.get(key)
    if z is None:
        import jax

        z = jax.device_put(np.zeros((rows, WORDS_PER_SHARD), np.uint32))
        _zeros[key] = z
    return z


class _Compiled:
    """A bitmap call compiled to (structure, leaf specs, scalars).

    ``memoizable`` is set by _compile_cached exactly when the plan was
    placed in the plan cache: only those objects have a stable identity
    across repeat queries, so only their operand assemblies are worth
    (and safe to bound) memoizing — per-call plans (TopN phase 2,
    const0-degenerate trees) would fill the operand memo with
    dead-on-arrival entries."""

    def __init__(self, node, specs, scalars):
        self.node = node
        self.specs = specs
        self.scalars = scalars
        self.memoizable = False


    def eval(self, idx: Index, shard: int):
        """Single-shard evaluation (IncludesColumn); batched queries go
        through Executor._batched_eval instead."""
        leaves = [s.resolve(idx, shard) for s in self.specs]
        if not leaves:
            leaves = [_zeros_words()]
        return expr.evaluate(self.node, leaves, self.scalars)


def _node_has_const0(node) -> bool:
    """True when a compiled tree contains a const0 leaf — compiled from
    an unknown row key (or a degenerate range), whose meaning can change
    with later writes; such plans are not memoized."""
    if not isinstance(node, tuple):
        return False
    if node and node[0] == "const0":
        return True
    return any(_node_has_const0(c) for c in node[1:])


class Deferred:
    """Handle for a pipelined query result (Executor.submit).

    For most pipelined calls the device program is already enqueued and
    ``result()`` performs only the blocking host readback (plus host
    finalization); because a single device's stream is ordered,
    resolving the LAST such Deferred implies every earlier program has
    completed. Exception: calls whose evaluation needs intermediate
    readbacks (pruned multi-level GroupBy) defer their dispatch into
    ``result()`` too — see Executor.submit's per-call contract.
    """

    __slots__ = ("_finalize", "_value")

    def __init__(self, finalize=None, value=None):
        self._finalize = finalize
        self._value = value

    def result(self):
        if self._finalize is not None:
            self._value = self._finalize()
            self._finalize = None
        return self._value


# ----------------------------------------------------------------- executor


def instrument_calls(index_name: str, calls, run_one) -> list:
    """Stats/trace/cost envelope around a query's calls: one
    ``executor.Execute`` span per query, per-call ``execute<Name>`` spans
    and ``query``/``queries`` stats. Shared by eager execution and the
    serving pipeline's resolve loop (server/api.py) so span and stat
    names cannot drift between the two paths. With a PROFILE active
    (utils/cost.py) each call additionally runs under its ProfileNode —
    wall time and result cardinality land per AST node, matching the
    span tree's per-call attribution so the two reconcile."""
    from pilosa_tpu.utils.stats import global_stats
    from pilosa_tpu.utils.tracing import global_tracer

    stats = global_stats()
    cost = current_cost()
    profile = cost.profile if cost is not None else None
    out = []
    # root_span: joins the request's trace under the HTTP root, or roots
    # its own tree for direct in-process callers (tests, CLI)
    with global_tracer().root_span("executor.Execute", index=index_name):
        for i, call in enumerate(calls):
            if profile is None:  # accounting-only path: no node scoping
                with global_tracer().span(f"execute{call.name}"), \
                        stats.timer("query", {"call": call.name}):
                    out.append(run_one(call))
                stats.count("queries", 1, {"call": call.name})
                continue
            node = profile.node_for(i, call)
            t0 = time.perf_counter()
            with use_node(cost, node):
                with global_tracer().span(f"execute{call.name}"), \
                        stats.timer("query", {"call": call.name}):
                    res = run_one(call)
                node.wall_s += time.perf_counter() - t0
                cost.note_rows(_result_cardinality(res))
            out.append(res)
            stats.count("queries", 1, {"call": call.name})
    return out


@staged("device.readback")
def _readback(device_array) -> np.ndarray:
    """The blocking host copy of a program's result."""
    return np.asarray(device_array)


def _result_cardinality(res) -> int:
    """Rows materialized by one call's result (PROFILE accounting):
    result-set cardinality for bitmap calls, element counts for
    TopN/GroupBy/Rows lists. Computed only when profiling — the RowResult
    popcount is not free."""
    if isinstance(res, RowResult):
        return int(res.count())
    if isinstance(res, (list, GroupCounts)):
        return len(res)
    return 0


class Executor:
    # Queries per micro-batched dispatch (see _microbatch_enqueue).
    MICROBATCH_MAX = 16
    # XLA accounts every parameter of a compiled program as distinct HBM
    # storage even when parameters alias one buffer (measured on v5e: a
    # 64-query batch of 2×128MiB leaves fails compile with "arguments
    # 16.00G"), so a micro-batch of wide queries (many leaves) must cap
    # its TOTAL argument bytes, not just its query count — 4-way
    # intersects over 1B columns would otherwise OOM at MICROBATCH_MAX.
    MICROBATCH_ARG_BUDGET = 4 << 30
    # Plan-memo bound; cleared wholesale when full (see _compile_cached).
    PLAN_CACHE_MAX = 4096

    def __init__(self, holder):
        self.holder = holder
        # cluster hooks (set by ClusterExecutor): key_resolver translates
        # unknown keys via the coordinator; key_backfill pulls the
        # coordinator's translate log before reverse lookups
        self.key_resolver = None
        self.key_backfill = None
        self.microbatch_max = self.MICROBATCH_MAX
        self.microbatch_arg_budget = self.MICROBATCH_ARG_BUDGET
        # divisor for per-DEVICE argument accounting: mesh-sharded leaves
        # occupy nbytes/n_devices per chip (DistExecutor sets mesh.size)
        self.arg_shard_factor = 1
        self._pending: dict = {}
        self._mb_lock = threading.Lock()
        # (index, call identity, wrap) -> validated plan; see _compile_cached
        self._plan_cache: dict = {}
        # shard-list identity -> ShardBlock (LRU); see _shard_block
        self._block_memo: collections.OrderedDict = collections.OrderedDict()
        # (plan identity, block identity) -> assembled device operands,
        # valid for ONE residency generation; see _eval_operands. A
        # listener on the row cache drops entries (and their
        # device-array references) EAGERLY on every generation bump so
        # a residency eviction actually frees HBM instead of waiting
        # for the next query's validity check; it is (re-)registered
        # lazily against whatever cache is globally live, because
        # set_global_row_cache can swap the cache after this executor
        # was built (Server.open's budget-sized cache).
        self._operand_memo: dict = {}
        self._operand_memo_gen = -1
        self._listened_cache = None
        # what determines a GroupBy level's packed operand -> the array
        # as placed on the device(s); see _level_operand. Indices and
        # scalars only, nothing read from a fragment, so no write,
        # eviction or residency generation makes an entry stale
        self._placed_operands: dict = {}
        # guards the re-home check-then-register below: two serving
        # threads racing it would both register the clear listener
        self._rehome_lock = threading.Lock()

    def _clear_operand_memo(self) -> None:
        """Generation listener (called under the residency lock — must
        stay lock-free and cheap)."""
        self._operand_memo.clear()

    # ------------------------------------------------------------ top level

    def execute(self, index_name: str, query, shards=None, deadline=None):
        if deadline is not None:
            # the local map is fast (per-shard work is cheap, the paper's
            # tail math is all coordination) — enforcing at the dispatch
            # boundary is what keeps an expired sub-query from occupying
            # a device dispatch slot at all
            deadline.check("local execute")
        idx = self.holder.index(index_name)
        if idx is None:
            raise PQLError(f"index {index_name!r} not found")
        if isinstance(query, str):
            query = parse(query)
        elif isinstance(query, Call):
            query = Query([query])
        return instrument_calls(
            index_name, query.calls,
            lambda call: self._execute_call(idx, call, shards),
        )

    def submit(self, index_name: str, query, shards=None, deadline=None):
        """Pipelined execution: parse, compile, and ENQUEUE each call's
        device program without blocking on the result readback; returns
        one ``Deferred`` per call, resolved on ``.result()``.

        Device streams are ordered, so a serving loop can enqueue a stream
        of queries and resolve them in order — the host↔device round trip
        (the latency floor under every blocking readback) overlaps with
        device compute instead of serializing after it. Pipelined
        reductions sharing a program shape — Count, the BSI aggregates
        Sum/Min/Max, AND TopN's phase-2 recount (candidate lists pad to
        power-of-two buckets so same-field TopN streams share shapes) —
        are additionally coalesced into micro-batched dispatches (see
        _microbatch_enqueue) and stay in flight until resolved. Dense
        single-level GroupBys and row-materializing bitmap calls enqueue
        their programs at submit time with the readback deferred to
        result(); pruned (multi-level) GroupBys defer ALL dispatch to
        result() (each level's readback gates the next level's
        candidates). Remaining call types (writes, host-only reads)
        evaluate eagerly at submit time and return an already-resolved
        Deferred.

        ``deadline`` (qos.Deadline) is enforced at the dispatch boundary:
        an already-expired request raises before any device program is
        enqueued, so a backlogged wave sheds its dead requests instead of
        spending dispatches on answers nobody is waiting for.
        """
        if deadline is not None:
            deadline.check("local submit")
        idx = self.holder.index(index_name)
        if idx is None:
            raise PQLError(f"index {index_name!r} not found")
        if isinstance(query, str):
            query = parse(query)
        elif isinstance(query, Call):
            query = Query([query])
        cost = current_cost()
        if cost is not None and cost.profile is not None:
            # submit-phase work (operand assembly, device enqueue) must
            # land on the SAME ProfileNode the resolve phase uses —
            # node_for is positional, so both phases address one node
            out = []
            for i, call in enumerate(query.calls):
                with use_node(cost, cost.profile.node_for(i, call)):
                    out.append(self._submit_one(idx, call, shards))
            return out
        return [self._submit_one(idx, call, shards) for call in query.calls]

    def _submit_one(self, idx: Index, call: Call, shards=None) -> "Deferred":
        if call.name == "Count":
            return self._submit_count(idx, call, shards, pipeline=True)
        if call.name in ("Sum", "Min", "Max"):
            return self._submit_bsi_aggregate(idx, call, shards,
                                              pipeline=True)
        if call.name == "TopN":
            return self._submit_topn(idx, call, shards, pipeline=True)
        if call.name == "GroupBy":
            return self._submit_groupby(idx, call, shards, pipeline=True)
        if call.name in _BITMAP_CALLS:
            return self._submit_bitmap(idx, call, shards, pipeline=True)
        if call.name == "Options" and call.children:
            # unwrap so the CHILD pipelines (a serving wave of
            # Options-wrapped Counts must coalesce, not evaluate eagerly
            # on the dispatcher); result options apply at resolve time
            inner = self._submit_one(
                idx, options_child(call),
                options_restrict_shards(call, shards),
            )
            return Deferred(
                lambda: apply_options_result(idx, call, inner.result())
            )
        return Deferred(value=self._execute_call(idx, call, shards))

    def _execute_call(self, idx: Index, call: Call, shards=None):
        name = call.name
        if name == "Options":
            return self._execute_options(idx, call, shards)
        if name in ("Set",):
            return self._execute_set(idx, call)
        if name == "Clear":
            return self._execute_clear(idx, call)
        if name == "ClearRow":
            return self._execute_clear_row(idx, call, shards)
        if name == "Store":
            return self._execute_store(idx, call, shards)
        if name == "Count":
            return self._execute_count(idx, call, shards)
        if name == "TopN":
            return self._execute_topn(idx, call, shards)
        if name in ("Sum", "Min", "Max"):
            return self._execute_bsi_aggregate(idx, call, shards)
        if name == "Rows":
            return self._execute_rows(idx, call, shards)
        if name == "GroupBy":
            return self._execute_groupby(idx, call, shards)
        if name == "IncludesColumn":
            return self._execute_includes_column(idx, call, shards)
        if name == "SetRowAttrs":
            return self._execute_set_row_attrs(idx, call)
        if name == "SetColumnAttrs":
            return self._execute_set_column_attrs(idx, call)
        if name in _BITMAP_CALLS:
            return self._execute_bitmap(idx, call, shards)
        raise PQLError(f"unsupported call {name!r}")

    # ------------------------------------------------------ key translation

    def _resolve_key(self, namespace: str, key: str, create: bool):
        """Key → ID. Known keys resolve locally; unknown ones go through
        key_resolver (the coordinator in a cluster — reference: translation
        primary) when wired, else the local store."""
        id_ = self.holder.translate.translate_one(namespace, key, create=False)
        if id_ is not None:
            return id_
        if self.key_resolver is not None:
            return self.key_resolver(namespace, key, create)
        if create:
            return self.holder.translate.translate_one(namespace, key, create=True)
        return None

    def _translate_col(self, idx: Index, col, create: bool = False):
        from pilosa_tpu.storage.translate import column_namespace

        if isinstance(col, int):
            return col
        if not idx.keys:
            raise PQLError(
                f"column key {col!r} on index {idx.name!r} without keys=true"
            )
        return self._resolve_key(column_namespace(idx.name), str(col), create)

    def _translate_row(self, idx: Index, field, row, create: bool = False):
        from pilosa_tpu.storage.translate import row_namespace

        if isinstance(row, int):
            return row
        if not field.options.keys:
            raise PQLError(
                f"row key {row!r} on field {field.name!r} without keys=true"
            )
        return self._resolve_key(
            row_namespace(idx.name, field.name), str(row), create
        )

    def _keys_of(self, namespace: str, ids):
        keys = self.holder.translate.keys_of(namespace, ids)
        if self.key_backfill is not None and any(k is None for k in keys):
            self.key_backfill()
            keys = self.holder.translate.keys_of(namespace, ids)
        return keys

    def _column_keys(self, idx: Index, columns):
        from pilosa_tpu.storage.translate import column_namespace

        return self._keys_of(column_namespace(idx.name), [int(c) for c in columns])

    def _row_keys(self, idx: Index, field, rows):
        from pilosa_tpu.storage.translate import row_namespace

        return self._keys_of(
            row_namespace(idx.name, field.name), [int(r) for r in rows]
        )

    # --------------------------------------------------------------- shards

    def _shards(self, idx: Index, shards=None) -> list[int]:
        if shards is not None:
            return list(shards)
        return idx.available_shards()

    # ------------------------------------------------------ batched mapping
    #
    # One compiled program + one device sync per query (executor/batch.py).
    # Subclasses override the three hooks to change placement/reduction:
    # DistExecutor (parallel/dist.py) shards the stacked leaves over a mesh
    # and swaps the program builders for shard_map+psum versions.

    def _shard_block(self, shard_list: list[int]):
        """Block for a query's shard list, memoized on the LIST OBJECT:
        Index.available_shards returns one memoized list until the shard
        set changes, so steady-state queries reuse one block — skipping
        the per-query sort of (up to) thousands of shard ids, the padded
        layout build, and the cache-key construction. Explicit shard
        lists (Options(shards=)) miss the identity check and build
        fresh, as before."""
        key = id(shard_list)
        entry = self._block_memo.get(key)
        if entry is not None and entry[0] is shard_list:
            self._block_memo.move_to_end(key)
            return entry[1]
        block = self._make_block(shard_list)
        if len(self._block_memo) >= 64:
            # LRU, not wholesale clear: explicit Options(shards=) lists
            # never recur (fresh list object per query) and must not
            # evict the hot available_shards entry when they age out
            self._block_memo.popitem(last=False)
        self._block_memo[key] = (shard_list, block)
        return block

    def _make_block(self, shard_list: list[int]):
        return batch.ShardBlock(shard_list)

    def _leaf_put(self, block):
        """Optional device_put override for stacked leaves (mesh sharding;
        the block supplies the global row count for multi-host feeding)."""
        return None

    def _note_reduce(self, reduce_kind: str, out_shape: tuple) -> None:
        """Reduction-lane wire accounting hook, called once per device
        dispatch with the packed result shape. Single-device execution
        has no reduction wire — DistExecutor records its bytes here."""

    def _row_host(self, stacked, block):
        """Row-gather readback: device [padded, words] result → host
        array."""
        return _readback(stacked)

    def _program(self, structure, reduce_kind: str, leaf_ranks: tuple,
                 n_scalars: int):
        return batch.local_fn(structure, reduce_kind, leaf_ranks, n_scalars)

    def _groupby_level_program(self, filt_structure, n_filt: int,
                               n_scalars: int, n_gather: int, n_planes: int):
        return batch.local_groupby_level_fn(
            filt_structure, n_filt, n_scalars, n_gather, n_planes
        )

    # stage that times _groupby_operand_put's placements
    _operand_stage = "device.upload"

    def _groupby_operand_put(self, scalars):
        """Placement beside _leaf_put for the small per-dispatch operand
        of a GroupBy level: returns put(ci), which takes one chunk's
        candidate indices int32[C, n_gather] to the ONE int32 array the
        level program takes after its leaves: the index arrays end to
        end, then the scalars (batch.unpack_groupby_operand). One array,
        because every host argument of a program call is a transfer of
        its own."""
        tail = np.asarray(scalars, np.int32).reshape(-1)

        def put(ci):
            return self._operand_place(np.concatenate(
                [np.asarray(ci, np.int32).T.reshape(-1), tail]))

        return put

    def _operand_place(self, packed):
        import jax.numpy as jnp

        return jnp.asarray(packed)

    # entries of _placed_operands before it is cleared whole (a plain
    # dict under the interpreter's lock, as _operand_memo is: a racing
    # double placement of one key leaves either array, both right)
    PLACED_OPERANDS_MAX = 512

    def _level_operand(self, cand: np.ndarray, lo: int, hi: int,
                       padded: int, scalars, cand_key):
        """The placed operand of one chunk of a level: candidates
        cand[lo:hi] padded to ``padded`` rows of -1, then the scalars
        (_groupby_operand_put). With a ``cand_key`` (whatever determines
        ``cand``) the array placed for the same chunk and scalars before
        is handed to any number of calls (no program donates an
        argument), and only a miss builds, places and enters the operand
        stage."""
        if cand_key is not None:
            key = (cand_key, lo, hi, padded, tuple(scalars))
            operand = self._placed_operands.get(key)
            if operand is not None:
                return operand
        ci = cand[lo:hi]
        if padded > hi - lo:
            ci = np.concatenate(
                [ci, np.full((padded - (hi - lo), cand.shape[1]), -1,
                             np.int32)]
            )
        with stage(self._operand_stage):
            operand = self._groupby_operand_put(scalars)(ci)
        note_groupby_operand_placement()
        if cand_key is not None:
            if len(self._placed_operands) >= self.PLACED_OPERANDS_MAX:
                self._placed_operands.clear()
            self._placed_operands[key] = operand
        return operand

    @staged("executor.operands")
    def _eval_operands(self, idx: Index, compiled: _Compiled, block,
                       extra_leaves=(), memoize: bool = True):
        """Resolve a compiled query's device leaves; scalars stay host
        ints (converted at dispatch — the micro-batch path ships a whole
        group's scalars as one array).

        Repeat (plan, block) assemblies are memoized for the duration of
        one residency generation: per-leaf cache lookups cost ~10 us of
        lock+LRU bookkeeping per query, which at micro-batched dispatch
        rates is a measurable slice of the serving path's host budget.
        Any write/evict/invalidate bumps the generation (residency.py),
        which eagerly clears the memo (generation listener registered in
        __init__). Correctness does not rest on the clears: every entry
        carries the generation read BEFORE its assembly and a hit must
        match the CURRENT generation, so a racing store of pre-write
        leaves into a just-cleared memo (assembler thread preempted
        across a write) produces an entry that can never be served.
        Identity (`is`) checks guard against id() reuse after
        plan-cache or block-memo eviction. Only plan-cache-resident
        plans (compiled.memoizable) are memoized — per-call plan
        objects (TopN phase 2, const0-degenerate trees) would fill the
        memo with dead-on-arrival entries whose wholesale clear at the
        size bound evicts the hot entries the memo exists for. A hit
        re-touches its leaves' residency LRU position (entry[5]): a
        served-on-every-query leaf must not look LRU-cold and become
        the first eviction victim under pressure."""
        memoize = memoize and not extra_leaves and compiled.memoizable
        if memoize:
            cache = residency.global_row_cache()
            if cache is not self._listened_cache:
                # the global cache can be swapped after construction
                # (Server.open's budget-sized cache); re-home the eager
                # clear listener so evictions on the LIVE cache drop our
                # array references, and dump entries from the old one.
                # Unregister from the old cache first: its bumps would
                # otherwise keep clearing a memo that no longer tracks
                # it, and a swap-back would stack duplicate listeners.
                # Locked double-check: concurrent serving threads racing
                # the swap must not both register.
                with self._rehome_lock:
                    if cache is not self._listened_cache:
                        if self._listened_cache is not None:
                            self._listened_cache.remove_generation_listener(
                                self._clear_operand_memo
                            )
                        cache.add_generation_listener(
                            self._clear_operand_memo
                        )
                        self._listened_cache = cache
                        self._operand_memo.clear()
            gen = cache.generation
            if gen != self._operand_memo_gen:
                self._operand_memo.clear()
                self._operand_memo_gen = gen
            mkey = (id(compiled), id(block))
            hit = self._operand_memo.get(mkey)
            if (hit is not None and hit[0] is compiled
                    and hit[1] is block and hit[4] == gen):
                cache.touch(hit[5])
                self._note_operands(idx, compiled, block, memo_hit=True)
                return hit[2], hit[3]
        put = self._leaf_put(block)
        leaves = self._resolve_leaves(idx, compiled, block, put)
        leaves.extend(extra_leaves)
        if not leaves:
            leaves = [batch.stacked_leaf(idx, _ZeroSpec(), block, put)]
        scalars = tuple(int(s) for s in compiled.scalars)
        if memoize:
            if len(self._operand_memo) >= 512:
                self._operand_memo.clear()
            leaf_keys = batch.leaf_keys(idx, compiled.specs, block)
            self._operand_memo[mkey] = (compiled, block, leaves, scalars,
                                        gen, leaf_keys)
        return leaves, scalars

    def _dispatch(self, node, reduce_kind: str, leaves, scalars):
        import jax.numpy as jnp

        fn = self._program(
            node, reduce_kind, tuple(l.ndim - 1 for l in leaves), len(scalars)
        )
        # enqueue time on the device stream; the cost plane attributes
        # the stage's own elapsed time to the active request/call node
        site = stage("device.dispatch", reduce=reduce_kind)
        with site:
            out = fn(*leaves, *(jnp.asarray(s, jnp.int32) for s in scalars))
        cost = current_cost()
        if cost is not None:
            cost.note_dispatch(site.elapsed)
        self._note_reduce(reduce_kind, out.shape)
        return out

    def _resolve_leaves(self, idx: Index, compiled: _Compiled, block,
                        put) -> list:
        """Resolve a plan's stacked device leaves, with cost-plane
        accounting: shard-heat access recording + per-leaf PROFILE
        records (field, cache hit, containers decoded by type, bytes
        uploaded — deltas of the request context around each leaf)."""
        cost = current_cost()
        self._note_operands(idx, compiled, block, memo_hit=False,
                            cost=cost)
        node = (cost.current if cost is not None
                and cost.profile is not None else None)
        if node is None:
            return [batch.stacked_leaf(idx, spec, block, put)
                    for spec in compiled.specs]
        leaves = []
        for spec in compiled.specs:
            snap = (cost.row_cache_hits, cost.c_array, cost.c_bitmap,
                    cost.c_run, cost.device_bytes)
            leaves.append(batch.stacked_leaf(idx, spec, block, put))
            rec = {
                "field": getattr(spec, "field", None),
                "cacheHit": cost.row_cache_hits > snap[0],
                "containers": {"array": cost.c_array - snap[1],
                               "bitmap": cost.c_bitmap - snap[2],
                               "run": cost.c_run - snap[3]},
                "bytesMoved": cost.device_bytes - snap[4],
            }
            row = getattr(spec, "row", None)
            if row is not None:
                rec["row"] = int(row)
            node.leaves.append(rec)
        return leaves

    def _note_operands(self, idx: Index, compiled: _Compiled, block,
                       memo_hit: bool, cost=None) -> None:
        """Request-level accounting for one operand assembly: shards
        touched, operand-memo hit flag, and per-(index, field, shard)
        heat — the admission signal /debug/heatmap serves (storage/
        heat.py). Recorded only inside an active cost context (the
        serving path), so background work cannot skew tenant heat."""
        if cost is None:
            cost = current_cost()
            if cost is None:
                return
        cost.note_shards(len(block.shards))
        if memo_hit and cost.current is not None:
            cost.current.operand_memo_hit = True
        fields = {spec.field for spec in compiled.specs
                  if getattr(spec, "field", None) is not None}
        if fields:
            # one batched heat record per assembly (ONE lock round trip);
            # scope-qualified like every residency key
            global_heat().record_access_many(idx.name, fields,
                                             block.shards,
                                             scope=idx.scope)

    def _batched_eval(self, idx: Index, compiled: _Compiled, block,
                      reduce_kind: str, extra_leaves=()):
        leaves, scalars = self._eval_operands(idx, compiled, block, extra_leaves)
        return self._dispatch(compiled.node, reduce_kind, leaves, scalars)

    # ------------------------------------------------- query micro-batching
    #
    # Pipelined (submit) reductions are coalesced: queries sharing one
    # program shape (structure, reduce kind, operand shapes) accumulate in
    # a pending group and dispatch as ONE device program of
    # ``microbatch_max`` queries (batch.local_fn_batched) — amortizing the
    # fixed per-dispatch launch cost that otherwise rivals the device
    # compute of an entire query, and serving the whole group's results
    # with one [B, ...] readback. A group also flushes when any of its
    # Deferreds resolves, so results are never held hostage. Leaves are
    # captured at submit time: writes between submit and flush patch the
    # residency cache functionally (new arrays), so an in-flight query
    # keeps its snapshot.

    def _microbatch_enqueue(self, node, reduce_kind: str, leaves, scalars):
        """Queue one pipelined query; returns a thunk yielding this
        query's packed host result, or None when micro-batching is off
        (then the caller dispatches per-query)."""
        if self.microbatch_max <= 1:
            return None
        shapes = tuple(tuple(l.shape) for l in leaves)
        key = (node, reduce_kind, shapes, len(scalars))
        with self._mb_lock:
            group = self._pending.get(key)
            if group is None:
                # group size: microbatch_max, capped so the batched
                # program's total PER-DEVICE argument bytes stay under
                # budget (XLA accounts each parameter separately — see
                # MICROBATCH_ARG_BUDGET; mesh-sharded leaves cost
                # nbytes/n_devices per chip)
                per_query = (sum(l.nbytes for l in leaves)
                             // self.arg_shard_factor)
                limit = max(1, min(
                    self.microbatch_max,
                    self.microbatch_arg_budget // max(per_query, 1),
                ))
                # floor to a power of two: the flush pads batches to
                # pow2 sizes, so a non-pow2 cap (budget-derived, e.g. 5)
                # would reintroduce an unbounded program-shape family
                limit = 1 << (limit.bit_length() - 1)
                group = self._pending[key] = {"rows": [], "out": None,
                                              "limit": limit}
            i = len(group["rows"])
            group["rows"].append((tuple(leaves), scalars))
            if len(group["rows"]) >= group["limit"]:
                self._flush_group_locked(key, group)

        def read():
            with self._mb_lock:
                if group["out"] is None:
                    self._flush_group_locked(key, group)
                out = group["out"]
            if not isinstance(out, np.ndarray):
                out = _readback(out)  # blocking, outside the lock
                with self._mb_lock:
                    group["out"] = out
            return out[i]

        return read

    def _program_batched(self, structure, reduce_kind: str, leaf_ranks: tuple,
                         n_scalars: int, n_queries: int):
        """Micro-batched program builder hook (one program, ``n_queries``
        same-shape queries). DistExecutor swaps in the shard_map+psum
        version so the mesh path keeps micro-batching."""
        return batch.local_fn_batched(structure, reduce_kind, leaf_ranks,
                                      n_scalars, n_queries)

    def _flush_group_locked(self, key, group) -> None:
        """Dispatch a pending group as one program (caller holds _mb_lock).

        The batch axis pads to the next power of two (duplicating the
        last row — same array objects, so no host copies) and readers
        index only the real rows. Without this, a serving wave of K
        concurrent queries dispatches a K-row program for EVERY distinct
        K, and XLA compiles each batch size from scratch — a wave
        pipeline under varied load would spend its time in the compiler.
        Padding bounds the shape family to {1,2,4,8,16} per structure."""
        if group["out"] is not None:
            return
        node, reduce_kind, shapes, n_scalars = key
        rows = group["rows"]
        n_prog = min(group["limit"], next_pow2(len(rows)))
        padded = rows + [rows[-1]] * (n_prog - len(rows))
        fn = self._program_batched(
            node, reduce_kind, tuple(len(s) - 1 for s in shapes),
            n_scalars, n_prog,
        )
        args = [leaf for leaves, _ in padded for leaf in leaves]
        if n_scalars:
            args.append(np.asarray([s for _, s in padded], np.int32))
        # the stage lands in the trace of whichever request flushed the
        # group — truthful attribution: that request paid the dispatch,
        # its batchmates ride for free (tagged with the shared size);
        # the cost plane attributes the dispatch the same way
        site = stage("device.dispatch", reduce=reduce_kind, batch=len(rows))
        with site:
            group["out"] = fn(*args)
        cost = current_cost()
        if cost is not None:
            cost.note_dispatch(site.elapsed, batch=len(rows))
        self._note_reduce(reduce_kind, group["out"].shape)
        if self._pending.get(key) is group:
            del self._pending[key]

    # --------------------------------------------------------- bitmap calls

    def _execute_bitmap(self, idx: Index, call: Call, shards=None) -> RowResult:
        return self._submit_bitmap(idx, call, shards).result()

    def _submit_bitmap(self, idx: Index, call: Call, shards=None,
                       pipeline: bool = False) -> "Deferred":
        """Row-materializing calls: the fused program is enqueued at
        submit time; the [padded, words] readback (the only multi-row
        device→host transfer in the system) happens at result()."""
        compiled = self._compile_cached(idx, call)
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return Deferred(
                value=self._finish_row_result(idx, call, RowResult({}))
            )
        block = self._shard_block(shard_list)
        stacked = self._batched_eval(idx, compiled, block, "row")
        # row attrs snapshot at SUBMIT time, like the bitmap data (a
        # SetRowAttrs between submit and result must not tear the
        # result); column-key translation stays at result() — the
        # translate log is append-only, so ids→keys cannot change
        attrs = self._row_result_attrs(idx, call)

        def finish() -> RowResult:
            host = self._row_host(stacked, block)
            segments = {}
            for i, shard in enumerate(block.shards):
                if host[i].any():
                    # copy: a view would pin the whole padded readback
                    segments[shard] = host[i].copy()
            res = RowResult(segments, attrs=attrs)
            if idx.keys:
                res.keys = [
                    k for k in self._column_keys(idx, res.columns().tolist())
                    if k is not None
                ]
            return res

        if pipeline:
            return Deferred(finish)
        return Deferred(value=finish())

    def _row_result_attrs(self, idx: Index, call: Call) -> dict:
        """Row attrs for a plain Row call (reference: Row results carry
        the row's attribute set)."""
        if call.name == "Row" and call.condition_field()[0] is None:
            try:
                field_name, row = self._row_field_and_value(call)
                field = idx.field(field_name)
                if field is not None and field.row_attrs is not None:
                    row_id = self._translate_row(idx, field, row, create=False)
                    if row_id is not None:
                        return field.row_attrs.attrs(row_id)
            except PQLError:
                pass
        return {}

    def _finish_row_result(self, idx: Index, call: Call, res: RowResult) -> RowResult:
        """Attach row attrs (plain Row calls) and translated column keys."""
        res.attrs = self._row_result_attrs(idx, call) or res.attrs
        if idx.keys:
            res.keys = [
                k for k in self._column_keys(idx, res.columns().tolist())
                if k is not None
            ]
        return res

    def _execute_count(self, idx: Index, call: Call, shards=None) -> int:
        return self._submit_count(idx, call, shards).result()

    def _submit_count(self, idx: Index, call: Call, shards=None,
                      pipeline: bool = False) -> "Deferred":
        if len(call.children) != 1:
            raise PQLError("Count requires exactly one child call")
        compiled = self._compile_cached(idx, call.children[0], wrap="count")
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return Deferred(value=0)
        block = self._shard_block(shard_list)
        return self._submit_reduction(
            idx, compiled, block, "count", pipeline,
            lambda packed: int(batch.merge_split(packed)),
        )

    def _submit_reduction(self, idx: Index, compiled: _Compiled, block,
                          reduce_kind: str, pipeline: bool,
                          finish) -> "Deferred":
        """Shared dispatch tail for pipelined scalar reductions (Count and
        the BSI aggregates): micro-batch same-shape pipelined queries into
        one program, else dispatch per query; ``finish`` maps this query's
        packed host row to its result."""
        if pipeline:
            leaves, scalars = self._eval_operands(idx, compiled, block)
            read = self._microbatch_enqueue(
                compiled.node, reduce_kind, leaves, scalars
            )
            if read is not None:
                return Deferred(lambda: finish(read()))
            packed = self._dispatch(compiled.node, reduce_kind, leaves,
                                    scalars)
        else:
            packed = self._batched_eval(idx, compiled, block, reduce_kind)
        return Deferred(lambda: finish(_readback(packed)))

    def includes_target(self, idx: Index, call: Call, shards=None):
        """Resolve IncludesColumn's target: (numeric column, shard), or
        None when the answer is trivially False (unknown column key, or
        an Options(shards=) restriction excluding the column's shard).
        Shared by the single-node and cluster dispatch paths so the
        key/shard semantics cannot drift."""
        col = call.arg("column")
        if col is None:
            raise PQLError("IncludesColumn requires column=")
        if len(call.children) != 1:
            raise PQLError("IncludesColumn requires one child call")
        col = self._translate_col(idx, col, create=False)
        if col is None:
            return None  # unknown column key: not included
        shard = shard_of(col)
        if shards is not None and shard not in shards:
            return None  # Options(shards=) excludes the column's shard
        return col, shard

    def _execute_includes_column(self, idx: Index, call: Call,
                                 shards=None) -> bool:
        target = self.includes_target(idx, call, shards)
        if target is None:
            return False
        col, shard = target
        pos = position(col)
        compiled = self._compile_cached(idx, call.children[0])
        words = _readback(compiled.eval(idx, shard))
        return bool((words[pos // 32] >> np.uint32(pos % 32)) & np.uint32(1))

    def _execute_options(self, idx: Index, call: Call, shards=None):
        res = self._execute_call(
            idx, options_child(call), options_restrict_shards(call, shards)
        )
        return apply_options_result(idx, call, res)

    # -------------------------------------------------------------- compile

    @staged("executor.plan")
    def _compile_cached(self, idx: Index, call: Call,
                        wrap: str | None = None,
                        build: Callable | None = None) -> _Compiled:
        """_compile with a plan memo. parse() memoizes query text to one
        immutable Call tree, so the tree's identity keys repeated queries
        — the serving hot path. A cached plan revalidates in two identity
        checks plus one int compare: the Call tree, the Index object (a
        delete_index + recreate under the same name restarts plan_epoch,
        so the epoch alone could alias a stale plan; the index is held
        weakly so the cache never pins a deleted index's bitmaps), and
        the index's schema epoch — bumped on field create/delete, which
        covers every compiled-in field property (views from time quantum,
        BSI base/bit_depth from min/max) since FieldOptions are immutable
        after creation. Plans whose tree degenerated to const0 (e.g. a
        row key unknown at compile time that a later write may create)
        are not cached."""
        key = (idx.name, id(call), wrap)
        entry = self._plan_cache.get(key)
        if entry is not None:
            call_ref, idx_ref, epoch, compiled = entry
            if (call_ref is call and idx_ref() is idx
                    and epoch == idx.plan_epoch):
                cost = current_cost()
                if cost is not None:
                    cost.note_plan(True)
                return compiled
        cost = current_cost()
        if cost is not None:
            cost.note_plan(False)
        # epoch snapshot BEFORE compiling: DDL racing the compile bumps
        # the epoch, so the entry (tagged pre-DDL) fails its next
        # validation instead of serving the stale plan under the new epoch
        epoch = idx.plan_epoch
        compiled = (self._compile(idx, call, wrap=wrap) if build is None
                    else build())
        if not _node_has_const0(compiled.node):
            if len(self._plan_cache) >= self.PLAN_CACHE_MAX:
                self._plan_cache.clear()
            self._plan_cache[key] = (call, weakref.ref(idx), epoch,
                                     compiled)
            compiled.memoizable = True
        return compiled

    def _compile(self, idx: Index, call: Call, wrap: str | None = None) -> _Compiled:
        specs: list = []
        scalars: list = []
        node = self._compile_node(idx, call, specs, scalars)
        if wrap == "count":
            node = ("count", node)
        return _Compiled(node, specs, scalars)

    def _compile_node(self, idx: Index, call: Call, specs, scalars):
        name = call.name
        if name == "Row" or name == "Range":
            return self._compile_row(idx, call, specs, scalars)
        if name in ("Union", "Intersect", "Xor"):
            if not call.children:
                return ("const0",)
            tag = {"Union": "or", "Intersect": "and", "Xor": "xor"}[name]
            node = self._compile_node(idx, call.children[0], specs, scalars)
            for child in call.children[1:]:
                node = (tag, node, self._compile_node(idx, child, specs, scalars))
            return node
        if name == "Difference":
            if not call.children:
                return ("const0",)
            node = self._compile_node(idx, call.children[0], specs, scalars)
            for child in call.children[1:]:
                node = ("diff", node, self._compile_node(idx, child, specs, scalars))
            return node
        if name == "Not":
            if len(call.children) != 1:
                raise PQLError("Not requires exactly one child call")
            exists = self._existence_node(idx, specs)
            return ("diff", exists, self._compile_node(idx, call.children[0], specs, scalars))
        if name == "All":
            return self._existence_node(idx, specs)
        if name == "Shift":
            if len(call.children) != 1:
                raise PQLError("Shift requires exactly one child call")
            n = call.arg("n", 1)
            scalars.append(int(n))
            return (
                "shift",
                self._compile_node(idx, call.children[0], specs, scalars),
                len(scalars) - 1,
            )
        raise PQLError(f"call {name!r} is not a bitmap (row-producing) call")

    def _compile_row(self, idx: Index, call: Call, specs, scalars):
        cond_field, cond = call.condition_field()
        if cond is not None:
            return self._compile_bsi_compare(idx, cond_field, cond, specs, scalars)
        field_name, row = self._row_field_and_value(call)
        field = idx.field(field_name)
        if field is None:
            raise PQLError(f"field {field_name!r} not found")
        if not isinstance(row, int):
            row = self._translate_row(idx, field, row, create=False)
            if row is None:
                return ("const0",)  # unknown key → empty row
        if row < 0:
            return ("const0",)  # negative rows cannot exist
        views: tuple[str, ...]
        t_from, t_to = call.arg("from"), call.arg("to")
        if t_from is not None or t_to is not None:
            if field.options.type != TYPE_TIME:
                raise PQLError("from/to args require a time field")
            views = tuple(
                views_by_time_range(
                    VIEW_STANDARD,
                    field.options.time_quantum,
                    _parse_time(t_from),
                    _parse_time(t_to),
                )
            )
        else:
            views = (VIEW_STANDARD,)
        specs.append(_RowSpec(field_name, views, row))
        return ("leaf", len(specs) - 1)

    def _compile_bsi_compare(self, idx: Index, field_name: str, cond: Condition,
                             specs, scalars):
        field = idx.field(field_name)
        if field is None:
            raise PQLError(f"field {field_name!r} not found")
        if field.options.type != TYPE_INT:
            raise PQLError(f"comparison on non-int field {field_name!r}")
        if cond.op == "><":
            lo, hi = cond.value
            if lo > hi:
                return ("const0",)
            ge = self._compile_bsi_compare(
                idx, field_name, Condition(">=", lo), specs, scalars
            )
            le = self._compile_bsi_compare(
                idx, field_name, Condition("<=", hi), specs, scalars
            )
            return ("and", ge, le)

        base = field.options.base
        depth = field.options.bit_depth
        max_stored = (1 << depth) - 1
        value = cond.value
        op = cond.op
        # isinstance check, not float(value): fractional predicates only
        # ever arrive as parser floats, and float(huge_int) overflows
        # where the pred>max_stored clamp below handles it fine.
        if isinstance(value, float) and not value.is_integer():
            # Stored values are integers, so a fractional predicate maps
            # exactly onto the integer lattice: x < 1.5 ⇔ x <= 1,
            # x > 1.5 ⇔ x >= 2, and ==/!= degenerate. Plain int() would
            # turn x < 1.5 into x < 1, wrongly excluding x == 1.
            if op == "==":
                return ("const0",)
            if op == "!=":
                return self._bsi_exists_node(field, specs)
            if math.isinf(value):
                # a ~310+-digit literal with a fractional part parses to
                # ±inf; floor() would raise, so clamp directly
                everything = (value > 0) == (op in ("<", "<="))
                return (self._bsi_exists_node(field, specs) if everything
                        else ("const0",))
            fl = math.floor(value)
            value, op = (fl, "<=") if op in ("<", "<=") else (fl + 1, ">=")
        pred = int(value) - base
        cond = Condition(op, value)
        exists = self._bsi_exists_node(field, specs)
        # range-clamp: out-of-range predicates degenerate to empty/universe
        if pred < 0:
            if cond.op in ("<", "<=", "=="):
                return ("const0",)
            return exists  # >, >=, != of anything stored
        if pred > max_stored:
            if cond.op in (">", ">=", "=="):
                return ("const0",)
            return exists
        planes_i = self._planes_index(field, specs)
        scalars.append(pred)
        return ("bsicmp", cond.op, planes_i, exists, len(scalars) - 1)

    def _planes_index(self, field, specs) -> int:
        for i, s in enumerate(specs):
            if isinstance(s, _PlanesSpec) and s.field == field.name:
                return i
        specs.append(_PlanesSpec(field.name, field.options.bit_depth))
        return len(specs) - 1

    def _bsi_exists_node(self, field, specs):
        specs.append(_RowSpec(field.name, (field.bsi_view_name(),), BSI_EXISTS_ROW))
        return ("leaf", len(specs) - 1)

    def _existence_node(self, idx: Index, specs):
        if not idx.track_existence:
            raise PQLError("Not/All require trackExistence on the index")
        specs.append(_RowSpec(EXISTENCE_FIELD, (VIEW_STANDARD,), 0))
        return ("leaf", len(specs) - 1)

    @staticmethod
    def _row_field_and_value(call: Call):
        for k, v in call.args.items():
            if k not in _RESERVED_ARGS and not isinstance(v, Condition):
                return k, v
        raise PQLError(f"{call.name} requires a field=row argument")

    # ------------------------------------------------------- BSI aggregates

    def _execute_bsi_aggregate(self, idx: Index, call: Call, shards=None) -> ValCount:
        return self._submit_bsi_aggregate(idx, call, shards).result()

    def _submit_bsi_aggregate(self, idx: Index, call: Call, shards=None,
                              pipeline: bool = False) -> "Deferred":
        field_name = call.arg("field") or call.arg("_field")
        if field_name is None:
            raise PQLError(f"{call.name} requires field=")
        field = idx.field(field_name)
        if field is None or field.options.type != TYPE_INT:
            raise PQLError(f"{call.name} requires an int field")
        filt_call = call.children[0] if call.children else None

        def build() -> _Compiled:
            specs: list = []
            scalars: list = []
            planes_i = self._planes_index(field, specs)
            filt_node = (self._compile_node(idx, filt_call, specs, scalars)
                         if filt_call else None)
            if call.name == "Sum":
                node = ("bsisum", planes_i, filt_node)
            else:
                node = ("bsiminmax", 1 if call.name == "Max" else 0,
                        planes_i, filt_node)
            return _Compiled(node, specs, scalars)

        compiled = self._compile_cached(idx, call, wrap="agg", build=build)
        base = field.options.base

        shard_list = self._shards(idx, shards)
        if not shard_list:
            return Deferred(value=ValCount(0, 0))
        block = self._shard_block(shard_list)

        if call.name == "Sum":
            reduce_kind = "bsisum"

            def finish(packed) -> ValCount:
                merged = batch.merge_split(packed)
                # [depth + 1]: plane counts ++ n
                count = int(merged[-1])
                total = sum(int(c) << i
                            for i, c in enumerate(merged[:-1].tolist()))
                return ValCount(total + base * count, count)
        else:
            reduce_kind = "max" if call.name == "Max" else "min"

            def finish(packed) -> ValCount:
                packed = np.asarray(packed)  # [best, count_lo, count_hi]
                best = int(packed[0])
                count = int(batch.merge_split(packed[1:]))
                if count == 0:
                    return ValCount(0, 0)
                return ValCount(best + base, count)

        return self._submit_reduction(
            idx, compiled, block, reduce_kind, pipeline, finish,
        )

    # ----------------------------------------------------------------- TopN

    def _execute_topn(self, idx: Index, call: Call, shards=None) -> list[Pair]:
        return self._submit_topn(idx, call, shards).result()

    def _submit_topn(self, idx: Index, call: Call, shards=None,
                     pipeline: bool = False) -> "Deferred":
        """TopN with a pipelineable phase 2. Phase 1 (ranked-cache
        candidates) is host-only; phase 2 — the exact recount over the
        stacked candidate matrix — is one ``countrows`` device program,
        which under ``submit()`` micro-batches with other pipelined TopNs
        of the same shape (candidate lists pad to the next power of two
        so same-field TopN streams share one program shape)."""
        field_name = call.arg("_field") or call.arg("field")
        if field_name is None:
            raise PQLError("TopN requires a field")
        field = idx.field(field_name)
        if field is None:
            raise PQLError(f"field {field_name!r} not found")
        n = call.arg("n", 10)
        filt_call = call.children[0] if call.children else None
        # TopN plans per request: phase 1 reads the ranked caches of the
        # live fragments, then the filter compiles
        with stage("executor.plan"):
            shard_list = self._shards(idx, shards)
            if not shard_list:
                return Deferred(value=[])
            view = field.view(VIEW_STANDARD)

            explicit_ids = call.arg("ids")
            if explicit_ids is not None:
                candidates = sorted(int(i) for i in explicit_ids)
            else:
                # phase 1: per-shard candidates from the ranked caches,
                # folded by the view once per version of it
                overfetch = max(n * TOPN_CANDIDATE_FACTOR, n + 10)
                candidates = list(view.topn_fold(
                    shard_list, overfetch, keep=shards is None,
                )) if view else []
            candidates = self._filter_topn_candidates(field, call, candidates)
            if not candidates:
                return Deferred(value=[])

            # phase 2: exact recount of every candidate across all shards —
            # countrows programs over stacked candidate matrices. The
            # candidate axis is CHUNKED to the per-device matrix byte budget
            # (a candidate row costs shards×128KiB; see
            # TOPN_MATRIX_BUDGET_BYTES) and each chunk pads to the chunk's
            # power-of-two size with ZERO rows (zeros match no write event,
            # so the residency patch routing stays exact) — so chunks of one
            # query AND pipelined TopN streams bucket into shared shapes and
            # micro-batch together.
            n_real = len(candidates)
            specs: list = []
            scalars: list = []
            filt_node = (
                self._compile_node(idx, filt_call, specs, scalars) if filt_call else None
            )
            node = ("countrows", len(specs), filt_node)
        block = self._shard_block(shard_list)
        bytes_per_cand = (
            block.padded * WORDS_PER_SHARD * 4 // self.arg_shard_factor
        )
        chunk_rows = max(
            1, min(next_pow2(n_real),
                   TOPN_MATRIX_BUDGET_BYTES // max(bytes_per_cand, 1))
        )
        chunk_rows = 1 << (chunk_rows.bit_length() - 1)  # round down to pow2

        # filter leaves/scalars are chunk-invariant: resolve once
        base_leaves, scalar_ints = self._eval_operands(
            idx, _Compiled(node, specs, scalars), block, memoize=False,
        ) if specs else ([], tuple(int(s) for s in scalars))
        put = self._leaf_put(block)

        reads = []  # one (chunk_candidates, result thunk) per chunk
        for lo in range(0, n_real, chunk_rows):
            chunk = candidates[lo:lo + chunk_rows]
            with stage("executor.operands"):
                matrix = batch.stacked_matrix(
                    idx, field_name, view, chunk, block, put,
                    pad_rows=chunk_rows - len(chunk),
                )
            leaves = base_leaves + [matrix]
            read = (self._microbatch_enqueue(node, "countrows", leaves,
                                             scalar_ints)
                    if pipeline else None)
            if read is None:
                packed = self._dispatch(node, "countrows", leaves,
                                        scalar_ints)
                read = (lambda p: lambda: _readback(p))(packed)
            reads.append((chunk, read))

        def finish() -> list[Pair]:
            # each chunk's packed [2, chunk_rows] split sums; the slice
            # drops the all-zero pad rows (always zero counts)
            totals: list[int] = []
            for chunk, read in reads:
                totals.extend(
                    batch.merge_split(np.asarray(read()))[:len(chunk)]
                    .tolist()
                )
            # threshold= : minimum global count to be included
            # (SURVEY-LOW surface, Appendix B — the upstream arg's exact
            # version gate is unverifiable with the mount empty;
            # conservative reading: a post-recount filter, so it never
            # changes which rows WOULD have qualified, only trims the
            # result). Applied after the exact phase-2 counts; the
            # cluster path strips it from mapped sub-queries and applies
            # it after the cross-node merge.
            floor = max(1, int(call.arg("threshold", 0) or 0))
            order = sorted(
                (int(-c), r)
                for r, c in zip(candidates, totals) if c >= floor
            )
            if n:
                order = order[:n]
            return self._finish_pairs(
                idx, field, [Pair(r, -negc) for negc, r in order]
            )

        return Deferred(finish)

    @staticmethod
    def _filter_topn_candidates(field, call: Call, candidates: list[int]) -> list[int]:
        """TopN(attrName=, attrValue=): keep candidate rows whose attrs
        match (reference TopN attribute filter). One bulk read for the
        whole candidate set — the cross-shard overfetch makes this an
        O(candidates) list, and a per-candidate query loop would pay one
        sqlite round trip each."""
        attr_name = call.arg("attrName")
        if attr_name is None or field.row_attrs is None:
            return candidates
        attr_value = call.arg("attrValue")
        attr_map = field.row_attrs.bulk(candidates) if candidates else {}
        return [
            r for r in candidates
            if attr_map.get(r, {}).get(attr_name) == attr_value
        ]

    def _finish_pairs(self, idx: Index, field, pairs: list[Pair]) -> list[Pair]:
        """Attach row keys to TopN pairs for keyed fields."""
        if field.options.keys and pairs:
            keys = self._row_keys(idx, field, [p.id for p in pairs])
            for p, k in zip(pairs, keys):
                p.key = k
        return pairs

    # ----------------------------------------------------------------- Rows

    def _execute_rows(self, idx: Index, call: Call, shards=None):
        field_name = call.arg("_field") or call.arg("field")
        field = idx.field(field_name) if field_name else None
        like = call.arg("like")
        if like is not None and (field is None or not field.options.keys):
            raise PQLError("Rows(like=) requires a field with keys=true")
        ids = self._rows_ids(idx, call, shards)
        if field is not None and field.options.keys:
            keys = [k for k in self._row_keys(idx, field, ids) if k is not None]
            if like is not None:
                import re

                pattern = re.compile(
                    "^" + ".*".join(re.escape(p) for p in str(like).split("%")) + "$"
                )
                keys = [k for k in keys if pattern.match(k)]
            return keys
        return ids

    def _rows_ids(self, idx: Index, call: Call, shards=None) -> list[int]:
        field_name = call.arg("_field") or call.arg("field")
        if field_name is None:
            raise PQLError("Rows requires a field")
        field = idx.field(field_name)
        if field is None:
            raise PQLError(f"field {field_name!r} not found")
        limit = call.arg("limit", 0)
        previous = call.arg("previous")
        column = call.arg("column")
        view = field.view(VIEW_STANDARD)
        if view is None:
            return []
        if column is not None:
            frag = view.fragment(shard_of(int(column)))
            out = (sorted(set(frag.rows_containing(position(int(column)))))
                   if frag is not None else [])
        else:
            # the view folds its fragments' non-empty rows once per
            # version of it; the tuple is its own, sliced into a new list
            out = view.rows_fold(self._shards(idx, shards),
                                 keep=shards is None)
        if previous is not None:
            out = out[bisect_right(out, int(previous)):]
        if limit:
            out = out[: int(limit)]
        return list(out)

    # -------------------------------------------------------------- GroupBy

    def _groupby_prelude(self, idx: Index, call: Call, shards=None):
        """Shared GroupBy argument parsing/validation: returns
        (limit, filter call|None, aggregate int field|None, dims, having
        predicate|None) where dims is [(field_name, row_ids), ...]; dims
        is empty when any dimension has no rows (→ empty result)."""
        if not call.children or any(c.name != "Rows" for c in call.children):
            raise PQLError("GroupBy requires Rows(...) children")
        limit = call.arg("limit", 0)
        filt_call = call.arg("filter")
        if not isinstance(filt_call, Call):
            filt_call = None

        # aggregate=Sum(field=...) (reference GroupBy aggregate, v1.4+)
        agg_call = call.arg("aggregate")
        agg_field = None
        if isinstance(agg_call, Call):
            if agg_call.name != "Sum":
                raise PQLError("GroupBy aggregate supports only Sum(...)")
            agg_name = agg_call.arg("field") or agg_call.arg("_field")
            agg_field = idx.field(agg_name) if agg_name else None
            if agg_field is None or agg_field.options.type != TYPE_INT:
                raise PQLError("GroupBy aggregate requires an int field")

        # build having= eagerly (before the possibly-empty dims early
        # return) so a malformed condition errors even on empty results
        having = having_predicate(call, has_agg=agg_field is not None)

        dims = []
        ranges = 0
        for child in call.children:
            fname = child.arg("_field") or child.arg("field")
            row_ids = self._rows_ids(idx, child, shards)
            if not row_ids:
                return limit, filt_call, agg_field, [], having
            dims.append((fname, row_ids))
            ranges += any(child.arg(k) is not None
                          for k in ("previous", "limit", "column"))
        if ranges:
            note_groupby_range_dims(ranges)
        return limit, filt_call, agg_field, dims, having

    def _groupby_counts(self, idx: Index, dims, cand: np.ndarray,
                        counts: np.ndarray, agg_arrs, agg_field,
                        columns: int, limit, having=None) -> GroupCounts:
        """The answer's columns from a final level's candidates (index
        tuples into ``dims``), their counts and aggregate partials:
        count > 0, having, order and limit as array operations, the
        rowID→rowKey translation of a keyed dimension field carried
        beside the ids (reference GroupBy FieldRow carries RowKey when
        the field has keys). ``columns`` bounds a plane count."""
        sums = None
        if agg_arrs is not None:
            sums = _groupby_sums(
                *agg_arrs, agg_field.options.base,
                agg_field.options.bit_depth, columns,
            )

        def take(ix) -> None:
            nonlocal cand, counts, sums
            cand, counts = cand[ix], counts[ix]
            if sums is not None:
                sums = sums[ix]

        keep = counts > 0
        if not keep.all():
            take(keep)
        if having is not None:
            take(np.array([
                having(c, s) for c, s in zip(
                    counts.tolist(),
                    sums.tolist() if sums is not None else repeat(None),
                )
            ], bool))
        # Order by the emitted representation — numeric rowIDs first
        # (numerically), then rowKeys (lexicographically) — so every
        # execution path (single-node, SPMD, cluster merge) agrees on
        # ordering and limit truncation. A level's candidates are in
        # lexicographic order of row INDEX, so they are in order already
        # unless some dimension's rows are not: then its indexes are
        # ranked once, by what they emit, and the groups sorted by rank.
        fields, ids, row_keys, ranks = [], [], [], []
        for fname, row_ids in dims:
            field = idx.field(fname)
            keys, emitted = None, row_ids
            if field is not None and field.options.keys:
                keys = dict(zip(row_ids, self._row_keys(idx, field, row_ids)))
                emitted = [(0, r) if keys[r] is None else (1, keys[r])
                           for r in row_ids]
            rank = None
            if emitted != sorted(emitted):
                order = sorted(range(len(emitted)), key=emitted.__getitem__)
                rank = np.empty(len(order), np.intp)
                rank[order] = np.arange(len(order))
            fields.append(fname)
            ids.append(np.asarray(row_ids, np.int64))
            row_keys.append(keys)
            ranks.append(rank)
        if any(r is not None for r in ranks):
            # lexsort's LAST key is the primary one
            take(np.lexsort([
                cand[:, d] if ranks[d] is None else ranks[d][cand[:, d]]
                for d in reversed(range(len(dims)))
            ]))
        if limit:
            take(slice(int(limit)))
        rows = np.empty(cand.shape, np.int64)
        for d, dim_ids in enumerate(ids):
            rows[:, d] = dim_ids[cand[:, d]]
        return GroupCounts(fields, rows, counts, sums, row_keys)

    def _execute_groupby(self, idx: Index, call: Call, shards=None) -> GroupCounts:
        return self._submit_groupby(idx, call, shards).result()

    def _submit_groupby(self, idx: Index, call: Call, shards=None,
                        pipeline: bool = False) -> "Deferred":
        """GroupBy as batched device programs with level pruning.

        The reference recurses per shard over the dimension cross-product,
        pruning prefixes whose intersection is empty
        (executor.executeGroupByShard). Here each prefix level is ONE
        batched program — candidate prefixes are gathered out of the
        stacked dimension matrices, counted per shard, and reduced on
        device. A cross-product small enough to skip pruning
        (_groupby_dense) costs exactly one device sync. A pruned GroupBy
        first counts every dimension ALONE under the filter (the
        marginal round: a count-only level a dimension, one sync for
        all of them), crosses only the rows that survived, and costs one
        sync more for the final level where the survivors' cross-product
        is small enough by the same rule, else one more per dimension
        from the second to the one before the last (run_pruned). A level
        reads each operand row once and keeps every candidate's
        accumulators on-chip (batch.groupby_level_body); it is chunked
        only past batch.groupby_chunk_groups candidates.

        Pipelined (submit): the common dense single-level case enqueues
        its level program WITHOUT the blocking readback — the host sync
        moves into ``Deferred.result()``, overlapping the round trip
        with whatever the serving loop enqueues next. The pruning path
        needs a readback per round to choose the next level's
        candidates, so it defers the whole evaluation to ``result()``.

        Either way the Deferred resolves to ONE ``GroupCounts``
        (executor/result.py): the final level's kept candidates as
        columns, which the JSON route renders to bytes as they are and
        which equals the list of GroupCount it makes for a consumer that
        walks it.
        """
        # GroupBy plans per request (its dimensions' row ids are read
        # from the live fragments), so its plan stage is the prelude and
        # the filter's compile, and its operand stage the stacked
        # filter leaves, dimension matrices and aggregate planes
        with stage("executor.plan"):
            limit, filt_call, agg_field, dims, having = \
                self._groupby_prelude(idx, call, shards)
            if not dims:
                return Deferred(value=GroupCounts())
            shard_list = self._shards(idx, shards)
            if not shard_list:
                return Deferred(value=GroupCounts())

            specs: list = []
            scalars: list = []
            filt_node = (
                self._compile_node(idx, filt_call, specs, scalars)
                if filt_call is not None
                else None
            )
            block = self._shard_block(shard_list)
        with stage("executor.operands"):
            put = self._leaf_put(block)
            filt_leaves = [batch.stacked_leaf(idx, s, block, put)
                           for s in specs]
            dim_mats = []
            for fname, row_ids in dims:
                field = idx.field(fname)
                view = field.view(VIEW_STANDARD) if field else None
                # zero rows keep the matrix in the layout whose
                # [n, S, W] view the level kernel reads in place
                dim_mats.append(
                    batch.stacked_matrix(
                        idx, fname, view, row_ids, block, put,
                        pad_rows=batch.groupby_pad_rows(len(row_ids)))
                )
            planes = (
                batch.stacked_leaf(
                    idx,
                    _PlanesSpec(
                        agg_field.name, agg_field.options.bit_depth,
                        pad_rows=batch.groupby_pad_rows(
                            2 + agg_field.options.bit_depth)),
                    block, put,
                )
                if agg_field is not None
                else None
            )

        sizes = tuple(len(row_ids) for _, row_ids in dims)
        n_planes = 0 if agg_field is None else 2 + agg_field.options.bit_depth

        def collect(cand, counts_arr, agg_arrs) -> GroupCounts:
            return self._groupby_counts(
                idx, dims, cand, counts_arr, agg_arrs, agg_field,
                len(block.shards) * SHARD_WIDTH, limit, having=having,
            )

        if _groupby_dense(sizes, n_planes):
            # small cross-product: every group in one level; the level
            # program is enqueued NOW, the readback waits for result()
            cand = _dense_candidates(sizes)
            packed, layout = self._groupby_level_enqueue(
                block, filt_leaves, filt_node, scalars, dim_mats, cand,
                planes, agg_field, cand_key=sizes,
            )
            has_agg = planes is not None
            depth = agg_field.options.bit_depth if has_agg else 0

            def finish() -> GroupCounts:
                counts_arr, agg_arrs = _groupby_level_unpack(
                    _readback(packed), layout, cand.shape[0], has_agg,
                    depth,
                )
                return collect(cand, counts_arr, agg_arrs)

            if pipeline:
                return Deferred(finish)
            return Deferred(value=finish())

        def run_pruned() -> GroupCounts:
            # prefix pruning over each dimension's survivors. A group
            # (a, b, c) is non-empty under the filter F only if a & F,
            # b & F and c & F each are, so every dimension is first
            # counted ALONE under the filter (the marginal round: one
            # count-only level a dimension, all enqueued before ONE
            # blocking readback) and only the rows that survive are ever
            # crossed. Then the dense rule is asked again with the
            # survivors' sizes: where their cross product fits, the
            # final level counts it at once; else prefixes are extended
            # one dimension at a time by that dimension's survivors,
            # dropping empty prefixes after each level (AND only shrinks
            # groups), each level's readback gating the next level's
            # candidates. Survivor lists ascend, so candidates stay in
            # lexicographic order of row index, prefix-major.
            note_groupby_pruned()
            final = len(dims) - 1

            def level(k: int, prefixes: np.ndarray):
                """Prefixes extended by dimension k's survivors,
                counted, and the non-empty ones kept: (candidates,
                counts, aggregates)."""
                cand = _index_cross(prefixes, survivors[k])
                counts_arr, agg_arrs = self._groupby_eval_level(
                    block, filt_leaves, filt_node, scalars,
                    dim_mats[: k + 1], cand,
                    planes if k == final else None,
                    agg_field if k == final else None,
                )
                keep = counts_arr > 0
                if agg_arrs is not None:
                    agg_arrs = (agg_arrs[0][keep], agg_arrs[1][:, keep])
                return cand[keep], counts_arr[keep], agg_arrs

            # a round trip the dense path does not make (enqueue,
            # blocking readback, choice of survivors), timed apart from
            # the resolve or execute it runs inside: the marginal round
            # is one, whatever the number of dimensions
            with stage("executor.prune_level", level="marginal"):
                # a dimension alone is every row of it, as a dense level
                # of one: its operand is placed once and found again
                enqueued = [
                    self._groupby_level_enqueue(
                        block, filt_leaves, filt_node, scalars, [mat],
                        _dense_candidates((n,)), None, None,
                        cand_key=(n,))
                    for mat, n in zip(dim_mats, sizes)
                ]
                survivors = [
                    np.flatnonzero(_groupby_level_unpack(
                        _readback(packed), layout, n, False, 0
                    )[0]).astype(np.int32)
                    for (packed, layout), n in zip(enqueued, sizes)
                ]
            kept = tuple(s.size for s in survivors)
            alive = all(kept)
            # the rule that chose this path, asked of what is left (two
            # dimensions have no joint level before the final one)
            straight = alive and (
                final == 1 or _groupby_dense(kept, n_planes))
            note_groupby_marginal(sum(sizes), sum(kept), straight)
            if not alive:
                return GroupCounts()
            # dimension 0's level IS its marginal
            cand = survivors[0][:, None]
            if straight:
                # no joint count-only level, no second round trip
                for rows in survivors[1:final]:
                    cand = _index_cross(cand, rows)
            else:
                for k in range(1, final):
                    with stage("executor.prune_level", level=k):
                        cand, _, _ = level(k, cand)
                    if cand.shape[0] == 0:
                        return GroupCounts()
            cand, counts_arr, agg_arrs = level(final, cand)
            if cand.shape[0] == 0:
                return GroupCounts()
            return collect(cand, counts_arr, agg_arrs)

        if pipeline:
            return Deferred(run_pruned)
        return Deferred(value=run_pruned())

    def _groupby_eval_level(self, block, filt_leaves, filt_node,
                            scalars, dim_mats, cand: np.ndarray, planes,
                            agg_field):
        """Evaluate one joint pruning level, its candidates chosen from
        a readback: enqueue + blocking readback."""
        packed, layout = self._groupby_level_enqueue(
            block, filt_leaves, filt_node, scalars, dim_mats, cand,
            planes, agg_field,
        )
        has_agg = planes is not None
        depth = agg_field.options.bit_depth if has_agg else 0
        return _groupby_level_unpack(
            _readback(packed), layout, cand.shape[0], has_agg, depth,
        )

    def _groupby_level_enqueue(self, block, filt_leaves, filt_node,
                               scalars, dim_mats, cand: np.ndarray, planes,
                               agg_field, cand_key=None):
        """Dispatch one level's per-candidate counts (plus BSI aggregate
        partials on the final level): one program, unless the level has
        more candidates than the kernel's accumulator block holds
        (batch.groupby_chunk_groups), when the chunks' results are
        concatenated on device. ``cand_key`` is a hashable that
        determines ``cand``'s content (a dense level: its dimensions'
        sizes), so that the level's operands are placed once and found
        again (_level_operand); None for candidates chosen from a
        readback, which are placed and forgotten. Returns (device packed
        array, chunk layout) — no host sync."""
        import jax.numpy as jnp

        n_gather = len(dim_mats)
        has_agg = planes is not None
        depth = agg_field.options.bit_depth if has_agg else 0
        c_total = cand.shape[0]
        n_planes = 2 + depth if has_agg else 0
        chunk = batch.groupby_chunk_groups(n_planes)
        fn = self._groupby_level_program(
            filt_node, len(filt_leaves), len(scalars), n_gather, n_planes,
        )
        args = list(filt_leaves) + list(dim_mats)
        if has_agg:
            args.append(planes)
        # static a program: the plan the body builds its kernel by
        paged = batch.groupby_level_plan(
            filt_node, [leaf.ndim for leaf in filt_leaves],
            tuple(m.shape[1] for m in dim_mats), n_planes,
            dim_mats[0].shape[0] // self.arg_shard_factor,
            dim_mats[0].shape[2])[2]

        packs = []
        layout = []  # (padded, actual) per chunk
        for lo in range(0, c_total, chunk):
            hi = min(lo + chunk, c_total)
            actual = hi - lo
            # padding is marked negative: the kernel stops at the last
            # real candidate (at least 8 wide, so that the smallest
            # levels share one compiled shape)
            padded = max(8, next_pow2(actual))
            operand = self._level_operand(cand, lo, hi, padded, scalars,
                                          cand_key)
            site = stage("device.dispatch", reduce="groupby")
            with site:
                packs.append(fn(*args, operand))
            cost = current_cost()
            if cost is not None:
                cost.note_dispatch(site.elapsed)
            self._note_reduce("groupby", packs[-1].shape)
            layout.append((padded, actual))
        row_visits, row_copies = batch.groupby_paged_rows(cand, paged, chunk)
        note_groupby_level(len(packs), c_total,
                           paged=len(packs) if any(paged) else 0,
                           row_visits=row_visits, row_copies=row_copies)

        if len(packs) == 1:
            return packs[0], layout
        with stage("device.dispatch", reduce="concat"):
            packed = jnp.concatenate(packs)
        return packed, layout

    # ---------------------------------------------------------------- writes

    def _execute_set(self, idx: Index, call: Call) -> bool:
        col = call.arg("_col")
        if col is None:
            raise PQLError("Set requires a column")
        col = self._translate_col(idx, col, create=True)
        if col < 0:
            raise PQLError(f"column {col} is negative")
        field_name, row = self._row_field_and_value(call)
        field = idx.field(field_name)
        if field is None:
            raise PQLError(f"field {field_name!r} not found")
        if field.options.type == TYPE_INT:
            try:
                with stage("fragment.write"):
                    changed = field.set_value(col, int(row))
            except ValueError as e:
                raise PQLError(str(e)) from e
        else:
            row = self._translate_row(idx, field, row, create=True)
            _check_row(row)
            ts = call.arg("timestamp")
            timestamp = _parse_time(ts) if ts is not None else None
            with stage("fragment.write"):
                changed = field.set_bit(int(row), col, timestamp=timestamp)
        with stage("fragment.write"):
            idx.mark_columns_exist([col])
        return changed

    def _execute_clear(self, idx: Index, call: Call) -> bool:
        col = call.arg("_col")
        if col is None:
            raise PQLError("Clear requires a column")
        col = self._translate_col(idx, col, create=False)
        if col is None:
            return False  # unknown column key: nothing to clear
        if col < 0:
            raise PQLError(f"column {col} is negative")
        field_name, row = self._row_field_and_value(call)
        field = idx.field(field_name)
        if field is None:
            raise PQLError(f"field {field_name!r} not found")
        if field.options.type == TYPE_INT:
            with stage("fragment.write"):
                return field.clear_value(col)
        row = self._translate_row(idx, field, row, create=False)
        if row is None:
            return False
        _check_row(row)
        with stage("fragment.write"):
            return field.clear_bit(int(row), col)

    def _execute_clear_row(self, idx: Index, call: Call, shards=None) -> bool:
        field_name, row = self._row_field_and_value(call)
        field = idx.field(field_name)
        if field is None:
            raise PQLError(f"field {field_name!r} not found")
        row = self._translate_row(idx, field, row, create=False)
        if row is None:
            return False  # unknown row key: nothing to clear
        _check_row(row)
        view = field.view(VIEW_STANDARD)
        changed = False
        if view is not None:
            for shard in self._shards(idx, shards):
                frag = view.fragment(shard)
                if frag is not None:
                    changed |= frag.clear_row(int(row)) > 0
        return changed

    def _execute_set_row_attrs(self, idx: Index, call: Call) -> None:
        """SetRowAttrs(field, rowID, attr=value, ...) — reference
        executor.executeSetRowAttrs (SURVEY.md §2 #12)."""
        field_name = call.arg("_field")
        if field_name is None:
            raise PQLError("SetRowAttrs requires a field")
        field = idx.field(field_name)
        if field is None:
            raise PQLError(f"field {field_name!r} not found")
        row = call.arg("_col")
        if row is None:
            raise PQLError("SetRowAttrs requires a row id")
        row = self._translate_row(idx, field, row, create=True)
        attrs = _attr_args(call)
        # the field-name arg can collide with an attr key; the reference
        # disambiguates by position — we've already consumed _field
        field.row_attrs.set_attrs(int(row), attrs)
        return None

    def _execute_set_column_attrs(self, idx: Index, call: Call) -> None:
        col = call.arg("_col")
        if col is None:
            raise PQLError("SetColumnAttrs requires a column id")
        col = self._translate_col(idx, col, create=True)
        idx.column_attrs.set_attrs(int(col), _attr_args(call))
        return None

    def _execute_store(self, idx: Index, call: Call, shards=None) -> bool:
        if len(call.children) != 1:
            raise PQLError("Store requires one child call")
        field_name, row = self._row_field_and_value(call)
        field = idx.field(field_name)
        if field is None:
            # validate BEFORE the implicit create so a rejected query
            # leaves no phantom field behind (an implicitly created
            # field has keys=false, so a string row can never translate)
            _check_row(row)
            field = idx.create_field(field_name)
        else:
            row = self._translate_row(idx, field, row, create=True)
            _check_row(row)
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return True
        compiled = self._compile_cached(idx, call.children[0])
        block = self._shard_block(shard_list)
        host = _readback(self._batched_eval(idx, compiled, block, "row"))
        for i, shard in enumerate(block.shards):
            frag = field.view(VIEW_STANDARD, create=True).fragment(shard, create=True)
            frag.write_row_words(int(row), host[i])
        return True


def _groupby_level_unpack(host: np.ndarray, layout, c_total: int,
                          has_agg: bool, depth: int):
    """Unpack a level's concatenated chunk sections (host side):
    per-candidate counts, plus (n, plane counts) with an aggregate."""

    def take2(off: int, n: int, padded: int) -> np.ndarray:
        """Merge one split-sum section [2·padded] → int64[n]."""
        return batch.merge_split(
            host[off:off + 2 * padded].reshape(2, padded)[:, :n]
        )

    counts = np.zeros(c_total, np.int64)
    n_g = np.zeros(c_total, np.int64) if has_agg else None
    pc = np.zeros((depth, c_total), np.int64) if has_agg else None
    off = out_off = 0
    for padded, actual in layout:
        counts[out_off:out_off + actual] = take2(off, actual, padded)
        if has_agg:
            n_g[out_off:out_off + actual] = take2(
                off + 2 * padded, actual, padded
            )
            pc_flat = host[off + 4 * padded:off + (4 + 2 * depth) * padded]
            pc[:, out_off:out_off + actual] = batch.merge_split(
                pc_flat.reshape(2, depth, padded)[:, :, :actual]
            )
            off += (4 + 2 * depth) * padded
        else:
            off += 2 * padded
        out_off += actual
    return counts, (n_g, pc) if has_agg else None


def _groupby_sums(n: np.ndarray, pc: np.ndarray, base: int, depth: int,
                  columns: int) -> np.ndarray:
    """Each group's BSI Sum from its plane counts ``pc[depth, G]`` and
    its count of non-null values ``n[G]``: sum(pc[b] << b) + base * n,
    exact. A plane count is at most ``columns``, so where depth and base
    leave every partial sum inside 62 bits the arithmetic is int64;
    otherwise it is done in Python integers (an object array)."""
    if (((1 << depth) - 1) + abs(base)) * columns < 1 << 62:
        return (1 << np.arange(depth, dtype=np.int64)) @ pc + base * n
    weights = np.array([1 << b for b in range(depth)], dtype=object)
    return np.dot(weights, pc.astype(object)) + base * n.astype(object)


def options_child(call: Call) -> Call:
    """Validate and return an Options() call's single child."""
    if len(call.children) != 1:
        raise PQLError("Options requires one child call")
    return call.children[0]


def options_restrict_shards(call: Call, shards):
    """Apply Options(shards=) to an engine-supplied shard list. The two
    INTERSECT: an engine list (a remote sub-query's per-node assignment,
    or a request-level ?shards= param) must never be widened by the
    user restriction — overriding it would make every replica evaluate
    the full user set and double-count in the cross-node merge. Shared
    by the single-node executor and the cluster layer so the semantics
    cannot drift."""
    opt = call.arg("shards")
    if opt is None:
        return shards
    opt = sorted({int(s) for s in opt})  # dedup: each shard counts once
    return opt if shards is None else sorted(set(opt) & set(shards))


def apply_options_result(idx: Index, call: Call, res):
    """The result-side tail of Options(): columnAttrs / excludeColumns
    on row-materializing results (applied after any cross-node merge)."""
    if isinstance(res, RowResult):
        if call.arg("columnAttrs"):
            res.column_attrs = column_attr_sets(idx, res)
        if call.arg("excludeColumns"):
            return strip_columns(res)
    return res


def column_attr_sets(idx: Index, res: RowResult) -> list[dict]:
    """columnAttrs option output: one bulk attr-store read for the
    result's columns (shared by PQL Options() and the request-level URL
    param so the two spellings cannot drift)."""
    cols = res.columns().tolist()
    attr_map = idx.column_attrs.bulk(cols) if cols else {}
    return [{"id": c, "attrs": attr_map[c]} for c in cols if c in attr_map]


def strip_columns(res: RowResult) -> RowResult:
    """excludeColumns option: drop the column identities (translated keys
    included — they ARE the columns on a keyed index) while keeping row
    attrs and any computed columnAttrs. Shared by PQL Options() and the
    request-level URL param."""
    out = RowResult({}, attrs=res.attrs,
                    keys=[] if res.keys is not None else None)
    out.column_attrs = res.column_attrs
    return out


def _condition_value(v):
    """Numeric coercion for Condition thresholds: int and float pass
    through untruncated (``count < 1.5`` must keep count==1 groups —
    int(1.5) → ``< 1`` would drop them), quoted numerics parse, junk
    raises PQLError (→ HTTP 400) instead of a bare TypeError."""
    if isinstance(v, (int, float)):
        return v
    try:
        return int(v)
    except (TypeError, ValueError):
        try:
            return float(v)
        except (TypeError, ValueError):
            raise PQLError(
                f"condition value {v!r} is not numeric"
            ) from None


def condition_test(cond: Condition, val: int) -> bool:
    """Evaluate a PQL Condition against a scalar (having= filters)."""
    if cond.op == "><":
        lo, hi = cond.value
        return _condition_value(lo) <= val <= _condition_value(hi)
    ref = _condition_value(cond.value)
    return {
        "<": val < ref, "<=": val <= ref, ">": val > ref, ">=": val >= ref,
        "==": val == ref, "!=": val != ref,
    }[cond.op]


def having_predicate(call: Call, has_agg: bool):
    """GroupBy(having=Condition(count > N)) / Condition(sum > N).

    SURVEY-LOW surface (Appendix B: exact upstream version gate
    unverifiable with the mount empty). Conservative reading implemented:
    exactly one condition on ``count`` or ``sum``, applied to fully
    merged groups BEFORE limit truncation — so having trims groups, never
    changes their counts, and a sum condition requires
    aggregate=Sum(...). Returns ``pred(count, sum) -> bool`` or None.
    """
    having = call.arg("having")
    if having is None:
        return None
    if not isinstance(having, Call) or having.name != "Condition":
        raise PQLError("having= requires Condition(count/sum <op> value)")
    conds = [(k, v) for k, v in having.args.items()
             if isinstance(v, Condition)]
    if len(conds) != 1 or conds[0][0] not in ("count", "sum"):
        raise PQLError(
            "having= supports exactly one condition on count or sum"
        )
    subject, cond = conds[0]
    if subject == "sum" and not has_agg:
        raise PQLError("having on sum requires aggregate=Sum(...)")

    def pred(count: int, sum_) -> bool:
        val = count if subject == "count" else int(sum_ or 0)
        return condition_test(cond, val)

    return pred


def _attr_args(call: Call) -> dict:
    """Named args of an attrs call, excluding reserved/positional ones."""
    return {
        k: v for k, v in call.args.items() if k not in _RESERVED_ARGS
    }


_BITMAP_CALLS = {
    "Row", "Union", "Intersect", "Difference", "Xor", "Not", "All", "Shift",
    "Range",
}

# Call types whose submit() ENQUEUES device work without blocking —
# the only ones a serving pipeline should coalesce. Everything else
# (Rows and other host-eager reads) evaluates fully inside submit(), so
# routing it through a single dispatcher thread would serialize work
# that N handler threads previously overlapped.
_PIPELINED_CALLS = (
    {"Count", "Sum", "Min", "Max", "TopN", "GroupBy"} | _BITMAP_CALLS
)


def pipeline_coalescable(query) -> bool:
    """True when every call in the query micro-batches under submit()
    (Options unwraps to its child for the purpose)."""
    def one(call) -> bool:
        if call.name == "Options":
            return bool(call.children) and one(call.children[0])
        return call.name in _PIPELINED_CALLS

    calls = getattr(query, "calls", None)
    return calls is not None and all(one(c) for c in calls)


def _index_cross(cand: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Extend candidate index tuples [P, k] by each of the next
    dimension's row indices ``rows`` int32[n] → [P·n, k+1], prefix-major."""
    left = np.repeat(cand, rows.size, axis=0)
    right = np.tile(rows, cand.shape[0])[:, None]
    return np.concatenate([left, right], axis=1)


def _groupby_dense(sizes: tuple, n_planes: int) -> bool:
    """Whether a GroupBy over dimensions of ``sizes`` rows is counted in
    ONE level of every group rather than by prefix pruning (n_planes:
    the aggregate's depth + 2, 0 without one). One dimension has no
    prefix to prune; more are dense while their cross product is at most
    GROUPBY_DENSE_MAX_GROUPS groups and GROUPBY_DENSE_MAX_PROGRAMS level
    programs."""
    groups = math.prod(sizes)
    return len(sizes) == 1 or groups <= min(
        GROUPBY_DENSE_MAX_GROUPS,
        GROUPBY_DENSE_MAX_PROGRAMS * batch.groupby_chunk_groups(n_planes))


@functools.lru_cache(maxsize=64)
def _dense_candidates(sizes: tuple) -> np.ndarray:
    """Every index tuple of a cross-product of ``sizes`` rows, in
    lexicographic order: a dense level's candidates, the same for every
    request of a query template (at most GROUPBY_DENSE_MAX_GROUPS rows
    past one dimension). Shared between callers, so read-only."""
    cand = np.zeros((1, 0), np.int32)
    for n in sizes:
        cand = _index_cross(cand, np.arange(n, dtype=np.int32))
    cand.flags.writeable = False
    return cand


def _check_row(row) -> None:
    if not isinstance(row, int):
        raise PQLError(f"row key {row!r} requires key translation (field keys)")
    if row < 0:
        raise PQLError(f"row {row} is negative")


def _parse_time(value) -> dt.datetime:
    if isinstance(value, dt.datetime):
        return value
    return dt.datetime.fromisoformat(str(value))

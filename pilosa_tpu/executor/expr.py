"""Fused bitmap-expression compiler.

A PQL bitmap call tree is lowered to a *structure* — nested hashable
tuples with leaf indices — and each distinct structure is traced+compiled
once (module-level cache). Evaluation takes (leaves, scalars) where leaves
are device-resident uint32 rows / BSI plane matrices and scalars are
query-time integers (shift amounts, BSI predicates), so re-running the
same query shape with different rows or predicates reuses the compiled
kernel.

This is the TPU replacement for the reference's per-container op dispatch
(executor.go executeBitmapCallShard over roaring containers — SURVEY.md
§3.2): XLA fuses the entire tree into one HBM pass, including the final
popcount for Count.

Node grammar (structure tuples):
  ('leaf', i)                     — uint32[words] row leaf
  ('const0',)                     — empty row
  ('and'|'or'|'xor'|'diff', a, b)
  ('flipall', a)                  — bitwise NOT over the full shard width
  ('shift', a, j)                 — shift by scalars[j]
  ('bsicmp', op, i_planes, i_exists_leaf, j_pred) — BSI comparison row
  ('count', a)                    — int32 scalar popcount reduction
  ('countrows', i_matrix, a|None) — int32[rows] popcount per matrix row,
                                    optionally masked by bitmap node a
  ('bsisum', i_planes, a|None)    — (int32[depth] plane counts, int32 n)
  ('bsiminmax', want_max, i_planes, a|None) — (value, count)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from pilosa_tpu.utils.compile_cache import named_jit

_U32 = jnp.uint32

# BSI plane-matrix row layout (matches storage.field BSI_* constants).
PLANES_EXISTS = 0
PLANES_SIGN = 1
PLANES_OFFSET = 2

_JIT_CACHE: dict = {}


def evaluate(structure, leaves, scalars):
    """Run a structure against device leaves; compiled once per structure."""
    fn = _JIT_CACHE.get(structure)
    if fn is None:
        fn = _build(structure)
        _JIT_CACHE[structure] = fn
    return fn(tuple(leaves), tuple(jnp.asarray(s, jnp.int32) for s in scalars))


def _build(structure):
    def eval_fn(leaves, scalars):
        return _go(structure, leaves, scalars)

    return named_jit("expr", eval_fn)


def _go(node, leaves, scalars):
    tag = node[0]
    if tag == "leaf":
        return leaves[node[1]]
    if tag == "const0":
        return jnp.zeros_like(leaves[0]) if leaves else jnp.zeros(0, _U32)
    if tag == "and":
        return _go(node[1], leaves, scalars) & _go(node[2], leaves, scalars)
    if tag == "or":
        return _go(node[1], leaves, scalars) | _go(node[2], leaves, scalars)
    if tag == "xor":
        return _go(node[1], leaves, scalars) ^ _go(node[2], leaves, scalars)
    if tag == "diff":
        return _go(node[1], leaves, scalars) & ~_go(node[2], leaves, scalars)
    if tag == "flipall":
        return ~_go(node[1], leaves, scalars)
    if tag == "shift":
        from pilosa_tpu.ops.bitops import shift

        # inline the shift body so it fuses with the rest of the tree
        return shift.__wrapped__(_go(node[1], leaves, scalars), scalars[node[2]])
    if tag == "count":
        sub = _go(node[1], leaves, scalars)
        return jnp.sum(lax.population_count(sub).astype(jnp.int32))
    if tag == "countrows":
        matrix = leaves[node[1]]
        if node[2] is not None:
            matrix = matrix & _go(node[2], leaves, scalars)[None, :]
        return jnp.sum(lax.population_count(matrix).astype(jnp.int32), axis=-1)
    if tag == "bsicmp":
        return _bsi_compare(
            node[1], leaves[node[2]], _go(node[3], leaves, scalars),
            scalars[node[4]],
        )
    if tag == "bsisum":
        planes = leaves[node[1]]
        filt = planes[PLANES_EXISTS]
        if node[2] is not None:
            filt = filt & _go(node[2], leaves, scalars)
        bits = planes[PLANES_OFFSET:] & filt[None, :]
        plane_counts = jnp.sum(lax.population_count(bits).astype(jnp.int32), axis=-1)
        n = jnp.sum(lax.population_count(filt).astype(jnp.int32))
        return plane_counts, n
    if tag == "bsiminmax":
        planes = leaves[node[2]]
        filt = planes[PLANES_EXISTS]
        if node[3] is not None:
            filt = filt & _go(node[3], leaves, scalars)
        return _bsi_minmax(bool(node[1]), planes, filt)
    raise ValueError(f"unknown expr node {tag!r}")


def _bsi_compare(op: str, planes, exists, pred):
    """BSI comparison against a traced predicate (classic O(depth)
    bit-sliced algorithm, vectorized over the whole shard row).

    planes: uint32[2+depth, words] (exists, sign, bit 0 … LSB-first).
    pred is the *offset-encoded* predicate (executor subtracts the base and
    range-clamps before calling).
    """
    depth = planes.shape[0] - PLANES_OFFSET
    zeros = jnp.zeros_like(exists)
    eq, lt, gt = exists, zeros, zeros
    for i in reversed(range(depth)):
        p = planes[PLANES_OFFSET + i]
        bit = (pred >> i) & 1
        is1 = (bit == 1)
        lt = lt | jnp.where(is1, eq & ~p, zeros)
        gt = gt | jnp.where(is1, zeros, eq & p)
        eq = eq & jnp.where(is1, p, ~p)
    if op == "<":
        return lt
    if op == "<=":
        return lt | eq
    if op == ">":
        return gt
    if op == ">=":
        return gt | eq
    if op == "==":
        return eq
    if op == "!=":
        return exists & ~eq
    raise ValueError(f"bad bsi op {op!r}")


def _bsi_minmax(want_max: bool, planes, candidates):
    """Greedy MSB-first walk: returns (offset-encoded extremum, count).

    count == 0 means no candidates (executor reports null).
    """
    depth = planes.shape[0] - PLANES_OFFSET
    value = jnp.int32(0)
    for i in reversed(range(depth)):
        p = planes[PLANES_OFFSET + i]
        t = candidates & (p if want_max else ~p)
        nonempty = jnp.any(t != 0)
        candidates = jnp.where(nonempty, t, candidates)
        if want_max:
            bit = nonempty.astype(jnp.int32)
        else:
            # for min, picking ~p means the bit is 0; forced to 1 only when
            # no candidate has a 0 in this plane
            bit = jnp.logical_not(nonempty).astype(jnp.int32)
        value = value | (bit << i)
    n = jnp.sum(lax.population_count(candidates).astype(jnp.int32))
    return value, n

"""Cell data from the seed, written as the server's own fragment files.

Data is written, not ingested: PR 21 measured HTTP ``/import`` at about
0.27 M bits/s, which would make every run of every later check pay
minutes of set-up for bytes that a restart reads from disk anyway. The
byte layout is ``pilosa_tpu/roaring/format.py``'s snapshot layout and
the tree is ``pilosa_tpu/storage``'s (``<index>/<field>/views/<view>/
fragments/<shard>`` with ``.meta`` JSON beside index and field); the
tests prove on the CPU that a server opened on these files answers
every template as the numpy reference does, and that ``cli check -d``
finds them sound.

Nothing here imports the program or JAX: numpy only.
"""

from __future__ import annotations

import json
import os
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SHARD_WIDTH = 1 << 20
CONTAINER_BITS = 1 << 16
CONTAINERS_PER_ROW = SHARD_WIDTH // CONTAINER_BITS  # 16
ARRAY_MAX = 4096  # at or under this a container is a sorted uint16 array
BITMAP_BYTES = CONTAINER_BITS // 8

KIND_ARRAY, KIND_BITMAP, KIND_RUN = 1, 2, 3
MAGIC, VERSION = 0x50C4B175, 1
HEADER = np.dtype([("magic", "<u4"), ("version", "<u2"), ("flags", "<u2"),
                   ("count", "<u4"), ("payload", "<u8")])
DESCR = np.dtype([("key", "<u8"), ("kind", "<u2"), ("n1", "<u2"),
                  ("len", "<u4")])
assert HEADER.itemsize == 20 and DESCR.itemsize == 16

BSI_EXISTS_ROW, BSI_OFFSET_ROW = 0, 2
EXISTS_FIELD = "_exists"

WRITER_THREADS = max(2, min(16, (os.cpu_count() or 2) - 1))


# ------------------------------------------------------------- the columns


def field_rows(spec: dict) -> int:
    """Number of rows of a set field, from whichever key states it."""
    if "weights" in spec:
        return len(spec["weights"])
    if "geometric" in spec:
        return int(spec["geometric"]["rows"])
    if "uniform" in spec:
        return int(spec["uniform"])
    return int(spec["rows"])


def field_weights(spec: dict) -> np.ndarray:
    """Relative frequency of each row of a set field drawn on its own."""
    if "weights" in spec:
        w = np.asarray(spec["weights"], np.float64)
    elif "geometric" in spec:
        g = spec["geometric"]
        lead = list(g.get("lead", []))
        n = int(g["rows"]) - len(lead)
        w = np.asarray(lead + [g["first"] * g["ratio"] ** i for i in range(n)],
                       np.float64)
    else:
        w = np.ones(field_rows(spec), np.float64)
    return w / w.sum()


def _field_rng(seed: int, name: str) -> np.random.Generator:
    # a field's values depend on the seed and its name only, so a cell
    # that materialises fewer fields sees the same rides in them
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), zlib.crc32(name.encode())])))


def _dtype_for(n_rows: int):
    return np.uint8 if n_rows <= 256 else np.uint16


def make_columns(config: dict, seed: int, n_shards: int,
                 fields: list[str]) -> dict[str, np.ndarray]:
    """One value per column for each named field, and for the fields
    those are computed from."""
    n = n_shards * SHARD_WIDTH
    specs = config["fields"]

    def parent(name: str):
        spec = specs[name]
        for k in ("derived", "linear"):
            if k in spec:
                return spec[k]["field"]
        return None

    wanted = set(fields) | {parent(f) for f in fields if parent(f)}
    roots = sorted(f for f in wanted if parent(f) is None)
    with ThreadPoolExecutor(WRITER_THREADS) as pool:
        out = dict(zip(roots, pool.map(
            lambda f: _draw(specs[f], _field_rng(seed, f), n), roots)))
    for name in sorted(wanted - set(roots)):
        spec = specs[name]
        if "derived" in spec:
            d = spec["derived"]
            out[name] = (out[d["field"]] // d["div"]).astype(
                _dtype_for(field_rows(spec)))
        else:
            lin = spec["linear"]
            vals = out[lin["field"]].astype(np.int32) * lin["scale"]
            vals += lin["base"]
            vals += _field_rng(seed, name).integers(
                0, 1 << lin["noise_bits"], n, dtype=np.int32)
            out[name] = vals
    for name, vals in out.items():
        spec = specs[name]
        if spec["type"] == "int" and (int(vals.min()) < spec["min"]
                                      or int(vals.max()) > spec["max"]):
            raise ValueError(f"field {name}: generated value outside "
                             f"[{spec['min']}, {spec['max']}]")
    return out


def _draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """Independent draws of a field that depends on no other."""
    if spec["type"] == "int":
        lo, hi = spec["uniform_int"]
        return rng.integers(lo, hi + 1, n, dtype=np.int32)
    rows = field_rows(spec)
    if "uniform" in spec:
        return rng.integers(0, rows, n, dtype=_dtype_for(rows))
    # categorical, through a 16-bit lookup table of the cumulative weights
    cdf = np.cumsum(field_weights(spec))
    lut = np.searchsorted(cdf, (np.arange(65536) + 0.5) / 65536.0
                          ).clip(0, rows - 1).astype(_dtype_for(rows))
    return lut[rng.integers(0, 65536, n, dtype=np.uint16)]


# -------------------------------------------------------- fragment encoding


RUN_BYTES = np.array([0, 0xFFFF], "<u2").view(np.uint8)  # one full run


def _kinds_and_lengths(n: np.ndarray):
    """Container kind and payload bytes by cardinality, as the server
    chooses them: array up to 4,096 bits, one run when full, else words."""
    kinds = np.where(n <= ARRAY_MAX, KIND_ARRAY,
                     np.where(n == CONTAINER_BITS, KIND_RUN, KIND_BITMAP))
    lens = np.where(kinds == KIND_ARRAY, 2 * n,
                    np.where(kinds == KIND_RUN, 4, BITMAP_BYTES))
    return kinds, lens


def encode_fragment(row_ids: np.ndarray, bits: np.ndarray) -> tuple[bytes, list]:
    """Snapshot bytes of one fragment from its dense rows.

    ``bits`` is ``uint8[len(row_ids), SHARD_WIDTH // 8]``, little-endian
    bit order (bit ``p`` of a row is bit ``p & 7`` of byte ``p >> 3``).
    Each row is 16 containers of 65,536 bits; an empty container is
    left out, one of at most 4,096 bits is a sorted uint16 array, a full
    one is a single run, any other is 1,024 uint64 words — what the
    server's own snapshot of the same bits holds. Also returns
    ``[(row, count)]`` for the fragment's ``.cache`` file.
    """
    n_rows = len(row_ids)
    cont = bits.reshape(n_rows * CONTAINERS_PER_ROW, BITMAP_BYTES)
    counts = np.bitwise_count(cont.view("<u8")).sum(axis=1, dtype=np.int64)
    keys = (np.repeat(np.asarray(row_ids, np.uint64), CONTAINERS_PER_ROW)
            * CONTAINERS_PER_ROW
            + np.tile(np.arange(CONTAINERS_PER_ROW, dtype=np.uint64), n_rows))
    live = np.nonzero(counts)[0]
    n_live = counts[live]
    kinds, lens = _kinds_and_lengths(n_live)
    # payload in key order: runs of neighbouring bitmap containers are
    # copied as one block, so a dense field costs a few copies a fragment
    pieces = []
    i, n_cont = 0, live.size
    live_l, kinds_l = live.tolist(), kinds.tolist()
    while i < n_cont:
        c = live_l[i]
        if kinds_l[i] == KIND_BITMAP:
            j = i
            while (j + 1 < n_cont and kinds_l[j + 1] == KIND_BITMAP
                   and live_l[j + 1] == live_l[j] + 1):
                j += 1
            pieces.append(cont[c:live_l[j] + 1].reshape(-1))
            i = j + 1
            continue
        if kinds_l[i] == KIND_RUN:
            pieces.append(RUN_BYTES)
        else:
            lows = np.nonzero(np.unpackbits(cont[c], bitorder="little"))[0]
            pieces.append(lows.astype("<u2").view(np.uint8))
        i += 1
    payload = np.concatenate(pieces) if pieces else np.zeros(0, np.uint8)
    row_counts = counts.reshape(n_rows, CONTAINERS_PER_ROW).sum(axis=1)
    cache = [(int(r), int(c)) for r, c in zip(row_ids, row_counts) if c]
    return _snapshot(keys[live], kinds, n_live, lens, payload), cache


_POS_CONTAINER = (np.arange(SHARD_WIDTH, dtype=np.uint32) >> 16)


def encode_set_fragment(values: np.ndarray, n_rows: int) -> tuple[bytes, list]:
    """The same snapshot for a set field that holds one value per column,
    without a dense bit matrix for its thin rows: one stable sort by
    (row, container) gives every array container's sorted low bits as a
    slice; only rows with a container over 4,096 bits are packed."""
    n_keys = n_rows * CONTAINERS_PER_ROW
    key = values.astype(np.uint32) * CONTAINERS_PER_ROW + _POS_CONTAINER
    if n_keys <= 1 << 16:
        key = key.astype(np.uint16)  # numpy sorts 16-bit keys by radix
    counts = np.bincount(key, minlength=n_keys)
    lows = np.argsort(key, kind="stable").astype(np.uint32).astype("<u2")
    starts = np.cumsum(counts) - counts
    live = np.nonzero(counts)[0]
    n_live = counts[live]
    kinds, lens = _kinds_and_lengths(n_live)
    dense_rows = np.unique(live[kinds == KIND_BITMAP] // CONTAINERS_PER_ROW)
    dense = np.packbits(
        values[None, :] == dense_rows.astype(values.dtype)[:, None],
        axis=1, bitorder="little",
    ).reshape(-1, BITMAP_BYTES)
    dense_at = np.full(n_rows, -1, np.int64)
    dense_at[dense_rows] = np.arange(dense_rows.size) * CONTAINERS_PER_ROW
    # payload in key order, one piece per run of like containers: array
    # containers that follow each other are one slice of ``lows``
    pieces = []
    live_l, kinds_l = live.tolist(), kinds.tolist()
    i, n_cont = 0, live.size
    while i < n_cont:
        j = i
        if kinds_l[i] == KIND_ARRAY:
            while j + 1 < n_cont and kinds_l[j + 1] == KIND_ARRAY:
                j += 1
            lo = int(starts[live_l[i]])
            hi = int(starts[live_l[j]] + counts[live_l[j]])
            pieces.append(lows[lo:hi].view(np.uint8))
        elif kinds_l[i] == KIND_RUN:
            pieces.append(RUN_BYTES)
        else:
            at = lambda c: int(dense_at[c // CONTAINERS_PER_ROW]
                               + c % CONTAINERS_PER_ROW)
            while (j + 1 < n_cont and kinds_l[j + 1] == KIND_BITMAP
                   and at(live_l[j + 1]) == at(live_l[j]) + 1):
                j += 1
            pieces.append(dense[at(live_l[i]):at(live_l[j]) + 1].reshape(-1))
        i = j + 1
    payload = np.concatenate(pieces) if pieces else np.zeros(0, np.uint8)
    row_counts = counts.reshape(n_rows, CONTAINERS_PER_ROW).sum(axis=1)
    cache = [(r, int(c)) for r, c in enumerate(row_counts) if c]
    return _snapshot(live, kinds, n_live, lens, payload), cache


def _snapshot(keys, kinds, n, lens, payload: np.ndarray) -> bytes:
    descr = np.zeros(len(keys), DESCR)
    descr["key"], descr["kind"] = keys, kinds
    descr["n1"], descr["len"] = n - 1, lens
    header = np.zeros(1, HEADER)
    header["magic"], header["version"] = MAGIC, VERSION
    header["count"], header["payload"] = len(keys), payload.size
    return header.tobytes() + descr.tobytes() + payload.tobytes()


def _bsi_bits(stored: np.ndarray, depth: int) -> tuple[np.ndarray, np.ndarray]:
    rows = [BSI_EXISTS_ROW] + [BSI_OFFSET_ROW + i for i in range(depth)]
    bits = np.empty((len(rows), SHARD_WIDTH // 8), np.uint8)
    bits[0] = 0xFF
    for i in range(depth):
        bits[1 + i] = np.packbits((stored >> i) & 1, bitorder="little")
    return np.asarray(rows), bits


# ------------------------------------------------------------ the data dir


def field_meta(spec: dict) -> dict:
    """The ``.meta`` JSON the server writes for a field of this spec."""
    is_int = spec["type"] == "int"
    return {"type": spec["type"], "cacheType": "ranked", "cacheSize": 50000,
            "min": spec["min"] if is_int else 0,
            "max": spec["max"] if is_int else 0,
            "timeQuantum": "", "keys": False}


def _write(path: str, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)


def _write_fragment(frag_dir: str, shard: int, blob: bytes, cache) -> None:
    _write(os.path.join(frag_dir, str(shard)), blob)
    if cache is not None:
        _write(os.path.join(frag_dir, f"{shard}.cache"),
               json.dumps({"kind": "ranked", "counts": cache}).encode())


def write_data_dir(data_dir: str, config: dict, columns: dict,
                   n_shards: int, fields: list[str]) -> int:
    """Write index, fields and one fragment per (field, shard) for
    ``fields`` plus the index's existence field. Returns bytes written."""
    index_dir = os.path.join(data_dir, config["index"])
    os.makedirs(index_dir)
    _write(os.path.join(index_dir, ".meta"),
           json.dumps({"keys": False, "trackExistence": True}).encode())
    jobs = []
    for name in [EXISTS_FIELD] + list(fields):
        if name == EXISTS_FIELD:
            meta = dict(field_meta({"type": "set"}), cacheType="none")
            view = "standard"
        else:
            spec = config["fields"][name]
            meta = field_meta(spec)
            view = "standard" if spec["type"] == "set" else f"bsig_{name}"
        fdir = os.path.join(index_dir, name)
        frag_dir = os.path.join(fdir, "views", view, "fragments")
        os.makedirs(frag_dir)
        _write(os.path.join(fdir, ".meta"), json.dumps(meta).encode())
        jobs += [(name, frag_dir, s) for s in range(n_shards)]

    full_row = np.full((1, SHARD_WIDTH // 8), 0xFF, np.uint8)
    exists_blob, _ = encode_fragment(np.array([0]), full_row)

    def one(job) -> int:
        name, frag_dir, shard = job
        if name == EXISTS_FIELD:
            _write_fragment(frag_dir, shard, exists_blob, None)
            return len(exists_blob)
        spec = config["fields"][name]
        vals = columns[name][shard * SHARD_WIDTH:(shard + 1) * SHARD_WIDTH]
        if spec["type"] == "int":
            depth = max(1, (spec["max"] - spec["min"]).bit_length())
            rows, bits = _bsi_bits(vals - spec["min"], depth)
            blob, cache = encode_fragment(rows, bits)
        else:
            blob, cache = encode_set_fragment(vals, field_rows(spec))
        _write_fragment(frag_dir, shard, blob, cache)
        return len(blob)

    with ThreadPoolExecutor(WRITER_THREADS) as pool:
        return sum(pool.map(one, jobs))

"""Multi-process serving tier (docs/OPERATIONS.md deployment shapes).

The single-process serving ceiling is the Python interpreter, not the
device: the single-interpreter HTTP + API work per request plateaus one
node while the accelerator idles (rate on the chip: not measured). This package shatters that ceiling with the standard
deployment shape for Python services, adapted to a device-owning
backend:

- N ``SO_REUSEPORT`` **worker processes** accept HTTP on the public
  port and run the per-request host work (socket handling, header/QoS
  envelope, PQL parse, admission, degraded-mode shedding, response
  writes) — the GIL-bound ~70% of a request;
- ONE **device-owner process** (the plain Server) keeps the holder,
  WAL, and device caches, and executes queries submitted by the
  workers;
- submissions cross a **pickle-free shared-memory ring** per worker
  (``shmring.py``): fixed-slot rings of length-prefixed bytes with
  torn-record-safe framing and backpressure instead of unbounded
  queueing — worker waves group-commit into the owner's micro-batched
  dispatches, the third instance of the group-commit shape after the
  WAL fsync groups and the remote wave batcher.

``mpserve.py`` holds both halves (OwnerRuntime + the worker entry);
platforms without ``SO_REUSEPORT`` fall back to single-process mode.
"""

from pilosa_tpu.serving.shmring import (
    RingFull,
    ShmRing,
    decode_frame,
    encode_frame,
)

__all__ = ["RingFull", "ShmRing", "decode_frame", "encode_frame"]

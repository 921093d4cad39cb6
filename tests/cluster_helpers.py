"""Shared in-process cluster fixtures: HTTP helper, server boot, and a
deterministic multi-shard seed. Used by the serving-pipeline,
cluster-of-meshes, and randomized-churn suites so the request encoding,
ServerConfig surface, and seed layout live in ONE place."""

import json
import threading
import time
import urllib.request

from pilosa_tpu.server import Server, ServerConfig
from pilosa_tpu.shardwidth import SHARD_WIDTH


def req(method, url, body=None, raw=False):
    data = (body if isinstance(body, (bytes, type(None)))
            else json.dumps(body).encode())
    r = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        r.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(r, timeout=60) as resp:
        payload = resp.read()
    return payload if raw else json.loads(payload or b"{}")


def uri(s: Server) -> str:
    return f"http://localhost:{s.port}"


def make_cluster(tmp_path, n, replica_n=1, use_mesh=False, prefix="node",
                 **config_kw):
    servers = []
    for i in range(n):
        seeds = [uri(servers[0])] if servers else []
        servers.append(Server(ServerConfig(
            data_dir=str(tmp_path / f"{prefix}{i}"), port=0,
            name=f"{prefix[0]}{i}", replica_n=replica_n, seeds=seeds,
            anti_entropy_interval=0, heartbeat_interval=0,
            use_mesh=use_mesh, **config_kw,
        )).open())
    return servers


def settle(servers, timeout=60.0):
    """Wait out what a membership change leaves running: a join is
    relayed and resized for on background threads (``join-relay``, the
    coordinator's ``coordinate-resize`` runs, one a node-join it hears),
    and ``Server.open`` returns before they end. A partition that lands
    while one of them is still queued is a different scenario from the
    one a test here sets up: the late resize passes quorum and acts, or
    stalls RESIZING with its peers cut off."""
    deadline = time.monotonic() + timeout
    while True:
        busy = [t for t in threading.enumerate()
                if t.name in ("join-relay", "coordinate-resize")]
        if not busy:
            break
        for t in busy:
            t.join(max(0.0, deadline - time.monotonic()))
        assert time.monotonic() < deadline, [t.name for t in busy]
    for s in servers:
        assert s.api.cluster.wait_until_normal(
            max(0.0, deadline - time.monotonic())), s.config.name


def join_node(tmp_path, seed_server, use_mesh=False, replica_n=1,
              name="late", prefix="latenode"):
    """Boot one more node seeded off ``seed_server`` (join-resize)."""
    return Server(ServerConfig(
        data_dir=str(tmp_path / prefix), port=0, name=name,
        replica_n=replica_n, seeds=[uri(seed_server)],
        anti_entropy_interval=0, heartbeat_interval=0, use_mesh=use_mesh,
    )).open()


def seed(node0, n_shards=6):
    """Schema + bits over ``n_shards`` shards + a BSI field.

    Layout (per shard s): row 1 holds cols {s*SW+100..103}, row 2 holds
    {s*SW+100..101} (a SUBSET of row 1, so intersections are
    non-trivial), and BSI field v maps col s*SW+100 -> (s+1)*7.
    """
    req("POST", f"{uri(node0)}/index/i",
        {"options": {"trackExistence": True}})
    req("POST", f"{uri(node0)}/index/i/field/f", {})
    req("POST", f"{uri(node0)}/index/i/field/v",
        {"options": {"type": "int", "min": 0, "max": 1000}})
    for row, per_shard in [(1, 4), (2, 2)]:
        cols = [
            s * SHARD_WIDTH + 100 + c
            for s in range(n_shards) for c in range(per_shard)
        ]
        req("POST", f"{uri(node0)}/index/i/field/f/import",
            {"rows": [row] * len(cols), "columns": cols})
    req("POST", f"{uri(node0)}/index/i/field/v/import-value",
        {"columns": [s * SHARD_WIDTH + 100 for s in range(n_shards)],
         "values": [(s + 1) * 7 for s in range(n_shards)]})

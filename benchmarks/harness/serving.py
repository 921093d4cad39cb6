"""The server child and the HTTP the harness speaks to it."""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import subprocess
import sys
import time

START_TIMEOUT_S = 300.0
# the server's own start-up line names the port it bound
LISTENING = re.compile(rb"listening on https?://[^ :]+:(\d+) ")
STOP_TIMEOUT_S = 300.0
# after the child's own exit, how long its process group may take to empty
GROUP_EMPTY_S = 5.0
# a first answer pays decode + upload + compile: minutes, not seconds
HTTP_TIMEOUT_S = 900.0


class HarnessError(Exception):
    pass


class Conn:
    """One keep-alive connection; ``query`` raises on a non-2xx."""

    def __init__(self, port: int, timeout: float = HTTP_TIMEOUT_S):
        self.port, self.timeout = port, timeout
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def close(self) -> None:
        self.conn.close()

    def __enter__(self) -> "Conn":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def request(self, method: str, path: str, body: bytes | None = None
                ) -> tuple[int, bytes]:
        self.conn.request(method, path, body=body)
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def get_json(self, path: str):
        status, data = self.request("GET", path)
        if not 200 <= status < 300:
            raise HarnessError(f"GET {path} -> HTTP {status}: {data[:300]!r}")
        return json.loads(data)

    def query(self, index: str, pql: str) -> list:
        status, data = self.request("POST", f"/index/{index}/query",
                                    pql.encode())
        if not 200 <= status < 300:
            raise HarnessError(f"{pql[:200]} -> HTTP {status}: {data[:300]!r}")
        return json.loads(data)["results"]

    def metrics(self) -> dict:
        """Unlabelled samples of GET /metrics, plus the numbers nested in
        GET /debug/vars as ``vars.<group>.<name>``."""
        status, data = self.request("GET", "/metrics")
        if status != 200:
            raise HarnessError(f"GET /metrics -> HTTP {status}")
        out = {}
        for line in data.decode().splitlines():
            if line and not line.startswith("#") and "{" not in line:
                name, _, value = line.partition(" ")
                try:
                    out[name] = float(value)
                except ValueError:
                    pass
        for group, body in self.get_json("/debug/vars").items():
            if isinstance(body, dict):
                for name, value in body.items():
                    if isinstance(value, (int, float)):
                        out[f"vars.{group}.{name}"] = float(value)
        return out


class ServerProc:
    """``python -m pilosa_tpu server`` (through server_child.py), default
    knobs unless the configuration names some, the only process that
    touches JAX. It binds port 0 and ``wait_ready`` reads the port it was
    given from the child's own log, so two runs on one machine (the
    driver's parent and change) can never be handed the same port, and no
    other process's server is ever mistaken for this one.

    The child leads a session, and so a process group, of its own: a
    deployment whose server spawns processes (``serving-workers``) is
    stopped as a whole. SIGTERM goes to the child alone, since the clean
    close is the program's to make; a kill goes to the group, and a group
    that outlives a clean stop is an error. With no knobs the group is the
    child."""

    def __init__(self, root: str, data_dir: str, log_path: str,
                 knobs: dict, env_extra: dict):
        self.port = 0
        self.log_path = log_path
        self.memory_path = os.path.join(os.path.dirname(log_path),
                                        "memory_stats.json")
        env = dict(os.environ, BENCH_MEMORY_STATS=self.memory_path,
                   BENCH_HARNESS_PID=str(os.getpid()),
                   PYTHONPATH=root + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        for k, v in knobs.items():
            env["PILOSA_TPU_" + k.upper().replace("-", "_")] = str(v)
        env.update(env_extra)
        self._log = open(log_path, "ab")
        child = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "server_child.py")
        self.proc = subprocess.Popen(
            [sys.executable, child, "-d", data_dir, "--bind", "127.0.0.1",
             "--port", "0"],
            cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)
        self.pgid = self.proc.pid
        self._group_gone = False  # its number is then free for reuse

    def wait_ready(self) -> None:
        t0 = time.monotonic()
        while time.monotonic() - t0 < START_TIMEOUT_S:
            if self.proc.poll() is not None:
                raise HarnessError(
                    f"server exited rc={self.proc.returncode} during "
                    f"start-up\n{self.log_tail()}")
            if not self.port:
                with open(self.log_path, "rb") as f:
                    m = LISTENING.search(f.read())
                self.port = int(m.group(1)) if m else 0
            try:
                if self.port:
                    with Conn(self.port, timeout=5.0) as c:
                        if c.request("GET", "/status")[0] == 200:
                            return
            except (OSError, http.client.HTTPException):
                pass
            time.sleep(0.1)
        raise HarnessError(f"server not ready after {START_TIMEOUT_S:.0f} s"
                           f"\n{self.log_tail()}")

    def terminate(self) -> None:
        """SIGTERM: the clean close (snapshots, WAL, chips released)
        starts; ``wait_stopped`` collects it."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)

    def wait_stopped(self) -> int:
        """Wait for the clean close to end. Returns the exit code. The
        child's exit has to leave its group empty: what is still there
        after GROUP_EMPTY_S is killed and named in a HarnessError."""
        try:
            rc = self.proc.wait(STOP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            self.kill()
            return self.proc.returncode
        self._log.close()
        deadline = time.monotonic() + GROUP_EMPTY_S
        while left := self.group_pids():
            if time.monotonic() >= deadline:
                self._kill_group()
                self._group_gone = True
                raise HarnessError(
                    f"server exit code {rc}, and its process group still "
                    f"held pids {left} {GROUP_EMPTY_S:.0f} s later (killed)")
            time.sleep(0.05)
        self._group_gone = True
        return rc

    def kill(self) -> None:
        """SIGKILL to the child's whole group, the child collected."""
        self._kill_group()
        if self.proc.poll() is None:
            self.proc.wait(30)
        if not self._log.closed:
            self._log.close()

    def _kill_group(self) -> None:
        """SIGKILL to the group, and what it had mapped under /dev/shm
        removed: a killed deployment cannot unlink its shared memory (the
        serving tier's rings), and nothing else on the machine would."""
        if self._group_gone:
            return
        shared = self._group_shm()
        try:
            os.killpg(self.pgid, signal.SIGKILL)
        except ProcessLookupError:
            pass  # empty already
        for path in shared:
            try:
                os.unlink(path)
            except OSError:
                pass  # unlinked by its owner meanwhile

    def _group_shm(self) -> set[str]:
        paths = set()
        for pid in self.group_pids():
            try:
                with open(f"/proc/{pid}/maps") as f:
                    # "address perms offset dev inode pathname"
                    fields = [line.split(None, 5) for line in f]
            except OSError:
                continue  # gone since the listing
            paths.update(x[5].rstrip("\n") for x in fields if len(x) == 6
                         and x[5].startswith("/dev/shm/")
                         and not x[5].rstrip("\n").endswith(" (deleted)"))
        return paths

    def group_pids(self) -> list[int]:
        """The processes of the child's group that still run (a zombie
        waiting for its parent to collect it does not)."""
        pids = []
        for name in os.listdir("/proc"):
            if not name.isdigit():
                continue
            try:
                with open(f"/proc/{name}/stat", "rb") as f:
                    # "pid (comm) state ppid pgrp ...": comm may hold ")"
                    state, _, pgrp = f.read().rpartition(b")")[2].split()[:3]
            except (OSError, ValueError):
                continue  # gone between the listing and the read
            if state != b"Z" and int(pgrp) == self.pgid:
                pids.append(int(name))
        return pids

    def memory_peak_bytes(self) -> int | None:
        """Peak bytes in use on the fullest device, after a clean stop."""
        try:
            with open(self.memory_path) as f:
                stats = json.load(f)
        except (OSError, ValueError):
            return None
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        peaks = [p for p in peaks if p is not None]
        return int(max(peaks)) if peaks else None

    def log_tail(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read()[-6000:].decode(errors="replace")

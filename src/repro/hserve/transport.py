"""Frontend <-> worker transports with pickle-free array framing.

The multi-host tier (docs/SERVING.md) splits ``HEServer`` into a
frontend that owns the queue/scheduler and N worker engines that own
device meshes.  Everything that crosses the cut goes through one wire
format so the in-process and subprocess deployments exercise the SAME
serialization path:

    frame := b"HSW1" | u32 header_len | header_json | payload*

The JSON header carries the message dict plus an ``arrays`` manifest
(name/dtype/shape per array); payloads are the raw C-contiguous bytes
concatenated in manifest order.  No pickle anywhere — a worker can only
ever receive ndarrays and JSON scalars, and the frame is portable
across interpreter versions.

Two transports share the interface (``send`` / ``recv`` / ``kill`` /
``alive`` / ``close``):

- ``InProcTransport`` drives a ``WorkerEngine`` in this process.  Every
  batch still round-trips the byte framing (encode -> decode -> handle
  -> encode -> decode), so frame bugs surface in fast unit tests, and
  ``kill()`` drops undelivered replies — the "worker died mid-batch"
  fault the requeue tests inject.
- ``SubprocessTransport`` spawns ``python -m repro.hserve.worker`` and
  speaks frames over its stdin/stdout pipes — a real process boundary
  with its own XLA host devices.
"""

from __future__ import annotations

import json
import os
import struct
import subprocess
import sys
from collections import deque
from typing import Any, Dict, Mapping, Tuple

import numpy as np

MAGIC = b"HSW1"
_LEN = struct.Struct("<I")

__all__ = [
    "WorkerDied",
    "encode_frame",
    "decode_frame",
    "read_frame",
    "InProcTransport",
    "SubprocessTransport",
]


class WorkerDied(RuntimeError):
    """The worker on the other end of a transport is gone.

    Raised by ``send``/``recv`` on broken pipes, EOF mid-frame, or a
    killed in-process worker.  The frontend catches this, marks the
    worker dead, and requeues its in-flight batch.
    """


def encode_frame(head: Dict[str, Any],
                 arrays: Mapping[str, np.ndarray] | None = None) -> bytes:
    """Serialize a message dict + named ndarrays into one frame."""
    arrays = arrays or {}
    manifest = []
    payloads = []
    for name, a in arrays.items():
        a = np.ascontiguousarray(a)
        manifest.append({"name": name, "dtype": str(a.dtype),
                         "shape": list(a.shape)})
        payloads.append(a.tobytes())
    header = dict(head)
    header["arrays"] = manifest
    hj = json.dumps(header, separators=(",", ":")).encode()
    return b"".join([MAGIC, _LEN.pack(len(hj)), hj, *payloads])


def decode_frame(buf: bytes) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
    """Inverse of :func:`encode_frame` over a complete in-memory frame."""
    if buf[:4] != MAGIC:
        raise WorkerDied(f"bad frame magic {buf[:4]!r}")
    (hlen,) = _LEN.unpack(buf[4:8])
    head = json.loads(buf[8:8 + hlen].decode())
    off = 8 + hlen
    arrays: Dict[str, np.ndarray] = {}
    for m in head.pop("arrays", []):
        dt = np.dtype(m["dtype"])
        n = int(np.prod(m["shape"], dtype=np.int64)) * dt.itemsize
        arrays[m["name"]] = np.frombuffer(
            buf[off:off + n], dtype=dt).reshape(m["shape"])
        off += n
    return head, arrays


def _read_exact(stream: Any, n: int) -> bytes:
    chunks = []
    got = 0
    while got < n:
        c = stream.read(n - got)
        if not c:
            raise WorkerDied("worker stream closed mid-frame")
        chunks.append(c)
        got += len(c)
    return b"".join(chunks)


def read_frame(stream: Any) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
    """Read one frame from a binary stream (worker stdout / stdin)."""
    magic = stream.read(4)
    if not magic:
        raise WorkerDied("worker stream closed (EOF)")
    if magic != MAGIC:
        raise WorkerDied(f"bad frame magic {magic!r}")
    (hlen,) = _LEN.unpack(_read_exact(stream, 4))
    head = json.loads(_read_exact(stream, hlen).decode())
    arrays: Dict[str, np.ndarray] = {}
    for m in head.pop("arrays", []):
        dt = np.dtype(m["dtype"])
        n = int(np.prod(m["shape"], dtype=np.int64)) * dt.itemsize
        arrays[m["name"]] = np.frombuffer(
            _read_exact(stream, n), dtype=dt).reshape(m["shape"])
    return head, arrays


class InProcTransport:
    """Drive a ``WorkerEngine`` in-process, through the byte framing.

    ``send`` is synchronous: the worker computes the reply inside the
    call and the reply frame is buffered until ``recv``.  ``kill()``
    between the two models a worker that finished computing but died
    before delivering — exactly the in-flight window the frontend must
    requeue.
    """

    kind = "inproc"

    def __init__(self, worker: Any) -> None:
        self.worker = worker
        self._replies: deque = deque()
        self._dead = False

    @property
    def alive(self) -> bool:
        return not self._dead

    def send(self, head: Dict[str, Any],
             arrays: Mapping[str, np.ndarray] | None = None) -> None:
        if self._dead:
            raise WorkerDied(f"worker {self.worker.wid} is dead")
        h, a = decode_frame(encode_frame(head, arrays))
        reply = self.worker.handle(h, a)
        if reply is not None:
            rhead, rarrays = reply
            self._replies.append(encode_frame(rhead, rarrays))

    def recv(self) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
        if self._dead:
            raise WorkerDied(f"worker {self.worker.wid} is dead")
        if not self._replies:
            raise WorkerDied(
                f"worker {self.worker.wid}: no reply pending")
        return decode_frame(self._replies.popleft())

    def kill(self) -> None:
        """Simulate worker death: drop any undelivered replies."""
        self._dead = True
        self._replies.clear()

    def revive(self) -> None:
        """Bring a killed in-process worker back (test harness only)."""
        self._dead = False
        self._replies.clear()

    def close(self) -> None:
        self._dead = True
        self._replies.clear()


def _parent_holds_accelerator() -> bool:
    """True once this process has initialised a non-CPU jax backend.

    Only already-initialised backends are inspected: asking jax for its
    devices would itself claim the chip.
    """
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    import jax
    return any(d.platform != "cpu" for d in jax.devices())


class SubprocessTransport:
    """Frames over the stdin/stdout pipes of a spawned worker process.

    `platform` is the child's ``JAX_PLATFORMS`` ("cpu", "tpu", ...). It
    has no default: a chip belongs to one process at a time, so a child
    that needs the chip the parent already holds would fail or hang —
    that case raises here instead of spawning. `devices` forces the
    child's host device count on the CPU platform.
    """

    kind = "subprocess"

    def __init__(self, platform: str, *, devices: int = 1,
                 env: Mapping[str, str] | None = None) -> None:
        if platform != "cpu" and _parent_holds_accelerator():
            raise RuntimeError(
                f"this process already holds the accelerator; a "
                f"{platform!r} worker subprocess could not get the chip. "
                "Use in-process workers (transport='inproc') instead.")
        # spawn args are kept so :meth:`respawn` can relaunch an
        # identical process after a crash
        self._platform = platform
        self._devices = devices
        self._env = dict(env) if env else None
        self.proc = self._spawn()

    def _spawn(self) -> subprocess.Popen:
        import repro
        # repro may be a namespace package (__file__ is None) — resolve
        # the src dir from its search path instead
        src_dir = os.path.dirname(
            os.path.abspath(list(repro.__path__)[0]))
        penv = dict(os.environ)
        penv.update(self._env or {})
        pp = penv.get("PYTHONPATH", "")
        penv["PYTHONPATH"] = src_dir + (os.pathsep + pp if pp else "")
        penv["JAX_PLATFORMS"] = self._platform
        if self._platform == "cpu":
            penv["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={self._devices}")
        # -c instead of -m: the package __init__ imports the worker
        # module, so `-m` would re-execute it as __main__ (runpy warns)
        return subprocess.Popen(
            [sys.executable, "-c",
             "from repro.hserve.worker import main; main()"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=penv)

    def respawn(self) -> None:
        """Relaunch the worker process with the original spawn args.

        The new process is a BLANK interpreter: it has no params, keys,
        tables, or compiled steps — the owner must replay the init
        frame (and await its ack) before routing work to it.
        `HEFrontend.revive_workers` does exactly that.
        """
        if self.alive:
            self.proc.kill()
        self.proc.wait(timeout=30)
        self.proc = self._spawn()

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None

    def send(self, head: Dict[str, Any],
             arrays: Mapping[str, np.ndarray] | None = None) -> None:
        if not self.alive:
            raise WorkerDied("worker process exited "
                             f"(rc={self.proc.returncode})")
        try:
            assert self.proc.stdin is not None
            self.proc.stdin.write(encode_frame(head, arrays))
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError) as e:
            raise WorkerDied(f"worker pipe broke: {e}") from e

    def recv(self) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
        assert self.proc.stdout is not None
        return read_frame(self.proc.stdout)

    def kill(self) -> None:
        self.proc.kill()
        self.proc.wait(timeout=30)

    def close(self) -> None:
        if self.alive:
            try:
                self.send({"type": "shutdown"})
                self.proc.wait(timeout=30)
            except (WorkerDied, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait(timeout=30)

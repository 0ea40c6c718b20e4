"""Shipper API: register / pull / renew / free KV byte bundles.

Prefers the native C++ core (llmd_tpu/native/kvship.cpp); falls back to a
pure-Python server/client speaking the identical length-prefixed wire
protocol, so mixed deployments interoperate. Semantics follow the reference
transfer layer (operations-vllm.md:18-47,155-160): pull model, leases with
consumer heartbeats, free-notify, reaper-based reclamation.
"""

from __future__ import annotations

import ctypes
import socket
import socketserver
import struct
import threading
import time

from llmd_tpu.kvtransfer import native

MAGIC = 0x4B565348  # "KVSH"
OP_PULL, OP_FREE, OP_RENEW, OP_STAT = 1, 2, 3, 4
ST_OK, ST_NOT_FOUND, ST_ERR = 0, 1, 2

# Reference default: 30s initial lease, heartbeat at 2/3 of the lease
# (operations-vllm.md:155-160).
DEFAULT_LEASE_MS = 30_000


class PullError(RuntimeError):
    def __init__(self, msg: str, status: int = ST_ERR) -> None:
        super().__init__(msg)
        self.status = status


# --------------------------------------------------------------------------- #
# Server


class ShipperServer:
    """Producer-side registry + TCP server.

    One instance per engine process; serves both metadata and KV bytes (the
    reference's TPU_SIDE_CHANNEL_PORT / TPU_KV_TRANSFER_PORT pair folded
    into one port).
    """

    def __init__(self, port: int = 0) -> None:
        self._native = native.load()
        self._handle = None
        self._py = None
        # Registrations inside the native call (ctypes lets go of the
        # interpreter there): ``close`` waits them out before it destroys
        # the server under them.
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._registering = 0  # llmd: guarded_by(_lock)
        if self._native is not None:
            self._handle = self._native.kvship_server_create(port)
        if self._handle:
            self.port = self._native.kvship_server_port(self._handle)
            self.backend = "native"
        else:
            self._py = _PyServer(port)
            self.port = self._py.port
            self.backend = "python"

    def register(
        self,
        key: str,
        data,
        lease_ms: int = DEFAULT_LEASE_MS,
        header: bytes = b"",
    ) -> None:
        """Register a bundle as header+payload.

        ``data`` is bytes or anything exposing a C-contiguous buffer (e.g. a
        numpy array); the buffer-protocol path hands the raw pointer to the
        native server, which makes the single owning copy — no Python-side
        concat or intermediate copy of a multi-hundred-MB KV payload.
        """
        with self._lock:
            handle = self._handle
            if handle is None and self._py is None:
                # Closed/crashed shipper: a clean error for the staging
                # thread to log — NOT an AttributeError that could leak
                # upward and take the engine step loop down with it.
                raise RuntimeError("shipper server is closed")
            self._registering += 1
        try:
            self._register(handle, key, data, lease_ms, header)
        finally:
            with self._lock:
                self._registering -= 1
                self._idle.notify_all()

    def _register(self, handle, key: str, data, lease_ms: int, header: bytes) -> None:
        if handle:
            mv = memoryview(data).cast("B")
            n = len(mv)
            if mv.readonly:  # bytes path (tests / small payloads): copy
                buf = (ctypes.c_uint8 * n).from_buffer_copy(mv)
            else:  # numpy path: zero-copy view of the array's buffer
                buf = (ctypes.c_uint8 * n).from_buffer(mv)
            dptr = ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8))
            hbuf = (ctypes.c_uint8 * max(len(header), 1)).from_buffer_copy(
                header or b"\0"
            )
            hptr = ctypes.cast(hbuf, ctypes.POINTER(ctypes.c_uint8))
            self._native.kvship_register2(
                handle, key.encode(), hptr, len(header), dptr, n, lease_ms
            )
        else:
            py = self._py
            if py is None:  # closed meanwhile
                raise RuntimeError("shipper server is closed")
            py.register(key, header + bytes(data), lease_ms)

    def unregister(self, key: str) -> bool:
        if self._handle:
            return self._native.kvship_unregister(self._handle, key.encode()) == 0
        return self._py.unregister(key) if self._py else False

    @property
    def registered_bytes(self) -> int:
        if self._handle:
            return self._native.kvship_registered_bytes(self._handle)
        return self._py.registered_bytes if self._py else 0

    @property
    def registered_count(self) -> int:
        if self._handle:
            return self._native.kvship_registered_count(self._handle)
        return self._py.registered_count if self._py else 0

    @property
    def expired_count(self) -> int:
        if self._handle:
            return self._native.kvship_expired_count(self._handle)
        return self._py.expired_count if self._py else 0

    def close(self) -> None:
        with self._lock:
            # No registration starts on the native server from here on,
            # and those inside it are waited out.
            handle, self._handle = self._handle, None
            while handle and self._registering:
                self._idle.wait()
        if handle:
            self._native.kvship_server_destroy(handle)
        elif self._py:
            self._py.close()
            self._py = None

    def __del__(self) -> None:  # best-effort
        try:
            self.close()
        # llmd: allow(broad-except) -- __del__ during interpreter teardown; nothing to surface to
        except Exception:
            pass


class _PyServer:
    """Pure-Python registry + threaded TCP server (protocol-identical)."""

    def __init__(self, port: int) -> None:
        self._entries: dict[str, tuple[bytes, float]] = {}  # llmd: guarded_by(_lock)
        self._lock = threading.Lock()
        self.expired_count = 0  # llmd: guarded_by(_lock)
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self) -> None:
                sock = self.request
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                sock.settimeout(60.0)  # idle-connection bound
                try:
                    while True:
                        hdr = _recv_exact(sock, 7)
                        if hdr is None:
                            return
                        magic, op, keylen = struct.unpack("<IBH", hdr)
                        if magic != MAGIC:
                            return
                        key = b""
                        if keylen:
                            key = _recv_exact(sock, keylen)
                            if key is None:
                                return
                        outer._dispatch(sock, op, key.decode())
                except (ConnectionError, OSError, struct.error):
                    return

        class Srv(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._srv = Srv(("0.0.0.0", port), Handler)
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(target=self._srv.serve_forever, daemon=True)
        self._thread.start()
        self._stop = threading.Event()
        self._reaper = threading.Thread(target=self._reap_loop, daemon=True)
        self._reaper.start()

    def _dispatch(self, sock: socket.socket, op: int, key: str) -> None:
        if op == OP_PULL:
            with self._lock:
                entry = self._entries.get(key)
            if entry is None:
                sock.sendall(struct.pack("<BQ", ST_NOT_FOUND, 0))
            else:
                sock.sendall(struct.pack("<BQ", ST_OK, len(entry[0])))
                sock.sendall(entry[0])
        elif op == OP_FREE:
            ok = self.unregister(key)
            sock.sendall(struct.pack("<BQ", ST_OK if ok else ST_NOT_FOUND, 0))
        elif op == OP_RENEW:
            raw = _recv_exact(sock, 8)
            if raw is None:
                return
            (lease_ms,) = struct.unpack("<Q", raw)
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None:
                    self._entries[key] = (entry[0], time.monotonic() + lease_ms / 1e3)
            st = ST_OK if entry is not None else ST_NOT_FOUND
            sock.sendall(struct.pack("<BQ", st, 0))
        elif op == OP_STAT:
            with self._lock:
                n = len(self._entries)
                b = sum(len(v[0]) for v in self._entries.values())
            sock.sendall(struct.pack("<BQQQ", ST_OK, 16, n, b))

    def register(self, key: str, data: bytes, lease_ms: int) -> None:
        with self._lock:
            self._entries[key] = (data, time.monotonic() + lease_ms / 1e3)

    def unregister(self, key: str) -> bool:
        with self._lock:
            return self._entries.pop(key, None) is not None

    @property
    def registered_bytes(self) -> int:
        with self._lock:
            return sum(len(v[0]) for v in self._entries.values())

    @property
    def registered_count(self) -> int:
        with self._lock:
            return len(self._entries)

    def _reap_loop(self) -> None:
        while not self._stop.wait(0.5):
            now = time.monotonic()
            with self._lock:
                dead = [k for k, (_, dl) in self._entries.items() if dl <= now]
                for k in dead:
                    del self._entries[k]
                    self.expired_count += 1

    def close(self) -> None:
        self._stop.set()
        self._srv.shutdown()
        self._srv.server_close()


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


# --------------------------------------------------------------------------- #
# Client ops (one connection per op, mirroring the native client)


def _py_roundtrip(
    host: str, port: int, op: int, key: str, lease_ms: int = 0
) -> tuple[int, bytes]:
    with socket.create_connection((host, port), timeout=30.0) as sock:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        kb = key.encode()
        msg = struct.pack("<IBH", MAGIC, op, len(kb)) + kb
        if op == OP_RENEW:
            msg += struct.pack("<Q", lease_ms)
        sock.sendall(msg)
        hdr = _recv_exact(sock, 9)
        if hdr is None:
            raise PullError("connection closed mid-response")
        st, length = struct.unpack("<BQ", hdr)
        payload = b""
        if length:
            payload = _recv_exact(sock, length)
            if payload is None:
                raise PullError("connection closed mid-payload")
        return st, payload


def pull(host: str, port: int, key: str) -> bytes:
    """One-sided pull of a registered bundle. Raises PullError if absent."""
    lib = native.load()
    if lib is not None:
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_uint64()
        st = lib.kvship_pull(
            host.encode(), port, key.encode(),
            ctypes.byref(out), ctypes.byref(out_len),
        )
        if st != ST_OK:
            raise PullError(
                f"pull {key!r} from {host}:{port} -> status {st}", status=st
            )
        try:
            return ctypes.string_at(out, out_len.value)
        finally:
            lib.kvship_buf_free(out)
    st, payload = _py_roundtrip(host, port, OP_PULL, key)
    if st != ST_OK:
        raise PullError(
            f"pull {key!r} from {host}:{port} -> status {st}", status=st
        )
    return payload


def pull_many(host: str, port: int, keys: list[str]) -> dict[str, bytes]:
    """Pull several bundles over ONE connection (pipelined requests).

    The federation restore path fetches every store-held page of a
    prefix run in one shot: one TCP connect + N request/response rounds
    on the same socket instead of N fresh connections (the per-page GET
    was the dominant fixed cost of a multi-page store hit). Keys the
    server does not hold are simply absent from the result; transport
    errors raise PullError (the caller's miss/degrade policy decides).

    Speaks the standard per-request wire protocol, so it works against
    both the python and native servers (their handlers loop on the
    connection); if the peer closes between requests, the remaining keys
    fall back to one-shot pulls.
    """
    out: dict[str, bytes] = {}
    if not keys:
        return out
    remaining = list(keys)
    try:
        with socket.create_connection((host, port), timeout=30.0) as sock:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            while remaining:
                key = remaining[0]
                kb = key.encode()
                sock.sendall(
                    struct.pack("<IBH", MAGIC, OP_PULL, len(kb)) + kb
                )
                hdr = _recv_exact(sock, 9)
                if hdr is None:
                    raise ConnectionError("peer closed mid-batch")
                st, length = struct.unpack("<BQ", hdr)
                payload = b""
                if length:
                    payload = _recv_exact(sock, length)
                    if payload is None:
                        raise ConnectionError("peer closed mid-payload")
                if st == ST_OK:
                    out[key] = payload
                remaining.pop(0)
    except (ConnectionError, OSError):
        # Mixed/native deployments that close per request: finish the
        # remainder as ordinary one-shot pulls (absent keys stay absent).
        for key in remaining:
            try:
                out[key] = pull(host, port, key)
            except PullError as e:
                if e.status != ST_NOT_FOUND:
                    raise
    return out


def pull_wait(
    host: str, port: int, key: str, deadline: float, poll_s: float = 0.01
) -> bytes:
    """Pull, retrying while the key is NOT-YET-registered (a producer that
    streams chunks as it stages them registers each one when its download
    completes). Hard errors and the ``deadline`` (monotonic) abort."""
    while True:
        try:
            return pull(host, port, key)
        except PullError as e:
            if e.status != ST_NOT_FOUND or time.monotonic() >= deadline:
                raise
        time.sleep(poll_s)


def free_notify(host: str, port: int, key: str) -> bool:
    """Tell the producer the bundle landed; it may reclaim the memory."""
    lib = native.load()
    if lib is not None:
        return lib.kvship_free_notify(host.encode(), port, key.encode()) == ST_OK
    try:
        st, _ = _py_roundtrip(host, port, OP_FREE, key)
    except (OSError, PullError):
        return False
    return st == ST_OK


def renew(host: str, port: int, key: str, lease_ms: int = DEFAULT_LEASE_MS) -> bool:
    """Consumer heartbeat: extend the producer-side lease."""
    lib = native.load()
    if lib is not None:
        return lib.kvship_renew(host.encode(), port, key.encode(), lease_ms) == ST_OK
    try:
        st, _ = _py_roundtrip(host, port, OP_RENEW, key, lease_ms)
    except (OSError, PullError):
        return False
    return st == ST_OK


def stat(host: str, port: int) -> tuple[int, int]:
    """(registered_count, registered_bytes) of a remote shipper."""
    lib = native.load()
    if lib is not None:
        arr = (ctypes.c_uint64 * 2)()
        if lib.kvship_stat(host.encode(), port, arr) != ST_OK:
            raise PullError(f"stat {host}:{port} failed")
        return arr[0], arr[1]
    st, payload = _py_roundtrip(host, port, OP_STAT, "")
    if st != ST_OK or len(payload) != 16:
        raise PullError(f"stat {host}:{port} failed")
    n, b = struct.unpack("<QQ", payload)
    return n, b

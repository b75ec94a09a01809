"""TCPStore: host-side key-value rendezvous.

Capability parity with the reference's TCPStore
(reference: paddle/phi/core/distributed/store/tcp_store.cc, pybind
paddle/fluid/pybind/communication.cc:140 create_or_get_global_tcp_store).

The server/client are native C++ (paddle_tpu/native/tcp_store.cc) loaded via
ctypes; a pure-Python server is the fallback when no toolchain exists.
Within a slice JAX's coordination service handles rendezvous — this store
carries the framework-level coordination (launch barriers, elastic
membership, cross-host handshakes).
"""
from __future__ import annotations

import ctypes
import os
import socket
import struct
import threading
from typing import Optional

__all__ = ["TCPStore", "create_or_get_global_tcp_store", "barrier"]


def _load_lib():
    from ..native import load_native
    lib = load_native("tcp_store")
    lib.pt_store_server_start.restype = ctypes.c_void_p
    lib.pt_store_server_start.argtypes = [ctypes.c_int,
                                          ctypes.POINTER(ctypes.c_int)]
    lib.pt_store_server_stop.argtypes = [ctypes.c_void_p]
    lib.pt_store_connect.restype = ctypes.c_int
    lib.pt_store_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                     ctypes.c_int]
    lib.pt_store_close.argtypes = [ctypes.c_int]
    lib.pt_store_set.restype = ctypes.c_int
    lib.pt_store_set.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                 ctypes.c_char_p, ctypes.c_uint32]
    lib.pt_store_get.restype = ctypes.c_int64
    lib.pt_store_get.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                 ctypes.c_int64, ctypes.c_void_p,
                                 ctypes.c_uint32]
    lib.pt_store_add.restype = ctypes.c_int64
    lib.pt_store_add.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                 ctypes.c_int64]
    lib.pt_store_wait.restype = ctypes.c_int
    lib.pt_store_wait.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                  ctypes.c_int64]
    lib.pt_store_check.restype = ctypes.c_int
    lib.pt_store_check.argtypes = [ctypes.c_int, ctypes.c_char_p]
    return lib


class _PyStoreServer:
    """Pure-Python fallback server speaking the same wire protocol."""

    def __init__(self, port: int):
        self._data = {}
        self._cond = threading.Condition()
        self._stop = False
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("0.0.0.0", port))
        self._sock.listen(128)
        self.port = self._sock.getsockname()[1]
        self._thread = threading.Thread(target=self._accept, daemon=True)
        self._thread.start()

    def _accept(self):
        while not self._stop:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _read(self, conn, n):
        buf = b""
        while len(buf) < n:
            chunk = conn.recv(n - len(buf))
            if not chunk:
                raise ConnectionError
            buf += chunk
        return buf

    def _serve(self, conn):
        try:
            while True:
                cmd = self._read(conn, 1)[0]
                klen = struct.unpack("<I", self._read(conn, 4))[0]
                key = self._read(conn, klen).decode()
                if cmd == 0:
                    vlen = struct.unpack("<I", self._read(conn, 4))[0]
                    val = self._read(conn, vlen)
                    with self._cond:
                        self._data[key] = val
                        self._cond.notify_all()
                    conn.sendall(b"\x00")
                elif cmd in (1, 3):
                    (timeout_ms,) = struct.unpack("<q", self._read(conn, 8))
                    with self._cond:
                        deadline = (None if timeout_ms < 0
                                    else timeout_ms / 1e3)
                        if key not in self._data:
                            self._cond.wait_for(
                                lambda: key in self._data or self._stop,
                                timeout=deadline)
                        val = self._data.get(key)
                    if cmd == 1:
                        if val is None:
                            conn.sendall(struct.pack("<I", 0xFFFFFFFF))
                        else:
                            conn.sendall(struct.pack("<I", len(val)) + val)
                    else:
                        conn.sendall(b"\x00" if val is not None else b"\x01")
                elif cmd == 2:
                    (delta,) = struct.unpack("<q", self._read(conn, 8))
                    with self._cond:
                        cur = 0
                        old = self._data.get(key)
                        if old is not None and len(old) == 8:
                            (cur,) = struct.unpack("<q", old)
                        new = cur + delta
                        self._data[key] = struct.pack("<q", new)
                        self._cond.notify_all()
                    conn.sendall(struct.pack("<q", new))
                elif cmd == 4:
                    with self._cond:
                        exists = key in self._data
                    conn.sendall(b"\x01" if exists else b"\x00")
                else:
                    return
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()

    def stop(self):
        self._stop = True
        with self._cond:
            self._cond.notify_all()
        try:
            self._sock.close()
        except OSError:
            pass


class _NativeClient:
    def __init__(self, lib, host, port, timeout):
        self._lib = lib
        self._fd = lib.pt_store_connect(host.encode(), port,
                                        int(timeout * 1000))
        if self._fd < 0:
            raise TimeoutError(f"cannot reach store at {host}:{port}")

    def set(self, key: bytes, value: bytes) -> bool:
        return self._lib.pt_store_set(self._fd, key, value, len(value)) == 0

    _GET_BUF = 1 << 16   # typical rendezvous values are tiny

    def get(self, key: bytes, timeout_ms: int) -> Optional[bytes]:
        buf = ctypes.create_string_buffer(self._GET_BUF)
        n = self._lib.pt_store_get(self._fd, key, timeout_ms, buf,
                                   self._GET_BUF)
        if n < 0:
            return None
        if n <= self._GET_BUF:
            return buf.raw[:n]
        # value larger than the fast-path buffer: re-fetch with exact size
        big = ctypes.create_string_buffer(int(n))
        n2 = self._lib.pt_store_get(self._fd, key, timeout_ms, big, int(n))
        return None if n2 < 0 else big.raw[:n2]

    def add(self, key: bytes, amount: int) -> int:
        v = self._lib.pt_store_add(self._fd, key, amount)
        if v == -(1 << 63):
            raise RuntimeError("store add failed")
        return int(v)

    def wait(self, key: bytes, timeout_ms: int) -> bool:
        return self._lib.pt_store_wait(self._fd, key, timeout_ms) == 0

    def check(self, key: bytes) -> bool:
        rc = self._lib.pt_store_check(self._fd, key)
        if rc < 0:
            # a lost connection is not "the key is absent": a receiver
            # that polls check() would wait on a dead store for good
            raise ConnectionError("store connection lost")
        return rc == 1

    def close(self):
        if self._fd >= 0:
            self._lib.pt_store_close(self._fd)
            self._fd = -1


class _PyClient:
    """Pure-Python client speaking the same wire protocol."""

    def __init__(self, host, port, timeout):
        import time
        deadline = time.monotonic() + timeout
        while True:
            try:
                self._sock = socket.create_connection((host, port),
                                                      timeout=5)
                break
            except OSError:
                if time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"cannot reach store at {host}:{port}")
                time.sleep(0.05)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _read(self, n):
        buf = b""
        while len(buf) < n:
            chunk = self._sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("store connection closed")
            buf += chunk
        return buf

    def _send_key(self, cmd, key: bytes):
        self._sock.sendall(bytes([cmd]) + struct.pack("<I", len(key)) + key)

    def set(self, key, value):
        self._send_key(0, key)
        self._sock.sendall(struct.pack("<I", len(value)) + value)
        return self._read(1) == b"\x00"

    def get(self, key, timeout_ms):
        self._send_key(1, key)
        self._sock.settimeout(max(timeout_ms / 1e3 + 5, 5))
        self._sock.sendall(struct.pack("<q", timeout_ms))
        (vlen,) = struct.unpack("<I", self._read(4))
        if vlen == 0xFFFFFFFF:
            return None
        return self._read(vlen)

    def add(self, key, amount):
        self._send_key(2, key)
        self._sock.sendall(struct.pack("<q", amount))
        return struct.unpack("<q", self._read(8))[0]

    def wait(self, key, timeout_ms):
        self._send_key(3, key)
        self._sock.settimeout(max(timeout_ms / 1e3 + 5, 5))
        self._sock.sendall(struct.pack("<q", timeout_ms))
        return self._read(1) == b"\x00"

    def check(self, key):
        self._send_key(4, key)
        return self._read(1) == b"\x01"

    def close(self):
        try:
            self._sock.close()
        except OSError:
            pass


class TCPStore:
    """reference-parity API: TCPStore(host, port, is_master, world_size,
    timeout) with set/get/add/wait/check."""

    MAX_VALUE = 1 << 26

    def __init__(self, host: str, port: int, is_master: bool = False,
                 world_size: int = 1, timeout: float = 300.0):
        self.host = host
        self.world_size = world_size
        self.timeout = timeout
        self._server = None
        self._py_server = None
        lib = None
        try:
            lib = _load_lib()
        except Exception:
            pass
        self._lib = lib
        if is_master:
            if lib is not None:
                out_port = ctypes.c_int(0)
                self._server = lib.pt_store_server_start(
                    port, ctypes.byref(out_port))
                if not self._server:
                    raise RuntimeError(f"cannot bind store on port {port}")
                self.port = out_port.value
            else:
                self._py_server = _PyStoreServer(port)
                self.port = self._py_server.port
        else:
            self.port = port
        if lib is not None:
            self._client = _NativeClient(lib, host, self.port, timeout)
        else:
            self._client = _PyClient(host, self.port, timeout)
        self._lock = threading.Lock()

    # -- API ---------------------------------------------------------------
    def set(self, key: str, value) -> None:
        if isinstance(value, str):
            value = value.encode()
        with self._lock:
            ok = self._client.set(key.encode(), value)
        if not ok:
            raise RuntimeError(f"store set({key}) failed")

    def get(self, key: str, timeout: Optional[float] = None) -> bytes:
        t = self.timeout if timeout is None else timeout
        with self._lock:
            val = self._client.get(key.encode(), int(t * 1000))
        if val is None:
            raise TimeoutError(f"store get({key}) timed out after {t}s")
        return val

    def add(self, key: str, amount: int) -> int:
        with self._lock:
            return self._client.add(key.encode(), amount)

    def wait(self, key: str, timeout: Optional[float] = None) -> None:
        t = self.timeout if timeout is None else timeout
        with self._lock:
            ok = self._client.wait(key.encode(), int(t * 1000))
        if not ok:
            raise TimeoutError(f"store wait({key}) timed out after {t}s")

    def check(self, key: str) -> bool:
        with self._lock:
            return self._client.check(key.encode())

    def close(self) -> None:
        """Idempotent shutdown of the client connection and (if master)
        the server."""
        client, self._client = getattr(self, "_client", None), None
        server, self._server = getattr(self, "_server", None), None
        py_server, self._py_server = getattr(self, "_py_server", None), None
        try:
            if client is not None:
                client.close()
            if server:
                self._lib.pt_store_server_stop(server)
            if py_server is not None:
                py_server.stop()
        except Exception:
            pass

    def __del__(self):
        self.close()


def barrier(store: TCPStore, key: str, world_size: int,
            timeout: Optional[float] = None) -> None:
    """Store-based reusable barrier: each rank increments a counter; the
    last arriver of each generation releases a per-generation key, so the
    same ``key`` can synchronize every epoch (reference: tcp_store-based
    barrier in launch/elastic flows)."""
    arrived = store.add("barrier/" + key, 1)
    gen = (arrived - 1) // world_size
    if arrived % world_size == 0:
        store.set(f"barrier_done/{key}/{gen}", b"1")
    store.wait(f"barrier_done/{key}/{gen}", timeout)


_global_store: Optional[TCPStore] = None
_global_store_lock = threading.Lock()


def create_or_get_global_tcp_store() -> TCPStore:
    """reference: pybind communication.cc:140 — rank 0 hosts, others
    connect, addresses from PADDLE_MASTER/PADDLE_TRAINER_ENDPOINTS env.
    Thread-safe: concurrent isend/irecv tasks must not double-bind."""
    global _global_store
    with _global_store_lock:
        return _create_or_get_global_tcp_store_locked()


def _create_or_get_global_tcp_store_locked() -> TCPStore:
    global _global_store
    if _global_store is not None:
        return _global_store
    endpoint = os.environ.get("PADDLE_MASTER")
    if endpoint is None:
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "127.0.0.1:6170")
        endpoint = eps.split(",")[0]
    host, port = endpoint.rsplit(":", 1)
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    # PADDLE_MASTER_BOUND: the launcher already hosts the store at this
    # address (multi-node mode) — every rank connects as a client
    bound = os.environ.get("PADDLE_MASTER_BOUND", "") not in ("", "0")
    _global_store = TCPStore(host, int(port),
                             is_master=(rank == 0 and not bound),
                             world_size=world)
    return _global_store

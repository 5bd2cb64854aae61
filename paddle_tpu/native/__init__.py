"""Native (C++) runtime components, loaded via ctypes.

Reference analog: the reference implements its data transport and
rendezvous natively (framework/data_feed.cc, distributed/store/tcp_store.cc)
— so does this framework: native/src/*.cc builds libptnative.so (CMake or
direct g++; no pybind11 — pure C ABI).

Components:
  ShmRingBuffer  process-shared ring for DataLoader worker batches
  TCPStore       rendezvous KV store (server + client)

The library auto-builds on first import when a toolchain is present;
`is_available()` gates callers so pure-Python fallbacks keep working.
"""

import os
import subprocess

_LIB = None
_BUILD_ERR = None


_SRC_FILES = ("ringbuffer.cc", "tcp_store.cc", "p2p.cc")


def _lib_path():
    return os.path.join(os.path.dirname(__file__), "libptnative.so")


def _src_dir():
    return os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "..", "native", "src"))


def _build_cmd(out):
    srcs = [os.path.join(_src_dir(), f) for f in _SRC_FILES]
    return ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-Wall",
            *srcs, "-o", out, "-lpthread", "-lrt"]


def _src_digest():
    """sha256 over the build command's shape and every file under
    native/src — what decides whether an existing library is current.
    (mtimes do not: a copied or freshly checked-out tree does not
    preserve them, so a stale .so could look newer than its sources.)"""
    import hashlib
    h = hashlib.sha256(" ".join(_build_cmd("")).encode())
    for f in sorted(os.listdir(_src_dir())):
        h.update(f.encode())
        with open(os.path.join(_src_dir(), f), "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


def _build(digest):
    """Compile to a temp name and rename, library first and its digest
    stamp last, so a concurrent importer never loads a half-written
    library or trusts a stamp whose library is not there yet."""
    out = _lib_path()
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(_build_cmd(tmp), check=True, capture_output=True)
        os.replace(tmp, out)
        with open(tmp, "w") as f:
            f.write(digest)
        os.replace(tmp, out + ".sha256")
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load():
    global _LIB, _BUILD_ERR
    if _LIB is not None or _BUILD_ERR is not None:
        return _LIB
    import ctypes
    path = _lib_path()
    try:
        digest = _src_digest()
        try:
            with open(path + ".sha256") as f:
                current = f.read().strip() == digest
        except OSError:
            current = False
        if not current or not os.path.exists(path):
            _build(digest)
        _LIB = ctypes.CDLL(path)
        _configure(_LIB, ctypes)
    except Exception as e:  # no toolchain / unsupported platform
        _BUILD_ERR = e
        _LIB = None
    return _LIB


def _configure(lib, ctypes):
    c = ctypes
    lib.ptrb_create.restype = c.c_void_p
    lib.ptrb_create.argtypes = [c.c_char_p, c.c_uint32, c.c_uint64]
    lib.ptrb_open.restype = c.c_void_p
    lib.ptrb_open.argtypes = [c.c_char_p]
    lib.ptrb_slot_size.restype = c.c_uint64
    lib.ptrb_slot_size.argtypes = [c.c_void_p]
    lib.ptrb_push.restype = c.c_int
    lib.ptrb_push.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64, c.c_double]
    lib.ptrb_pop.restype = c.c_int64
    lib.ptrb_pop.argtypes = [c.c_void_p, c.c_void_p, c.c_uint64, c.c_double]
    lib.ptrb_close_producer.restype = None
    lib.ptrb_close_producer.argtypes = [c.c_void_p]
    lib.ptrb_size.restype = c.c_int
    lib.ptrb_size.argtypes = [c.c_void_p]
    lib.ptrb_close.restype = None
    lib.ptrb_close.argtypes = [c.c_void_p, c.c_int]

    lib.ptts_server_start.restype = c.c_void_p
    lib.ptts_server_start.argtypes = [c.c_int]
    lib.ptts_server_port.restype = c.c_int
    lib.ptts_server_port.argtypes = [c.c_void_p]
    lib.ptts_server_stop.restype = None
    lib.ptts_server_stop.argtypes = [c.c_void_p]
    lib.ptts_connect.restype = c.c_void_p
    lib.ptts_connect.argtypes = [c.c_char_p, c.c_int, c.c_double]
    lib.ptts_set.restype = c.c_int
    lib.ptts_set.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_uint64]
    lib.ptts_get.restype = c.c_int64
    lib.ptts_get.argtypes = [c.c_void_p, c.c_char_p, c.c_void_p, c.c_uint64,
                             c.c_double]
    lib.ptts_add.restype = c.c_int64
    lib.ptts_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.ptts_del.restype = c.c_int
    lib.ptts_del.argtypes = [c.c_void_p, c.c_char_p]
    lib.ptts_close.restype = None
    lib.ptts_close.argtypes = [c.c_void_p]

    lib.ptpp_create.restype = c.c_void_p
    lib.ptpp_create.argtypes = [c.c_int]
    lib.ptpp_port.restype = c.c_int
    lib.ptpp_port.argtypes = [c.c_void_p]
    lib.ptpp_probe.restype = c.c_int64
    lib.ptpp_probe.argtypes = [c.c_void_p, c.c_uint64, c.c_double]
    lib.ptpp_recv.restype = c.c_int64
    lib.ptpp_recv.argtypes = [c.c_void_p, c.c_uint64, c.c_void_p,
                              c.c_uint64, c.c_double]
    lib.ptpp_send.restype = c.c_int
    lib.ptpp_send.argtypes = [c.c_void_p, c.c_char_p, c.c_int, c.c_uint64,
                              c.c_char_p, c.c_uint64]
    lib.ptpp_destroy.restype = None
    lib.ptpp_destroy.argtypes = [c.c_void_p]


def decode_counter(raw) -> int:
    """Decode a TCPStore counter value: ``add()`` keeps counters as raw
    little-endian int64 bytes; a ``set()`` writes ascii. One decoder for
    every consumer (elastic heartbeats, launch re-form watch)."""
    if isinstance(raw, (bytes, bytearray)) and len(raw) == 8:
        return int.from_bytes(raw, "little", signed=True)
    return int(raw)


def is_available() -> bool:
    return _load() is not None


def get_lib():
    lib = _load()
    if lib is None:
        raise RuntimeError(
            f"native library unavailable: {_BUILD_ERR!r}")
    return lib


class ShmRingBuffer:
    """Process-shared MPMC ring of fixed-size slots (see ringbuffer.cc)."""

    def __init__(self, name: str, nslots: int = 8,
                 slot_size: int = 8 << 20, create: bool = True):
        import ctypes
        self._ct = ctypes
        self._lib = get_lib()
        self.name = name
        if create:
            self._h = self._lib.ptrb_create(name.encode(), nslots, slot_size)
        else:
            self._h = self._lib.ptrb_open(name.encode())
        if not self._h:
            raise RuntimeError(f"shm ring {name!r} "
                               f"{'create' if create else 'open'} failed")
        self._owner = create
        self.slot_size = self._lib.ptrb_slot_size(self._h)
        self._popbuf = ctypes.create_string_buffer(self.slot_size)

    def push(self, data: bytes, timeout: float = 30.0):
        rc = self._lib.ptrb_push(self._h, data, len(data), timeout)
        if rc == -1:
            raise TimeoutError(f"push timed out after {timeout}s")
        if rc == -2:
            raise ValueError(f"payload {len(data)} > slot {self.slot_size}")
        if rc == -3:
            raise BrokenPipeError("ring closed")
        if rc != 0:
            raise RuntimeError(f"push failed rc={rc}")

    def pop(self, timeout: float = 30.0) -> bytes:
        n = self._lib.ptrb_pop(self._h, self._popbuf, self.slot_size,
                               timeout)
        if n == -1:
            raise TimeoutError(f"pop timed out after {timeout}s")
        if n == -3:
            raise EOFError("ring closed and drained")
        if n < 0:
            raise RuntimeError(f"pop failed rc={n}")
        # string_at copies exactly n bytes (.raw[:n] would materialize the
        # whole slot and then slice — 2x slot_size churn per batch)
        return self._ct.string_at(self._popbuf, n)

    def close_producer(self):
        self._lib.ptrb_close_producer(self._h)

    def __len__(self):
        return self._lib.ptrb_size(self._h)

    def close(self, unlink: bool = None):
        if self._h:
            self._lib.ptrb_close(
                self._h, 1 if (unlink if unlink is not None else
                               self._owner) else 0)
            self._h = None


class TCPStore:
    """Rendezvous KV store ≙ paddle TCPStore (tcp_store.cc).

    is_master=True also runs the server in-process (rank 0)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 is_master: bool = False, timeout: float = 30.0):
        self._lib = get_lib()
        self._srv = None
        if is_master:
            self._srv = self._lib.ptts_server_start(port)
            if not self._srv:
                raise RuntimeError(f"TCPStore server failed on port {port}")
            port = self._lib.ptts_server_port(self._srv)
        self.host, self.port = host, port
        self._cli = self._lib.ptts_connect(host.encode(), port, timeout)
        if not self._cli:
            if self._srv:
                self._lib.ptts_server_stop(self._srv)
            raise ConnectionError(f"TCPStore connect {host}:{port} failed")

    def set(self, key: str, value: bytes):
        if isinstance(value, str):
            value = value.encode()
        rc = self._lib.ptts_set(self._cli, key.encode(), value, len(value))
        if rc != 0:
            raise RuntimeError(f"set({key!r}) failed rc={rc}")

    def get(self, key: str, timeout: float = 30.0) -> bytes:
        import ctypes
        cap = 1 << 20
        buf = ctypes.create_string_buffer(cap)
        n = self._lib.ptts_get(self._cli, key.encode(), buf, cap, timeout)
        if n == -1:
            raise TimeoutError(f"get({key!r}) timed out")
        if n < 0:
            raise RuntimeError(f"get({key!r}) failed rc={n}")
        return buf.raw[:n]

    def add(self, key: str, amount: int = 1) -> int:
        v = self._lib.ptts_add(self._cli, key.encode(), amount)
        if v == -(2 ** 63):
            raise RuntimeError(f"add({key!r}) failed")
        return v

    def delete_key(self, key: str):
        self._lib.ptts_del(self._cli, key.encode())

    def wait(self, keys, timeout: float = 30.0):
        for k in (keys if isinstance(keys, (list, tuple)) else [keys]):
            self.get(k, timeout=timeout)

    def close(self):
        if self._cli:
            self._lib.ptts_close(self._cli)
            self._cli = None
        if self._srv:
            self._lib.ptts_server_stop(self._srv)
            self._srv = None


class P2PEndpoint:
    """Tag-addressed point-to-point message endpoint (see p2p.cc;
    ≙ fleet_executor/message_bus.cc + interceptor.cc mailboxes). One per
    rank: ``send(host, port, tag, payload)`` is fire-and-forget on a
    cached connection; ``recv(tag)`` blocks on the local mailbox."""

    def __init__(self, port: int = 0):
        import ctypes
        self._ct = ctypes
        self._lib = get_lib()
        self._h = self._lib.ptpp_create(port)
        if not self._h:
            raise RuntimeError(f"P2PEndpoint failed to listen on {port}")
        self.port = self._lib.ptpp_port(self._h)

    def send(self, host: str, port: int, tag: int, payload: bytes):
        rc = self._lib.ptpp_send(self._h, host.encode(), port, tag,
                                 payload, len(payload))
        if rc == -1:
            raise ConnectionError(f"p2p connect {host}:{port} failed")
        if rc != 0:
            raise BrokenPipeError(f"p2p send to {host}:{port} failed")

    def recv(self, tag: int, timeout: float = 60.0) -> bytes:
        n = self._lib.ptpp_probe(self._h, tag, timeout)
        if n == -1:
            raise TimeoutError(f"p2p recv(tag={tag}) timed out "
                               f"after {timeout}s")
        buf = self._ct.create_string_buffer(max(int(n), 1))
        m = self._lib.ptpp_recv(self._h, tag, buf, n, 0.0)
        if m < 0:
            raise RuntimeError(f"p2p recv(tag={tag}) failed rc={m}")
        return self._ct.string_at(buf, m)

    def close(self):
        if self._h:
            self._lib.ptpp_destroy(self._h)
            self._h = None


__all__ = ["is_available", "get_lib", "decode_counter",
           "ShmRingBuffer", "TCPStore",
           "P2PEndpoint"]

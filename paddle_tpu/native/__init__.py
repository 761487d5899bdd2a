"""ctypes bindings for the native runtime (C++) components.

The reference's native components (SURVEY §2 bold rows) that survive the
TPU redesign as host-side C++: RecordIO data chunk IO, the buddy
allocator (a host arena kept for parity; HBM itself is PJRT-managed), and the
fault-tolerant master task-queue service. Loaded lazily; ``make``
brings the binaries up to date with their sources first, and a build
that fails raises with the compiler's output — nothing falls back to a
stale or missing binary in silence.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libpaddle_tpu_native.so")
_lib: Optional[ctypes.CDLL] = None


def build(target: str = "all", fresh: bool = False) -> None:
    """``make <target>`` in this directory (the Makefile's targets:
    all, infer, infer-nopy, pjrt, serving). ``fresh`` rebuilds
    unconditionally (``make -B``) — for runs that must not trust a
    binary that happened to be on disk, e.g. chip_smoke.py, whose tree
    is a copy that may carry stale ignored build products. Raises
    RuntimeError with make's and the compiler's output on failure."""
    cmd = ["make", "-C", _DIR] + (["-B"] if fresh else []) + [target]
    r = subprocess.run(cmd, capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"{' '.join(cmd)} failed (rc={r.returncode}):\n"
                           f"{r.stdout}{r.stderr}")


def ensure_built() -> bool:
    """Bring libpaddle_tpu_native.so up to date with its sources (make
    decides whether anything is to do); raises when the build fails."""
    build()
    return True


def load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    ensure_built()
    lib = ctypes.CDLL(_LIB_PATH)
    # recordio
    lib.recordio_writer_open.restype = ctypes.c_void_p
    lib.recordio_writer_open.argtypes = [ctypes.c_char_p]
    lib.recordio_writer_write.restype = ctypes.c_int
    lib.recordio_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_uint32]
    lib.recordio_writer_close.restype = ctypes.c_uint64
    lib.recordio_writer_close.argtypes = [ctypes.c_void_p]
    lib.recordio_reader_open.restype = ctypes.c_void_p
    lib.recordio_reader_open.argtypes = [ctypes.c_char_p]
    lib.recordio_reader_count.restype = ctypes.c_uint64
    lib.recordio_reader_count.argtypes = [ctypes.c_void_p]
    lib.recordio_reader_read.restype = ctypes.c_int64
    lib.recordio_reader_read.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                         ctypes.c_char_p, ctypes.c_uint64]
    lib.recordio_reader_close.argtypes = [ctypes.c_void_p]
    # buddy allocator
    lib.buddy_create.restype = ctypes.c_void_p
    lib.buddy_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
    lib.buddy_alloc.restype = ctypes.c_void_p
    lib.buddy_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.buddy_free.restype = ctypes.c_int
    lib.buddy_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.buddy_used.restype = ctypes.c_uint64
    lib.buddy_used.argtypes = [ctypes.c_void_p]
    lib.buddy_peak.restype = ctypes.c_uint64
    lib.buddy_peak.argtypes = [ctypes.c_void_p]
    lib.buddy_destroy.argtypes = [ctypes.c_void_p]
    # master
    lib.master_start.restype = ctypes.c_void_p
    lib.master_start.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
                                 ctypes.c_int]
    lib.master_port.restype = ctypes.c_int
    lib.master_port.argtypes = [ctypes.c_void_p]
    lib.master_stop.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


class NativeRecordIOWriter:
    def __init__(self, path: str):
        self._lib = lib = load()
        self._h = lib.recordio_writer_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def write(self, payload: bytes):
        if isinstance(payload, str):
            payload = payload.encode()
        if self._lib.recordio_writer_write(self._h, payload, len(payload)) != 0:
            raise IOError("write failed")

    def close(self) -> int:
        n = self._lib.recordio_writer_close(self._h)
        self._h = None
        return n

    def __enter__(self):
        return self

    def __exit__(self, *a):
        if self._h:
            self.close()


class NativeRecordIOReader:
    def __init__(self, path: str):
        self._lib = lib = load()
        self._h = lib.recordio_reader_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def __len__(self):
        return self._lib.recordio_reader_count(self._h)

    def read(self, i: int) -> bytes:
        size = self._lib.recordio_reader_read(self._h, i, None, 0)
        if size < 0:
            raise IndexError(i)
        buf = ctypes.create_string_buffer(size)
        n = self._lib.recordio_reader_read(self._h, i, buf, size)
        if n == -2:
            raise IOError(f"record {i}: crc mismatch")
        if n < 0:
            raise IOError(f"record {i}: read failed")
        return buf.raw[:n]

    def __iter__(self):
        for i in range(len(self)):
            yield self.read(i)

    def close(self):
        self._lib.recordio_reader_close(self._h)
        self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        if self._h:
            self.close()


class BuddyAllocator:
    """Host arena allocator (paddle/memory buddy parity)."""

    def __init__(self, arena_size: int = 1 << 24, min_block: int = 256):
        self._lib = lib = load()
        self._h = lib.buddy_create(arena_size, min_block)
        if not self._h:
            raise MemoryError(
                f"buddy arena allocation failed (arena_size={arena_size})")

    def alloc(self, size: int) -> Optional[int]:
        p = self._lib.buddy_alloc(self._h, size)
        return p or None

    def free(self, ptr: int):
        if self._lib.buddy_free(self._h, ptr) != 0:
            raise ValueError("unknown pointer")

    @property
    def used(self) -> int:
        return self._lib.buddy_used(self._h)

    @property
    def peak(self) -> int:
        return self._lib.buddy_peak(self._h)

    def destroy(self):
        self._lib.buddy_destroy(self._h)
        self._h = None


class MasterServer:
    """In-process master service handle (ParameterServerController /
    --start_pserver analog: the trainer can self-host the coordinator)."""

    def __init__(self, port: int = 0, snapshot_path: str = "",
                 timeout_s: int = 60, max_failures: int = 3):
        self._lib = lib = load()
        self._h = lib.master_start(port, snapshot_path.encode(), timeout_s,
                                   max_failures)
        if not self._h:
            raise RuntimeError("master failed to start")

    @property
    def port(self) -> int:
        return self._lib.master_port(self._h)

    def stop(self):
        if self._h:
            self._lib.master_stop(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.stop()


def _routable_local_ip() -> str:
    """Best local address for cross-host advertisement: the UDP-connect
    probe picks the interface that routes outward (gethostbyname(hostname)
    commonly yields loopback on /etc/hosts-style setups)."""
    import socket as socket_mod

    s = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_DGRAM)
    try:
        s.connect(("10.255.255.255", 1))  # no packet sent; routing only
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def master_serve(port: int = 7164, snapshot: str = None,
                 task_timeout: float = 60.0, failure_limit: int = 3,
                 discovery_root: str = None, advertise_addr: str = None):
    """Run the master service in the foreground until interrupted
    (`paddle master` CLI; go/master standalone daemon analog). With
    ``discovery_root``, campaign for leadership and publish
    ``advertise_addr`` (default: the routable local IP) so
    ElasticMasterClient trainers can (re)discover this master."""
    import time

    srv = MasterServer(port=port, snapshot_path=snapshot or "",
                       timeout_s=int(task_timeout),
                       max_failures=failure_limit)
    lease = None
    registry = None
    if discovery_root:
        from paddle_tpu.distributed.discovery import (DiscoveryRegistry,
                                                      publish_master)
        registry = DiscoveryRegistry(discovery_root)
        host = advertise_addr or _routable_local_ip()
        lease = publish_master(registry, host, srv.port)
        if lease is None:
            srv.stop()
            raise RuntimeError("another master holds the leadership lease")
    print(f"master serving on port {srv.port}")
    try:
        # serving is tied to leadership: losing the lease exits the loop
        # (split-brain guard — the deposed process must stop serving)
        while lease is None or not lease.lost.wait(1.0):
            if lease is None:
                time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    finally:
        if lease is not None:
            lease.release()
        if registry is not None:
            registry.stop_all()
        srv.stop()


def _pjrt_tensor_struct():
    import ctypes

    class PjrtTensor(ctypes.Structure):
        _fields_ = [("dtype", ctypes.c_int32), ("rank", ctypes.c_int32),
                    ("dims", ctypes.c_int64 * 8),
                    ("data", ctypes.c_void_p),
                    ("size_bytes", ctypes.c_int64)]

    return PjrtTensor


# ptpu_pjrt_tensor dtype tags (capi.h PTPU_DT_*) <-> numpy
_PJRT_DTYPES = {"float32": 0, "int32": 1, "int64": 2, "bool": 3,
                "uint8": 4, "float64": 5}


class PjrtRunner:
    """Python handle over the PJRT C API runner (pjrt_runner.cc): load a
    PJRT plugin .so, compile a static-batch StableHLO module from a
    merged bundle, execute typed batches — the library itself is pure C++
    (no Python, no JAX); this wrapper only marshals test/user calls.

    ``execute_n`` is the r15 n-ary surface (any number of typed args and
    results, matching the bundle's recorded signature); ``execute``
    keeps the legacy single-f32-arg/first-result form.

    plugin_options: "key=value;key=value" plugin create options
    (all-digit values sent as int64); libtpu.so needs none.
    """

    def __init__(self, plugin_so: str, mlir: bytes = b"",
                 plugin_options: str = "", static_batch: int = None):
        import ctypes
        import os as _os

        path = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                             "libpaddle_tpu_pjrt.so")
        if not _os.path.exists(path):
            raise RuntimeError("libpaddle_tpu_pjrt.so not built "
                               "(make -C paddle_tpu/native pjrt)")
        lib = ctypes.CDLL(path)
        self._T = _pjrt_tensor_struct()
        lib.ptpu_pjrt_create_opts.restype = ctypes.c_void_p
        lib.ptpu_pjrt_create_opts.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p]
        lib.ptpu_pjrt_execute.restype = ctypes.c_int
        lib.ptpu_pjrt_execute.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        lib.ptpu_pjrt_execute_n.restype = ctypes.c_int
        lib.ptpu_pjrt_execute_n.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(self._T), ctypes.c_int32,
            ctypes.POINTER(self._T), ctypes.c_int32]
        lib.ptpu_pjrt_execute_prog.restype = ctypes.c_int
        lib.ptpu_pjrt_execute_prog.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.POINTER(self._T),
            ctypes.c_int32, ctypes.POINTER(self._T), ctypes.c_int32]
        lib.ptpu_pjrt_add_program.restype = ctypes.c_int
        lib.ptpu_pjrt_add_program.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        lib.ptpu_pjrt_num_outputs.restype = ctypes.c_int
        lib.ptpu_pjrt_num_outputs.argtypes = [ctypes.c_void_p]
        lib.ptpu_pjrt_num_outputs_prog.restype = ctypes.c_int
        lib.ptpu_pjrt_num_outputs_prog.argtypes = [ctypes.c_void_p,
                                                   ctypes.c_int32]
        lib.ptpu_pjrt_device_count.restype = ctypes.c_int
        lib.ptpu_pjrt_device_count.argtypes = [ctypes.c_void_p]
        lib.ptpu_pjrt_last_error.restype = ctypes.c_char_p
        self._lib = lib
        self._ct = ctypes
        self._static_batch = static_batch
        self._h = lib.ptpu_pjrt_create_opts(
            plugin_so.encode(), mlir or None, len(mlir),
            plugin_options.encode() or None)
        if not self._h:
            raise RuntimeError(
                f"pjrt runner: {lib.ptpu_pjrt_last_error().decode()}")

    @property
    def num_outputs(self) -> int:
        return self._lib.ptpu_pjrt_num_outputs(self._ct.c_void_p(self._h))

    def add_program(self, mlir: bytes) -> int:
        """Compile an ADDITIONAL StableHLO module on this runner's
        client (r19 multi-program surface — the serving daemon holds a
        bundle's forward + decode init/step modules on one client).
        Returns the program index for :meth:`execute_n`'s ``prog``."""
        idx = self._lib.ptpu_pjrt_add_program(
            self._ct.c_void_p(self._h), mlir, len(mlir))
        if idx < 0:
            raise RuntimeError(
                "pjrt add_program: "
                f"{self._lib.ptpu_pjrt_last_error().decode()}")
        return idx

    def num_outputs_prog(self, prog: int) -> int:
        return self._lib.ptpu_pjrt_num_outputs_prog(
            self._ct.c_void_p(self._h), prog)

    def execute_n(self, inputs, initial_capacity: int = 1 << 20,
                  prog: int = 0):
        """Run compiled program ``prog`` (default: the create-time
        module) over n typed numpy args; returns the list of typed
        result arrays. Result buffers start at ``initial_capacity``
        bytes each and are retried right-sized when the runner reports
        -2 (capacity)."""
        import numpy as np

        ct = self._ct
        T = self._T
        args = (T * len(inputs))()
        arrs = []
        for i, x in enumerate(inputs):
            x = np.ascontiguousarray(x)
            tag = _PJRT_DTYPES.get(x.dtype.name)
            if tag is None:
                raise TypeError(f"arg {i}: unsupported dtype {x.dtype}")
            if x.ndim > 8:
                raise ValueError(f"arg {i}: rank {x.ndim} > 8")
            arrs.append(x)
            args[i].dtype = tag
            args[i].rank = x.ndim
            for d, n in enumerate(x.shape):
                args[i].dims[d] = n
            args[i].data = x.ctypes.data_as(ct.c_void_p)
            args[i].size_bytes = x.nbytes
        n_out = self.num_outputs_prog(prog)
        if n_out < 0:
            raise RuntimeError("runner holds no compiled program "
                               f"at index {prog}")
        caps = [int(initial_capacity)] * n_out
        for _attempt in range(2):
            results = (T * n_out)()
            bufs = []
            for i, cap in enumerate(caps):
                b = np.empty(cap, np.uint8)
                bufs.append(b)
                results[i].data = b.ctypes.data_as(ct.c_void_p)
                results[i].size_bytes = cap
            rc = self._lib.ptpu_pjrt_execute_prog(
                ct.c_void_p(self._h), prog, args, len(inputs), results,
                n_out)
            if rc == -2:
                caps = [max(int(results[i].size_bytes), 1)
                        for i in range(n_out)]
                continue
            if rc != 0:
                raise RuntimeError(
                    "pjrt execute_n: "
                    f"{self._lib.ptpu_pjrt_last_error().decode()}")
            inv = {v: k for k, v in _PJRT_DTYPES.items()}
            out = []
            for i in range(n_out):
                shape = tuple(results[i].dims[d]
                              for d in range(results[i].rank))
                dt = np.dtype(inv[results[i].dtype])
                nbytes = int(results[i].size_bytes)
                out.append(bufs[i][:nbytes].view(dt).reshape(shape).copy())
            return out
        raise RuntimeError("pjrt execute_n: capacity retry did not settle")

    @property
    def device_count(self) -> int:
        return self._lib.ptpu_pjrt_device_count(self._ct.c_void_p(self._h))

    def execute(self, x):
        """Run the compiled module. The module's batch is static
        (PJRT_STATIC_BATCH at export): shorter batches are zero-padded
        up and the result sliced back; larger batches are rejected."""
        import numpy as np

        ct = self._ct
        x = np.ascontiguousarray(x, np.float32)
        rows = x.shape[0]
        if self._static_batch is not None:
            if rows > self._static_batch:
                raise ValueError(
                    f"batch {rows} exceeds the module's static batch "
                    f"{self._static_batch}; split the batch")
            if rows < self._static_batch:
                x = np.pad(x, ((0, self._static_batch - rows), (0, 0)))

        def run(cap):
            out = np.empty(cap, np.float32)
            n = ct.c_int64(0)
            rc = self._lib.ptpu_pjrt_execute(
                ct.c_void_p(self._h),
                x.ctypes.data_as(ct.POINTER(ct.c_float)),
                x.shape[0], x.shape[1],
                out.ctypes.data_as(ct.POINTER(ct.c_float)), cap,
                ct.byref(n))
            return rc, n.value, out

        cap0 = 1 << 16
        rc, n, out = run(cap0)
        if rc != 0 and n > cap0:
            rc, n, out = run(n)     # retry at the reported size
        if rc != 0:
            raise RuntimeError(
                f"pjrt execute: {self._lib.ptpu_pjrt_last_error().decode()}")
        res = out[:n].reshape(x.shape[0], -1)
        return res[:rows].copy()

    def close(self):
        if self._h:
            self._lib.ptpu_pjrt_destroy(self._ct.c_void_p(self._h))
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()

"""Python wrapper for the native C++ PJRT executor host (native/pjrt_host.cc).

The native host owns the device: it loads a PJRT plugin (.so), creates the
client, compiles StableHLO, and executes — Python only supplies program
text and numpy buffers. This is the framework's libtensorflow-equivalent
native runtime (SURVEY.md §2.4): the full execute path (H2D, run, D2H) is
C++.

Usage::

    host = PjrtHost(cpu_plugin_path())
    exe = host.compile(stablehlo_text)
    outs = exe(np_a, np_b, out_specs=[((4,), np.float32)])

Note: one process should own one client per plugin. If JAX has already
initialized the same plugin's backend in-process, create the host in a
separate process instead.
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..native import _find_lib

__all__ = [
    "PjrtHost",
    "NativeExecutable",
    "cpu_plugin_path",
    "default_plugin_path",
    "probe_plugin",
    "stablehlo_for",
    "wait_or_terminate",
]


def wait_or_terminate(proc, timeout_s: float, grace_s: float = 20.0):
    """Wait for a child with a deadline; on overrun, SIGTERM + grace but
    NEVER SIGKILL — a force-killed process mid device-claim leaks the
    claim and wedges a shared chip for every later process. If the child
    ignores SIGTERM it is left running (and reported), which is the
    lesser evil. Returns the child's returncode, or None on overrun."""
    import subprocess
    import sys as _sys

    try:
        return proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            print(
                f"# child {proc.pid} ignored SIGTERM; leaving it running "
                "rather than SIGKILLing mid device-claim",
                file=_sys.stderr,
            )
        return None

# PJRT_Buffer_Type ordinals (pjrt_c_api.h enum order).
_PJRT_TYPE = {
    np.dtype(np.bool_): 1,
    np.dtype(np.int8): 2,
    np.dtype(np.int16): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.uint8): 6,
    np.dtype(np.uint16): 7,
    np.dtype(np.uint32): 8,
    np.dtype(np.uint64): 9,
    np.dtype(np.float16): 10,
    np.dtype(np.float32): 11,
    np.dtype(np.float64): 12,
}


def _pjrt_type(dt: np.dtype) -> int:
    dt = np.dtype(dt)
    if dt.name == "bfloat16":
        return 13
    t = _PJRT_TYPE.get(dt)
    if t is None:
        raise TypeError(f"dtype {dt} not supported by the native host")
    return t


def cpu_plugin_path() -> Optional[str]:
    """The repo-built CPU PJRT plugin (native/libtfs_pjrt_cpu.so), if built.

    A dlopen-able CPU plugin backed by the TF wheel's XLA CPU client
    (native/pjrt_cpu_plugin.cc); needs no device claim and no health
    probe, so native-host tests run everywhere regardless of chip state.
    """
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    p = os.path.join(root, "native", "libtfs_pjrt_cpu.so")
    return p if os.path.exists(p) else None


def default_plugin_path() -> Optional[str]:
    """Locate a PJRT C-API plugin .so.

    Search order: ``TFS_PJRT_PLUGIN`` env var, installed ``jax_plugins``
    namespace packages (the official plugin distribution channel —
    jaxlib itself ships NO dlopen-able CPU plugin; its CPU client is
    statically linked), then the repo-built CPU plugin
    (`cpu_plugin_path`) as the accelerator-less fallback.
    """
    env = os.environ.get("TFS_PJRT_PLUGIN")
    if env and os.path.exists(env):
        return env
    try:  # jax_plugins namespace packages (e.g. libtpu, gpu plugins)
        import glob as _glob
        import importlib
        import pkgutil

        import jax_plugins  # type: ignore[import-not-found]

        for m in sorted(
            pkgutil.iter_modules(jax_plugins.__path__), key=lambda m: m.name
        ):
            mod = importlib.import_module(f"jax_plugins.{m.name}")
            root = os.path.dirname(mod.__file__)
            hits = sorted(
                h
                for h in _glob.glob(
                    os.path.join(root, "**", "*.so"), recursive=True
                )
                if "pjrt" in os.path.basename(h).lower()
                or "plugin" in os.path.basename(h).lower()
            )
            if hits:
                return hits[0]
    except Exception:
        pass  # unreadable plugin root: fall back to the repo CPU plugin
    return cpu_plugin_path()


def probe_plugin(path: str, timeout_s: float = 60.0) -> bool:
    """True when the plugin initializes a client in a CHILD process
    within the timeout. A wedged device claim (e.g. a leaked grant on a
    shared chip) hangs client creation indefinitely; probing in a child
    keeps that failure bounded and out of the caller's process.

    The default timeout sits well above worst-case cold init (tens of
    seconds on TPU); overruns are handled by `wait_or_terminate` —
    SIGTERM with grace, never SIGKILL mid device-claim."""
    import subprocess
    import sys

    code = (
        "from tensorframes_tpu.runtime.pjrt_host import PjrtHost;"
        f"h = PjrtHost({path!r}); print(h.platform)"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    return wait_or_terminate(proc, timeout_s) == 0


def _compile_options_bytes() -> bytes:
    """Serialized CompileOptionsProto (single replica/partition)."""
    from jax._src.lib import xla_client

    return xla_client.CompileOptions().SerializeAsString()


def stablehlo_for(fn, *example_args) -> str:
    """Lower a jittable function to StableHLO text (target-neutral)."""
    import jax

    lowered = jax.jit(fn).lower(*example_args)
    return str(lowered.compiler_ir(dialect="stablehlo"))


class NativeExecutable:
    def __init__(self, host: "PjrtHost", handle):
        self._host = host
        self._handle = handle

    def __call__(
        self,
        *inputs: np.ndarray,
        out_specs: Sequence[Tuple[Tuple[int, ...], np.dtype]],
    ) -> List[np.ndarray]:
        return self._host._execute(self._handle, list(inputs), list(out_specs))

    def close(self):
        # a closed host already destroyed the client (and with it every
        # executable) — freeing against a NULL ctx would segfault
        if self._handle and getattr(self._host, "_ctx", None):
            self._host._lib.tfs_pjrt_executable_free(
                self._host._ctx, self._handle
            )
        self._handle = None

    def __del__(self):  # executor-cache eviction must free the handle
        try:
            self.close()
        except Exception:
            pass  # interpreter teardown: host/lib may already be gone


class PjrtHost:
    def __init__(
        self,
        plugin_path: Optional[str] = None,
        create_options: Optional[dict] = None,
    ):
        plugin_path = plugin_path or default_plugin_path()
        if plugin_path is None:
            raise RuntimeError(
                "no PJRT plugin found; set TFS_PJRT_PLUGIN to a plugin .so"
            )
        create_options = create_options or {}
        lib_path = _find_lib()
        if lib_path is None:
            raise RuntimeError(
                "native library not built: run `make -C native`"
            )
        self._lib = ctypes.CDLL(lib_path)
        self._bind()
        n = len(create_options)
        keys = (ctypes.c_char_p * max(1, n))()
        types = (ctypes.c_int32 * max(1, n))()
        strs = (ctypes.c_char_p * max(1, n))()
        ints = (ctypes.c_int64 * max(1, n))()
        for i, (k, v) in enumerate(create_options.items()):
            keys[i] = k.encode()
            if isinstance(v, str):
                types[i] = 0
                strs[i] = v.encode()
            else:
                types[i] = 1
                ints[i] = int(v)
        err = ctypes.create_string_buffer(1024)
        self._ctx = self._lib.tfs_pjrt_load(
            plugin_path.encode(), keys, types, strs, ints, n, err, len(err)
        )
        if not self._ctx:
            raise RuntimeError(f"PJRT plugin load failed: {err.value.decode()}")

    def _bind(self):
        lib = self._lib
        lib.tfs_pjrt_load.restype = ctypes.c_void_p
        lib.tfs_pjrt_load.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.tfs_pjrt_destroy.argtypes = [ctypes.c_void_p]
        lib.tfs_pjrt_platform.restype = ctypes.c_char_p
        lib.tfs_pjrt_platform.argtypes = [ctypes.c_void_p]
        lib.tfs_pjrt_device_count.restype = ctypes.c_int64
        lib.tfs_pjrt_device_count.argtypes = [ctypes.c_void_p]
        lib.tfs_pjrt_compile.restype = ctypes.c_void_p
        lib.tfs_pjrt_compile.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.tfs_pjrt_executable_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.tfs_pjrt_execute.restype = ctypes.c_void_p
        lib.tfs_pjrt_execute.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.tfs_pjrt_outset_count.restype = ctypes.c_int64
        lib.tfs_pjrt_outset_count.argtypes = [ctypes.c_void_p]
        lib.tfs_pjrt_output_size.restype = ctypes.c_int64
        lib.tfs_pjrt_output_size.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.tfs_pjrt_output_read.restype = ctypes.c_int
        lib.tfs_pjrt_output_read.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.tfs_pjrt_outset_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]

    # ------------------------------------------------------------------
    @property
    def platform(self) -> str:
        return self._lib.tfs_pjrt_platform(self._ctx).decode()

    @property
    def device_count(self) -> int:
        return self._lib.tfs_pjrt_device_count(self._ctx)

    def compile(self, stablehlo: str) -> NativeExecutable:
        code = stablehlo.encode()
        opts = _compile_options_bytes()
        err = ctypes.create_string_buffer(4096)
        h = self._lib.tfs_pjrt_compile(
            self._ctx, code, len(code), opts, len(opts), err, len(err)
        )
        if not h:
            raise RuntimeError(f"PJRT compile failed: {err.value.decode()}")
        return NativeExecutable(self, h)

    def _execute(self, exec_handle, inputs, out_specs):
        n = len(inputs)
        arrs = [np.asarray(a, order="C") for a in inputs]
        datas = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in arrs]
        )
        dims_flat: List[int] = []
        offsets: List[int] = []
        ndims: List[int] = []
        types: List[int] = []
        for a in arrs:
            offsets.append(len(dims_flat))
            dims_flat.extend(a.shape)
            ndims.append(a.ndim)
            types.append(_pjrt_type(a.dtype))
        dims_arr = (ctypes.c_int64 * max(1, len(dims_flat)))(*dims_flat)
        off_arr = (ctypes.c_int64 * max(1, n))(*offsets)
        nd_arr = (ctypes.c_int64 * max(1, n))(*ndims)
        ty_arr = (ctypes.c_int32 * max(1, n))(*types)
        err = ctypes.create_string_buffer(4096)
        outset = self._lib.tfs_pjrt_execute(
            self._ctx, exec_handle, n, datas, dims_arr, off_arr, nd_arr,
            ty_arr, err, len(err),
        )
        if not outset:
            raise RuntimeError(f"PJRT execute failed: {err.value.decode()}")
        try:
            count = self._lib.tfs_pjrt_outset_count(outset)
            if count != len(out_specs):
                raise RuntimeError(
                    f"executable produced {count} outputs, expected "
                    f"{len(out_specs)}"
                )
            results = []
            for i, (shape, dtype) in enumerate(out_specs):
                size = self._lib.tfs_pjrt_output_size(
                    self._ctx, outset, i, err, len(err)
                )
                if size < 0:
                    raise RuntimeError(
                        f"PJRT output size failed: {err.value.decode()}"
                    )
                out = np.empty(shape, dtype=dtype)
                if out.nbytes != size:
                    raise RuntimeError(
                        f"output {i}: expected {out.nbytes} bytes for "
                        f"{shape}/{np.dtype(dtype)}, runtime reports {size}"
                    )
                rc = self._lib.tfs_pjrt_output_read(
                    self._ctx, outset, i,
                    out.ctypes.data_as(ctypes.c_void_p), out.nbytes,
                    err, len(err),
                )
                if rc != 0:
                    raise RuntimeError(
                        f"PJRT output read failed: {err.value.decode()}"
                    )
                results.append(out)
            return results
        finally:
            self._lib.tfs_pjrt_outset_free(self._ctx, outset)

    def close(self):
        if self._ctx:
            self._lib.tfs_pjrt_destroy(self._ctx)
            self._ctx = None

"""paddle.utils.cpp_extension — compile-and-load custom C++ host ops.

Reference parity: python/paddle/utils/cpp_extension/extension_utils.py +
setup/load (JIT-compile user C++/CUDA ops into a .so, bind as paddle ops).

TPU-native scope: device compute belongs in Pallas kernels
(``paddle.utils.register_op`` / ``register_kernel`` — nothing to compile,
Mosaic builds them at trace time).  What legitimately stays C++ on a TPU
host is HOST-side work: custom preprocessing, tokenization, CPU reference
kernels.  ``load`` compiles C++ sources with the system toolchain (g++,
ctypes binding — no pybind11 needed) and exposes each declared function as
a framework op running as a host callback — callable eagerly and inside
``jit.to_static`` programs (XLA host callback).

C ABI contract for exported functions (elementwise/shape-preserving)::

    extern "C" void my_op(const float* x, float* y, int64_t n);
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["load", "CppExtension", "get_build_directory"]

_DEFAULT_BUILD = os.path.join(tempfile.gettempdir(), "paddle_tpu_extensions")


def get_build_directory() -> str:
    os.makedirs(_DEFAULT_BUILD, exist_ok=True)
    return _DEFAULT_BUILD


def CppExtension(sources: Sequence[str], *args, **kwargs):
    """API-parity shim: the reference's setuptools Extension factory; here
    sources pass straight to load()."""
    return {"sources": list(sources)}


class CustomOpModule:
    """Holds the loaded library and the generated op callables."""

    def __init__(self, name: str, lib_path: str):
        self.name = name
        self.lib_path = lib_path
        self._lib = ctypes.CDLL(lib_path)

    def __repr__(self):
        return f"<CustomOpModule {self.name} from {self.lib_path}>"


def _compile(name: str, sources: List[str], extra_cflags, build_directory,
             verbose: bool) -> str:
    build = build_directory or get_build_directory()
    os.makedirs(build, exist_ok=True)
    # the binary is keyed by what it was built from: another source set
    # under the same name never reuses (or overwrites a loaded) library
    h = hashlib.sha256(repr(list(extra_cflags or [])).encode())
    for s in sources:
        with open(s, "rb") as f:
            h.update(f.read())
    out = os.path.join(build, f"lib{name}-{h.hexdigest()[:16]}.so")
    if os.path.exists(out):
        return out
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp]
    cmd += list(extra_cflags or [])
    cmd += [os.path.abspath(s) for s in sources]
    if verbose:
        print("[cpp_extension]", " ".join(cmd))
    res = subprocess.run(cmd, capture_output=True, text=True)
    if res.returncode != 0:
        raise RuntimeError(
            f"g++ failed for extension {name!r}:\n{res.stderr}")
    os.replace(tmp, out)
    return out


def load(name: str, sources: Sequence[str],
         functions: Optional[Dict[str, dict]] = None,
         extra_cflags: Optional[Sequence[str]] = None,
         build_directory: Optional[str] = None,
         verbose: bool = False) -> CustomOpModule:
    """Compile `sources` and register each function in `functions` as a
    framework op.

    functions: {fn_name: {"dtype": "float32"}} — every fn follows the
    elementwise C ABI ``void fn(const T* x, T* y, int64_t n)``.  Each
    becomes an attribute of the returned module AND a registered op
    callable on Tensors (host callback under jit).
    """
    lib_path = _compile(name, list(sources), extra_cflags, build_directory,
                        verbose)
    mod = CustomOpModule(name, lib_path)
    for fn_name, spec in (functions or {}).items():
        dtype = np.dtype((spec or {}).get("dtype", "float32"))
        cfunc = getattr(mod._lib, fn_name)
        ctype = np.ctypeslib.ndpointer(dtype=dtype, flags="C_CONTIGUOUS")
        cfunc.argtypes = [ctype, ctype, ctypes.c_int64]
        cfunc.restype = None

        def _host(x, _cfunc=cfunc, _dt=dtype):
            x = np.ascontiguousarray(np.asarray(x, dtype=_dt))
            out = np.empty_like(x)
            _cfunc(x.reshape(-1), out.reshape(-1), x.size)
            return out

        def _primal(x, _host=_host, _dt=dtype):
            import jax

            return jax.pure_callback(
                _host, jax.ShapeDtypeStruct(x.shape, _dt),
                x.astype(_dt), vmap_method="sequential")

        from ..core.custom_kernel import register_op

        op_callable = register_op(f"{name}.{fn_name}", _primal)
        setattr(mod, fn_name, op_callable)
    return mod

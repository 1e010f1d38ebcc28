"""On-demand native build of the C++ runtime pieces.

Counterpart of the reference's build utilities
(/root/reference/graphlearn_torch/python/utils/build.py + setup.py): the
reference ships a pybind11 extension; here the native runtime (csrc/) is a
plain shared library compiled with g++ on first use and bound via ctypes
(pybind11 is not available in this image).
"""
import hashlib
import os
import subprocess
import threading

_lock = threading.Lock()
_lib = None

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CSRC = os.path.join(_REPO_ROOT, 'csrc')
_BUILD = os.path.join(_REPO_ROOT, 'build')


def _sources():
  return sorted(os.path.join(_CSRC, f) for f in os.listdir(_CSRC)
                if f.endswith('.cc'))


def native_lib_path() -> str:
  """build/libglt_c-<hash of csrc/*.cc>.so: the name binds the binary to
  the sources it was built from, so a stale or foreign library (a copied
  tree keeps no meaningful mtimes) is never loaded."""
  h = hashlib.sha256()
  for src in _sources():
    h.update(os.path.basename(src).encode())
    with open(src, 'rb') as f:
      h.update(f.read())
  return os.path.join(_BUILD, f'libglt_c-{h.hexdigest()[:16]}.so')


def build_native(force: bool = False) -> str:
  """Compile csrc/*.cc into :func:`native_lib_path` unless that exact
  build already exists."""
  out = native_lib_path()
  if not force and os.path.exists(out):
    return out
  os.makedirs(_BUILD, exist_ok=True)
  # compile to a private name, then rename: a concurrent process must
  # never dlopen a half-written library
  tmp = f'{out}.{os.getpid()}.tmp'
  cmd = ['g++', '-O2', '-fPIC', '-shared', '-std=c++17', '-pthread',
         '-o', tmp] + _sources()
  subprocess.run(cmd, check=True, capture_output=True, text=True)
  os.replace(tmp, out)
  return out


def load_native():
  """ctypes handle to the native runtime, building it if needed."""
  global _lib
  with _lock:
    if _lib is None:
      import ctypes
      path = build_native()
      lib = ctypes.CDLL(path)
      lib.shmq_create.restype = ctypes.c_void_p
      lib.shmq_create.argtypes = [ctypes.c_uint64]
      lib.shmq_attach.restype = ctypes.c_void_p
      lib.shmq_attach.argtypes = [ctypes.c_int]
      lib.shmq_id.restype = ctypes.c_int
      lib.shmq_id.argtypes = [ctypes.c_void_p]
      lib.shmq_enqueue.restype = ctypes.c_int
      lib.shmq_enqueue.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   ctypes.c_uint64]
      lib.shmq_next_size.restype = ctypes.c_int64
      lib.shmq_next_size.argtypes = [ctypes.c_void_p, ctypes.c_long]
      lib.shmq_dequeue.restype = ctypes.c_int64
      lib.shmq_dequeue.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   ctypes.c_uint64, ctypes.c_long]
      lib.shmq_count.restype = ctypes.c_uint64
      lib.shmq_count.argtypes = [ctypes.c_void_p]
      lib.shmq_finish.argtypes = [ctypes.c_void_p]
      lib.shmq_reset_finished.argtypes = [ctypes.c_void_p]
      lib.shmq_close.argtypes = [ctypes.c_void_p]
      _lib = lib
  return _lib

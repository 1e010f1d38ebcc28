"""Device assignment helpers.

TPU-native port of /root/reference/graphlearn_torch/python/utils/device.py:
the reference rotates sampling workers across CUDA devices; here devices are
jax devices and the default policy is round-robin over local chips.
"""
import os
from typing import Optional, Sequence


def get_available_device(index: int = 0, devices: Optional[Sequence] = None):
  """Round-robin device pick (reference: device.py:22-40)."""
  import jax
  devs = list(devices) if devices is not None else jax.local_devices()
  if not devs:
    return None
  return devs[index % len(devs)]


def ensure_device(device=None):
  """Default device when none given (reference: device.py:42-54)."""
  import jax
  if device is not None:
    return device
  devs = jax.local_devices()
  return devs[0] if devs else None


def global_device_put(arr, sharding):
  """device_put that also works on multi-host meshes.

  On a single-host mesh this is `jax.device_put`. When ``sharding`` spans
  devices this process cannot address (a multi-host mesh from
  dist_context.init_multihost), the array is assembled from the locally
  addressable shards via `make_array_from_callback` — every process passes
  the same full host array (the "each host loads what it serves" model;
  the callback touches only this process's shard slices).
  """
  import jax
  if getattr(sharding, 'is_fully_addressable', True):
    return jax.device_put(arr, sharding)
  import numpy as np
  arr = np.asarray(arr)
  return jax.make_array_from_callback(arr.shape, sharding,
                                      lambda idx: arr[idx])


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the one in-checkout compile-cache directory (git-ignored). The path is
#: part of the cache key, so it is fixed: never a temp name, pid or time.
XLA_CACHE_DIR = os.path.join(_CHECKOUT, '.xla_cache')


def enable_compilation_cache(min_compile_secs: float = 1.0) -> str:
  """Persist XLA executables to disk so repeated process runs warm-start
  (the JIT-world equivalent of the reference's AOT-built CUDA wheels).

  Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already caches there
  and this function sets no directory in code — whoever launched the
  process placed the cache. Otherwise the cache goes to
  :data:`XLA_CACHE_DIR` inside the checkout. Returns the directory in
  effect.

  The cache is keyed WITH instruction metadata. jax's default key strips
  it, so an executable compiled before a scope was named (or renamed)
  would be loaded for the program that names it, and a profile would
  show the old names: the layer clock (the ``glt.*`` scopes,
  docs/observability.md) lives in exactly that metadata. Source paths
  in the metadata are taken relative to the checkout, so a checkout
  that moves still hits; an edit that moves a traced line compiles
  that program once more.
  """
  import re

  import jax
  jax.config.update('jax_persistent_cache_min_compile_time_secs',
                    min_compile_secs)
  jax.config.update('jax_compilation_cache_include_metadata_in_key', True)
  if jax.config.jax_hlo_source_file_canonicalization_regex is None:
    jax.config.update('jax_hlo_source_file_canonicalization_regex',
                      '^' + re.escape(_CHECKOUT + os.sep))
  cache_dir = os.environ.get('JAX_COMPILATION_CACHE_DIR')
  if not cache_dir:
    cache_dir = XLA_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update('jax_compilation_cache_dir', cache_dir)
  _restore_access_times(cache_dir)
  return cache_dir


def _restore_access_times(cache_dir: str) -> None:
  """Give every ``<key>-cache`` entry that lost its ``<key>-atime``
  file a new one. Under a size limit (``jax_compilation_cache_max_size``)
  jax reads the access time of EVERY entry before it writes one, and a
  single missing file makes every write fail with a warning: nothing
  new is cached, and each process compiles its programs again (seen on
  the chip machine's own cache, PERF.md section 6, PR 31: 86 s of
  ``first_call_s`` on every run)."""
  import glob
  import time
  try:
    for entry in glob.glob(os.path.join(cache_dir, '*-cache')):
      atime = entry[:-len('-cache')] + '-atime'
      if not os.path.exists(atime):
        with open(atime, 'wb') as f:
          f.write(time.time_ns().to_bytes(8, 'little'))
  except OSError:
    pass   # a cache that cannot be repaired is a slower start, no error

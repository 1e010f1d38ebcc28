"""One-chip probe of the two-level index (ops/sorted_index.py) at the mesh
cell's table sizes: ``chiprun --chips 1 -- python3 scripts/probe_sorted_index.py``.

Times ``searchsorted_membership`` against ``indexed_membership`` and the
forms PR 36 tried and dropped (the bisect's rounds written out, one
gather of each bucket's window followed by a compare-and-count, by slices
and by elements), checks every form returns the same ``(found, pos)``, and
prints one ``micro:`` JSON line a case. PERF.md section 6, PR 36 has the
readings (a window gathered by slices costs 870 ms where the bisect costs
24). The same index over ``row_ids`` (ROADMAP.md S9(1)) is the next user.
"""
import json, os, sys, time
import numpy as np
import jax, jax.numpy as jnp
from jax import lax
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from graphlearn_tpu.ops.unique import searchsorted_membership
from graphlearn_tpu.ops import sorted_index as si

N = 37_019_985
rng = np.random.default_rng(36)

def unrolled_form(table, starts, q, shift, depth):
  """The helper's bisect with its ``depth`` rounds written out."""
  last = starts.shape[0] - 1
  j = jnp.clip(q >> shift, 0, last)
  lo, hi = starts[j], starts[jnp.minimum(j + 1, last)]
  top = table.shape[0] - 1
  for _ in range(depth):
    mid = (lo + hi) >> 1
    right = (lo < hi) & (table[jnp.minimum(mid, top)] < q)
    lo, hi = jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)
  pos = jnp.minimum(lo, top)
  return table[pos] == q, pos

def window_form(table, starts, q, shift, width):
  last = starts.shape[0] - 1
  j = jnp.clip(q >> shift, 0, last)
  lo, hi = starts[j], starts[jnp.minimum(j + 1, last)]
  n = table.shape[0]
  s = jnp.clip(lo, 0, n - width)
  win = jax.vmap(lambda a: lax.dynamic_slice(table, (a,), (width,)))(s)
  idx = s[:, None] + jnp.arange(width, dtype=jnp.int32)
  ok = (idx >= lo[:, None]) & (idx < hi[:, None]) & (win < q[:, None])
  pos = jnp.minimum(lo + jnp.sum(ok, axis=1, dtype=jnp.int32), n - 1)
  return table[pos] == q, pos

def window_elem_form(table, starts, q, shift, width):
  last = starts.shape[0] - 1
  j = jnp.clip(q >> shift, 0, last)
  lo, hi = starts[j], starts[jnp.minimum(j + 1, last)]
  n = table.shape[0]
  idx = lo[:, None] + jnp.arange(width, dtype=jnp.int32)
  win = table[jnp.minimum(idx, n - 1)]
  ok = (idx < hi[:, None]) & (win < q[:, None])
  pos = jnp.minimum(lo + jnp.sum(ok, axis=1, dtype=jnp.int32), n - 1)
  return table[pos] == q, pos

def timeit(fn, *a, reps=20):
  out = fn(*a); jax.block_until_ready(out)
  t0 = time.perf_counter()
  for _ in range(reps):
    out = fn(*a)
  jax.block_until_ready(out)
  return (time.perf_counter() - t0) / reps * 1e3, out

def case(name, table_np, q_np):
  idx = si.build_sorted_index_host(table_np, N)
  width = int((idx.starts[1:] - idx.starts[:-1]).max())
  table, starts, q = jnp.asarray(table_np), jnp.asarray(idx.starts), jnp.asarray(q_np)
  t0 = time.perf_counter()
  dstarts, dmax = jax.jit(lambda t: si.bucket_starts(t, N, idx.shift))(table)
  jax.block_until_ready(dstarts)
  build_s = time.perf_counter() - t0
  assert np.array_equal(np.asarray(dstarts), idx.starts) and int(dmax) == width
  forms = dict(
      searchsorted=jax.jit(searchsorted_membership),
      indexed_membership=jax.jit(lambda t, s, x: si.indexed_membership(t, s, x, idx.shift, idx.depth)),
      bisect_unrolled=jax.jit(lambda t, s, x: unrolled_form(t, s, x, idx.shift, idx.depth)),
      window_slice=jax.jit(lambda t, s, x: window_form(t, s, x, idx.shift, width)),
      window_elem=jax.jit(lambda t, s, x: window_elem_form(t, s, x, idx.shift, width)))
  res = dict(case=name, rows=int(table_np.shape[0]), queries=int(q_np.shape[0]), shift=idx.shift,
             depth=idx.depth, max_bucket=width, starts=int(idx.starts.shape[0]), build_first_call_s=round(build_s, 3))
  ref = None
  for k, fn in forms.items():
    args = (table, q) if k == 'searchsorted' else (table, starts, q)
    try:
      ms, out = timeit(fn, *args)
    except Exception as e:
      res[k] = 'failed: ' + str(e)[:200]; continue
    out = [np.asarray(o) for o in out]
    if ref is None: ref = out
    res[k + '_ms'] = round(ms, 4)
    res[k + '_equal'] = bool(np.array_equal(out[0], ref[0]) and np.array_equal(out[1], ref[1]))
  print('micro: ' + json.dumps(res), flush=True)

rows = (1 + 4 * np.arange(9_254_997, dtype=np.int64)).astype(np.int32)
rows = rows[rows < N]
rows_t = np.concatenate([rows, np.full(9_254_997 - rows.shape[0], np.iinfo(np.int32).max, np.int32)])
m = 4 * 124_944
q = np.full(m, -1, np.int32)
valid = rng.random(m) < 0.084
q[valid] = rng.choice(rows, int(valid.sum()))
case('rows.bucket_slots(8%valid,-1 pad)', rows_t, q)
case('rows.all_valid', rows_t, rng.choice(rows, m).astype(np.int32))
rand_rows = np.sort(rng.choice(N, 9_254_997, replace=False)).astype(np.int32)
case('rows.random_book.all_valid', rand_rows, rng.choice(rand_rows, m).astype(np.int32))
cache = np.sort(rng.choice(N, 1_850_999, replace=False)).astype(np.int32)
m2 = 263_040
q2 = np.zeros(m2, np.int32)
v2 = rng.random(m2) < 0.66
hit = rng.random(m2) < 0.757
q2[v2 & hit] = rng.choice(cache, int((v2 & hit).sum()))
q2[v2 & ~hit] = rng.integers(0, N, int((v2 & ~hit).sum()))
case('cache.node_buffer(66%valid,0 pad)', cache, q2)
case('cache.all_random', cache, rng.integers(0, N, m2).astype(np.int32))

"""The mesh feature store finds every position through its two-level
indexes (ISSUE 36): same rows as the host's exact gather under any book,
no whole-table ``searchsorted`` left in the lookup program, and the index
a store built is on record.
"""
import re

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.distributed.dist_feature import DistFeature

N, P, F = 203, 4, 4          # N no multiple of a bucket


def _mesh(kind):
  import jax
  from jax.sharding import Mesh
  devs = np.array(jax.devices()[:P])
  if kind == 'flat':
    return Mesh(devs, ('g',))
  return Mesh(devs.reshape(2, 2), ('slice', 'chip'))


def _world(book):
  rng = np.random.default_rng(11)
  pb = ((np.arange(N) % P) if book == 'mod'
        else rng.integers(0, P, N)).astype(np.int32)
  feat = rng.normal(size=(N, F)).astype(np.float32)
  feat[5, 1] = -0.0
  parts = []
  for p in range(P):
    own = np.nonzero(pb == p)[0]
    own = own[rng.permutation(own.shape[0])]     # unsorted on purpose
    parts.append((own.astype(np.int64), feat[own]))
  return pb, feat, parts, np.bincount(rng.zipf(1.5, 900) % N, minlength=N)


def _requests(seed=3, b=24):
  rng = np.random.default_rng(seed)
  ids = rng.integers(0, N, (P, b)).astype(np.int32)
  ids[:, 4] = ids[:, 3]                          # a duplicate
  ids[:, 7] = N - 1
  ids[:, 8] = 0
  ids[:, -3:] = -1                               # FILL pads
  return ids


def _want(store, ids):
  rows = store.cpu_get(np.maximum(ids, 0).reshape(-1)).reshape(
      ids.shape + (F,))
  return np.where((ids >= 0)[..., None], rows, 0)


@pytest.mark.parametrize('book', ['random', 'mod'])
@pytest.mark.parametrize('cache_rows', [0, 23])
@pytest.mark.parametrize('mesh_kind', ['flat', 'slice_chip'])
def test_get_is_cpu_get_bit_for_bit(book, cache_rows, mesh_kind):
  pb, feat, parts, hot = _world(book)
  df = DistFeature(P, parts, pb, _mesh(mesh_kind), cache_rows=cache_rows,
                   hotness=hot)
  ids = _requests()
  got = np.asarray(df.get(ids))
  assert got.tobytes() == _want(df, ids).tobytes()
  assert got.tobytes() == np.where((ids >= 0)[..., None],
                                   feat[np.maximum(ids, 0)], 0).tobytes()
  s = df.stats()
  assert s['lookups'] == int((ids >= 0).sum()) and s['overflow'] == 0
  assert (s['hits'] > 0) == (cache_rows > 0)
  assert s['hits'] == int(np.isin(ids[ids >= 0], df.cache_ids).sum()
                          if cache_rows else 0)


@pytest.mark.parametrize('book', ['random', 'mod'])
@pytest.mark.parametrize('cache_rows', [0, 23])
def test_slab_get_is_cpu_get_bit_for_bit(book, cache_rows, tmp_path):
  """``slab=True``: the demand-paged per-step lookup of an oversubscribed
  store (hot prefix + staged slab) through the same index."""
  from graphlearn_tpu.storage import TieredDistFeature
  pb, feat, parts, hot = _world(book)
  df = TieredDistFeature(P, parts, pb, _mesh('flat'),
                         spill_dir=str(tmp_path), hot_prefix_rows=6,
                         cache_rows=cache_rows, hotness=hot)
  ids = _requests(seed=4)
  got = np.asarray(df.get(ids))
  assert got.tobytes() == _want(df, ids).tobytes()
  assert got.tobytes() == np.where((ids >= 0)[..., None],
                                   feat[np.maximum(ids, 0)], 0).tobytes()


def _searches(text):
  """Operand lengths ``(table, queries)`` of every ``searchsorted`` call
  in a lowered program's text."""
  return [(int(a), int(b)) for a, b in re.findall(
      r'call @searchsorted\w*\([^)]*\) : '
      r'\(tensor<(\d+)xi32>, tensor<(\d+)xi32>\)', text)]


def test_no_whole_table_search_left_in_the_lookup_program(tmp_path):
  import jax
  from graphlearn_tpu.ops import searchsorted_membership
  from graphlearn_tpu.storage import TieredDistFeature
  pb, _, parts, hot = _world('random')
  b = 24
  ids = _requests(b=b)
  # the detector sees a search where there is one
  probe = jax.jit(searchsorted_membership).lower(
      np.arange(9, dtype=np.int32), ids[0]).as_text()
  assert _searches(probe) == [(9, b)]
  df = DistFeature(P, parts, pb, _mesh('flat'), cache_rows=23, hotness=hot)
  text = jax.jit(df._build_fn(b)).lower(ids, ids >= 0).as_text()
  df.reset_stats()                   # the lowering left a tracer there
  assert 'all_to_all' in text and 'searchsorted' not in text
  # the slab path keeps ONE search: over the staged position list, which
  # has no id space to index; none over feat_ids or cache_ids
  tdf = TieredDistFeature(P, parts, pb, _mesh('flat'),
                          spill_dir=str(tmp_path), hot_prefix_rows=6,
                          cache_rows=23, hotness=hot)
  cap = 16
  scan = tdf.dist_scan_tables()
  shard = dict(feat_ids=scan['feat_ids'], feat_starts=scan['feat_starts'],
               hot=scan['hot'],
               slab_pos=np.full((P, cap), np.iinfo(np.int32).max, np.int32),
               slab_rows=np.zeros((P, cap, F), np.float32))
  text = tdf._build_slab_fn(b, cap).lower(
      shard, {k: scan[k] for k in tdf.REPL_KEYS},
      np.zeros((P, 4), np.int32), ids, ids >= 0).as_text()
  found = _searches(text)
  assert found and all(table == cap for table, _ in found)
  assert cap not in (tdf.n_max, tdf.cache_rows)


def test_a_built_store_publishes_its_index():
  """``dist_feature.index_depth.{rows,cache}`` and ``.index_bytes``: set
  when the store builds its indexes, read without touching a device."""
  from graphlearn_tpu import metrics
  from graphlearn_tpu.ops import sorted_index
  names = ('dist_feature.index_depth.rows', 'dist_feature.index_depth.cache',
           'dist_feature.index_bytes')
  for name in names:
    metrics.set_gauge(name, -1)
  pb, _, parts, hot = _world('random')
  df = DistFeature(P, parts, pb, _mesh('flat'), cache_rows=23, hotness=hot)
  rows, cache = df._row_index, df._cache_index
  assert rows.shift == sorted_index.index_shift(df.n_max, N)
  assert cache.shift == sorted_index.index_shift(23, N)
  assert rows.starts.shape == (P, (N >> rows.shift) + 2)
  assert cache.starts.shape == ((N >> cache.shift) + 2,)
  assert metrics.gauge(names[0]).value == rows.depth >= 1
  assert metrics.gauge(names[1]).value == cache.depth >= 1
  assert metrics.gauge(names[2]).value == 4 * (
      rows.starts.shape[1] + cache.starts.shape[0])
  # a store with no cache publishes its rows' index alone
  for name in names:
    metrics.set_gauge(name, -1)
  bare = DistFeature(P, parts, pb, _mesh('flat'))
  assert metrics.gauge(names[0]).value == bare._row_index.depth
  assert metrics.gauge(names[1]).value == -1
  assert metrics.gauge(names[2]).value == 4 * bare._row_index.starts.shape[1]
  # and its pad table resolves to a single bucket, same code
  assert bare._cache_index.starts.shape == (2,)
  assert (np.asarray(bare.get(_requests())).tobytes()
          == _want(bare, _requests()).tobytes())


def test_the_index_names_are_registered_and_documented():
  import os
  from graphlearn_tpu.metrics.registry_names import REGISTERED_METRICS
  assert 'dist_feature.*' in REGISTERED_METRICS
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  with open(os.path.join(root, 'docs', 'observability.md')) as f:
    doc = f.read()
  for name in ('dist_feature.index_depth.rows',
               'dist_feature.index_depth.cache', 'dist_feature.index_bytes'):
    assert f'`{name}`' in doc, name

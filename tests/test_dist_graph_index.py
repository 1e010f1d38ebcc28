"""The mesh graph finds every row through a two-level index over
``row_ids`` (ISSUE 38): the shard-local draws equal a ``jnp.searchsorted``
reference bit for bit on the same key, the index a device builds is the
host's, a dataset whose graph and store share one id array share one
index, a typed graph holds one an edge type, and what was built is on
record.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.ops import sorted_index
from graphlearn_tpu.typing import GraphPartitionData

INT32_MAX = np.iinfo(np.int32).max
INT32_MIN = np.iinfo(np.int32).min
N, P, F = 240, 4, 4


# ------------------------------------------- the reference: searchsorted

def searched_rows(row_ids, seeds, seed_mask):
  """The row lookup the draws ran before the index: a binary search over
  the whole table."""
  import jax.numpy as jnp
  pos = jnp.clip(jnp.searchsorted(row_ids, seeds), 0, row_ids.shape[0] - 1)
  return (row_ids[pos] == seeds) & seed_mask, pos


def uniform_sample_searched(row_ids, indptr_loc, indices, seeds, seed_mask,
                            k, key, *_index):
  import jax
  import jax.numpy as jnp
  found, pos = searched_rows(row_ids, seeds, seed_mask)
  start = indptr_loc[pos]
  deg = jnp.where(found, indptr_loc[pos + 1] - start, 0)
  u = jax.random.uniform(key, (seeds.shape[0], k))
  rand_off = jnp.floor(u * deg[:, None].astype(u.dtype)).astype(jnp.int32)
  rand_off = jnp.minimum(rand_off, jnp.maximum(deg[:, None] - 1, 0))
  seq_off = jnp.arange(k, dtype=jnp.int32)[None, :]
  offsets = jnp.where(deg[:, None] > k, rand_off, seq_off)
  mask = found[:, None] & (offsets < deg[:, None])
  epos = start[:, None] + offsets
  nbrs = jnp.where(mask, indices[jnp.where(mask, epos, 0)], -1)
  return nbrs, jnp.where(mask, epos, 0), mask


def weighted_sample_searched(row_ids, indptr_loc, indices, row_cumsum,
                             seeds, seed_mask, k, key, *_index):
  import jax
  import jax.numpy as jnp
  b = seeds.shape[0]
  found, pos = searched_rows(row_ids, seeds, seed_mask)
  start = indptr_loc[pos]
  deg = jnp.where(found, indptr_loc[pos + 1] - start, 0)
  end = start + deg
  total = jnp.where(deg > 0, row_cumsum[jnp.maximum(end - 1, 0)], 1.0)
  u = jax.random.uniform(key, (b, k)) * total[:, None]
  lo = jnp.broadcast_to(start[:, None], (b, k))
  hi = jnp.broadcast_to(end[:, None], (b, k))
  for _ in range(32):
    mid = (lo + hi) // 2
    right = row_cumsum[jnp.clip(mid, 0, row_cumsum.shape[0] - 1)] < u
    lo, hi = jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)
  wpos = jnp.minimum(lo, jnp.maximum(end[:, None] - 1, 0))
  seq_off = jnp.arange(k, dtype=start.dtype)[None, :]
  epos = jnp.where(deg[:, None] > k, wpos, start[:, None] + seq_off)
  mask = found[:, None] & (
      jnp.where(deg[:, None] > k, 0, seq_off) < deg[:, None])
  nbrs = jnp.where(mask, indices[jnp.where(mask, epos, 0)], -1)
  return nbrs, jnp.where(mask, epos, 0), mask


# ------------------------------------------------------ one shard's draw

ID_SPACE = 5000


def _shard(kind):
  """One shard's local CSR ``(row_ids, indptr, indices, weights' CDF)``
  over ids of ``[0, ID_SPACE)``, the row table padded with INT_MAX."""
  rng = np.random.default_rng(5)
  if kind == 'spread':
    own = np.sort(rng.choice(ID_SPACE, 300, replace=False))
  elif kind == 'clustered':         # one full bucket of ids, few others
    own = np.union1d(np.arange(2048, 2048 + 128),
                     rng.choice(ID_SPACE, 60, replace=False))
  else:                             # a shard that owns nothing
    own = np.zeros((0,), np.int64)
  rows = 340                        # INT_MAX tail padding
  row_ids = np.full((rows,), INT32_MAX, np.int32)
  row_ids[:own.shape[0]] = own
  deg = rng.integers(0, 9, own.shape[0])        # some rows of degree 0
  indptr = np.zeros((rows + 1,), np.int32)
  indptr[1:own.shape[0] + 1] = np.cumsum(deg)
  indptr[own.shape[0] + 1:] = indptr[own.shape[0]]
  e = max(int(indptr[-1]), 1)
  indices = rng.integers(0, ID_SPACE, e).astype(np.int32)
  w = rng.uniform(0.1, 2.0, e).astype(np.float32)
  cum = np.cumsum(w)
  base = np.concatenate([[0.0], cum])[indptr[:-1]]
  wcum = (cum - np.repeat(base, np.diff(indptr))[:e]).astype(np.float32) \
      if indptr[-1] else np.zeros((e,), np.float32)
  return own, row_ids, indptr, indices, wcum


def _queries(own):
  rng = np.random.default_rng(6)
  owned = (rng.choice(own, 40) if own.shape[0]
           else np.zeros((0,), np.int64))
  unowned = np.setdiff1d(rng.integers(0, ID_SPACE, 60), own)
  odd = np.array([-1, -1, INT32_MAX, INT32_MAX - 1, ID_SPACE, ID_SPACE + 77,
                  1 << 30, -7, INT32_MIN, 0, ID_SPACE - 1], np.int64)
  q = np.concatenate([owned, unowned, odd]).astype(np.int32)
  q = q[rng.permutation(q.shape[0])]
  mask = rng.random(q.shape[0]) < 0.9           # a few valid ids masked out
  return q, mask


@pytest.mark.parametrize('mask_kind', ['mixed', 'all', 'pads_out'])
@pytest.mark.parametrize('draw', ['uniform', 'weighted'])
@pytest.mark.parametrize('kind', ['spread', 'clustered', 'empty'])
def test_local_draw_through_the_index_is_the_searched_draw(kind, draw,
                                                           mask_kind):
  import jax
  own, row_ids, indptr, indices, wcum = _shard(kind)
  ix = sorted_index.build_sorted_index_host(row_ids, ID_SPACE)
  if kind == 'clustered':
    # a bucket holding every id of its range: the deepest an index goes
    assert (ix.shift, ix.depth) == (7, 8)
  q, mask = _queries(own)
  if mask_kind == 'all':
    mask = np.ones_like(mask)
  elif mask_kind == 'pads_out':     # what the exchange hands over: >= 0
    mask = q >= 0
  key = jax.random.PRNGKey(17)
  k = 4                             # under and over the degrees
  if draw == 'uniform':
    got = glt.ops.uniform_sample_local(
        row_ids, indptr, indices, q, mask, k, key, ix.starts, ix.shift,
        ix.depth)
    want = uniform_sample_searched(row_ids, indptr, indices, q, mask, k,
                                   key)
  else:
    got = glt.ops.weighted_sample_local(
        row_ids, indptr, indices, wcum, q, mask, k, key, ix.starts,
        ix.shift, ix.depth)
    want = weighted_sample_searched(row_ids, indptr, indices, wcum, q,
                                    mask, k, key)
  for name, a, b in zip(('nbrs', 'epos', 'mask'), got, want):
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name
  hit = np.asarray(got[2]).any(axis=1)
  assert not hit[~mask].any() and not hit[~np.isin(q, own)].any()
  assert hit.any() == bool(own.shape[0])


@pytest.mark.parametrize('kind', ['spread', 'clustered', 'empty'])
def test_row_lookup_equals_searchsorted_for_every_query(kind):
  """The helper both draws share: ``found`` and ``pos`` are the search's,
  the pad -1, INT_MAX and ids past the id space included."""
  from graphlearn_tpu.ops.neighbor import _local_rows
  own, row_ids, _, _, _ = _shard(kind)
  ix = sorted_index.build_sorted_index_host(row_ids, ID_SPACE)
  q, mask = _queries(own)
  import jax.numpy as jnp
  found, pos = _local_rows(jnp.asarray(row_ids), jnp.asarray(ix.starts), q,
                           mask, ix.shift, ix.depth)
  wfound, wpos = searched_rows(row_ids, q, mask)
  assert np.array_equal(np.asarray(found), np.asarray(wfound))
  assert np.array_equal(np.asarray(pos), np.asarray(wpos))


def test_no_search_over_row_ids_is_left_in_the_draws():
  import inspect

  import jax
  from graphlearn_tpu.ops import neighbor
  _, row_ids, indptr, indices, wcum = _shard('spread')
  ix = sorted_index.build_sorted_index_host(row_ids, ID_SPACE)
  q, mask = _queries(np.zeros((0,), np.int64))
  key = jax.random.PRNGKey(0)
  text = glt.ops.uniform_sample_local.lower(
      row_ids, indptr, indices, q, mask, 4, key, ix.starts, ix.shift,
      ix.depth).as_text()
  assert 'searchsorted' not in text
  text = glt.ops.weighted_sample_local.lower(
      row_ids, indptr, indices, wcum, q, mask, 4, key, ix.starts, ix.shift,
      ix.depth).as_text()
  assert 'searchsorted' not in text
  # the detector sees a search where there is one
  assert 'searchsorted' in jax.jit(searched_rows).lower(
      row_ids, q, mask).as_text()
  assert 'searchsorted' not in inspect.getsource(neighbor)


# ------------------------------------------------- the graph's own index

def _mesh(kind='flat'):
  import jax
  from jax.sharding import Mesh
  devs = np.array(jax.devices()[:P])
  if kind == 'flat':
    return Mesh(devs, ('g',))
  return Mesh(devs.reshape(2, 2), ('slice', 'chip'))


def _parts(rng, node_pb, edges=1500, weights=False):
  rows = rng.integers(0, N, edges)
  cols = (rng.zipf(1.6, edges) - 1) % N
  w = rng.uniform(0.1, 3.0, edges).astype(np.float32)
  parts = []
  for q in range(P):
    m = node_pb[rows] == q
    parts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]), eids=np.nonzero(m)[0],
        weights=w[m] if weights else None))
  return parts


@pytest.fixture(scope='module')
def host_graph():
  rng = np.random.default_rng(0)
  node_pb = rng.integers(0, P, N).astype(np.int32)
  return glt.distributed.DistGraph(P, 0, _parts(rng, node_pb), node_pb)


@pytest.mark.parametrize('mesh_kind', ['flat', 'slice_chip'])
def test_device_built_index_is_the_host_built(host_graph, mesh_kind):
  mesh = _mesh(mesh_kind)
  ga = host_graph.device_arrays(mesh)
  ix = host_graph.row_index
  assert ix.shift == sorted_index.index_shift(host_graph.row_ids.shape[1], N)
  assert ix.starts.shape == (P, (N >> ix.shift) + 2)
  assert ga['row_starts'].sharding.is_equivalent_to(ga['row_ids'].sharding,
                                                    2)
  dev = glt.distributed.DistGraph.from_device_shards(
      mesh, host_graph.node_pb,
      **{k: ga[k] for k in ('row_ids', 'indptr', 'indices', 'eids')})
  assert (dev.row_index.shift, dev.row_index.depth) == (ix.shift, ix.depth)
  assert np.array_equal(np.asarray(dev.row_index.starts), ix.starts)
  da = dev.device_arrays(mesh)
  assert da['row_starts'] is dev.row_index.starts and set(da) == set(ga)
  assert da['row_starts'].sharding.is_equivalent_to(ga['row_ids'].sharding,
                                                    2)


def _every_node_a_row(mesh, rng, node_pb):
  """Device shards of a graph whose row table lists EVERY owned node (the
  mesh family's layout): ``(graph dict, features dict)`` with ONE id
  array for both."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec
  rows = rng.integers(0, N, 1200)
  cols = rng.integers(0, N, 1200)
  n_max = int(np.bincount(node_pb, minlength=P).max()) + 3
  e_max = int(np.bincount(node_pb[rows], minlength=P).max())
  ids = np.full((P, n_max), INT32_MAX, np.int32)
  indptr = np.zeros((P, n_max + 1), np.int32)
  indices = np.full((P, e_max), -1, np.int32)
  feats = np.zeros((P, n_max, F), np.float32)
  for q in range(P):
    own = np.nonzero(node_pb == q)[0]
    ids[q, :own.shape[0]] = own
    feats[q, :own.shape[0]] = rng.normal(size=(own.shape[0], F))
    m = node_pb[rows] == q
    order = np.argsort(rows[m], kind='stable')
    deg = np.bincount(np.searchsorted(own, rows[m]), minlength=own.shape[0])
    indptr[q, 1:own.shape[0] + 1] = np.cumsum(deg)
    indptr[q, own.shape[0] + 1:] = indptr[q, own.shape[0]]
    indices[q, :m.sum()] = cols[m][order]
  shard = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
  put = lambda a: jax.device_put(a, shard)
  fid = put(ids)
  return (dict(row_ids=fid, indptr=put(indptr), indices=put(indices)),
          dict(feat_ids=fid, feats=put(feats)), (rows, cols))


GAUGES = ('dist_graph.index_depth', 'dist_graph.index_bytes')


def _clear_gauges():
  from graphlearn_tpu import metrics
  for name in GAUGES:
    metrics.set_gauge(name, -1)


def _gauges():
  from graphlearn_tpu import metrics
  return tuple(metrics.gauge(name).value for name in GAUGES)


@pytest.mark.parametrize('one_array', [True, False])
def test_dataset_shares_the_store_index_over_one_id_array(one_array):
  import jax.numpy as jnp
  mesh = _mesh()
  rng = np.random.default_rng(3)
  node_pb = rng.integers(0, P, N).astype(np.int32)
  graph, features, (rows, cols) = _every_node_a_row(mesh, rng, node_pb)
  if not one_array:                 # equal ids, another array
    graph['row_ids'] = jnp.array(graph['row_ids'], copy=True)
    assert graph['row_ids'] is not features['feat_ids']
  _clear_gauges()
  with glt.utils.count_dispatches() as counts:
    ds = glt.distributed.DistDataset.from_device_shards(
        mesh, node_pb, graph, features)
  gi, fi = ds.graph.row_index, ds.node_features._row_index
  ga, fa = ds.graph.device_arrays(mesh), ds.node_features.device_arrays()
  assert (gi is fi) == one_array
  assert (ga['row_starts'] is fa['feat_starts']) == one_array
  assert (gi.shift, gi.depth) == (fi.shift, fi.depth)
  assert np.array_equal(np.asarray(gi.starts), np.asarray(fi.starts))
  # shared: no bytes of the graph's own and no set-up program for it
  assert _gauges() == (gi.depth,
                       0 if one_array else 4 * gi.starts.shape[1])
  assert counts.counts.get('dist_graph.build_index', 0) == (0 if one_array else 1)
  # and the sampler over it draws real edges
  out = glt.distributed.DistNeighborSampler(
      ds.graph, [3, 2], mesh, seed=1).sample_from_nodes(
          np.arange(P * 8, dtype=np.int32) % N)
  edges = set(zip(rows.tolist(), cols.tolist()))
  node, em = np.asarray(out.node), np.asarray(out.edge_mask)
  row, col = np.asarray(out.row), np.asarray(out.col)
  assert em.sum() > 0
  for p in range(P):
    # message direction: neighbour (row) -> the node it was drawn for
    src, dst = node[p][col[p][em[p]]], node[p][row[p][em[p]]]
    assert all((s, d) in edges for s, d in zip(src.tolist(), dst.tolist()))


def test_host_built_graph_publishes_its_index(host_graph):
  rng = np.random.default_rng(0)
  node_pb = host_graph.node_pb
  _clear_gauges()
  g = glt.distributed.DistGraph(P, 0, _parts(rng, node_pb), node_pb)
  assert _gauges() == (g.row_index.depth, 4 * g.row_index.starts.shape[1])
  assert g.row_index.depth >= 1


def _typed_parts():
  """Two node types of different sizes, three edge types (one whose rows
  are the smaller type)."""
  rng = np.random.default_rng(8)
  n = dict(paper=N, author=57)
  pbs = {t: rng.integers(0, P, c).astype(np.int32) for t, c in n.items()}
  ets = [('paper', 'cites', 'paper'), ('author', 'writes', 'paper'),
         ('paper', 'rev_writes', 'author')]
  edges = {et: (rng.integers(0, n[et[0]], 900),
                rng.integers(0, n[et[2]], 900)) for et in ets}
  parts = []
  for q in range(P):
    part = {}
    for et, (r, c) in edges.items():
      m = pbs[et[0]][r] == q
      part[et] = GraphPartitionData(edge_index=np.stack([r[m], c[m]]),
                                    eids=np.nonzero(m)[0])
    parts.append(part)
  return n, pbs, ets, edges, parts


def test_typed_graph_holds_one_index_an_edge_type():
  n, pbs, ets, _, parts = _typed_parts()
  _clear_gauges()
  hg = glt.distributed.DistHeteroGraph(P, 0, parts, pbs)
  dev = hg.device_arrays(_mesh())
  total = 0
  for et in ets:
    g, ix = hg.sub[et], hg.sub[et].row_index
    want = sorted_index.build_sorted_index_host(g.row_ids, n[et[0]])
    assert (ix.shift, ix.depth) == (want.shift, want.depth), et
    assert np.array_equal(ix.starts, want.starts), et
    assert ix.starts.shape == (P, (n[et[0]] >> ix.shift) + 2), et
    assert np.array_equal(np.asarray(dev[et]['row_starts']), ix.starts), et
    total += 4 * ix.starts.shape[1]
  assert _gauges() == (max(hg.sub[et].row_index.depth for et in ets), total)


def test_the_gauges_are_registered_and_documented():
  import os
  from graphlearn_tpu.metrics.registry_names import REGISTERED_METRICS
  assert 'dist_graph.*' in REGISTERED_METRICS
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  with open(os.path.join(root, 'docs', 'observability.md')) as f:
    doc = f.read()
  for name in GAUGES:
    assert f'`{name}`' in doc, name

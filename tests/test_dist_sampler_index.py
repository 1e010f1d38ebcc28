"""The mesh sampler over the graph's row index (ISSUE 38) returns, on the
same keys, the arrays it returns when every shard-local row lookup is a
``jnp.searchsorted`` over the whole table: flat and two-axis meshes,
uniform and weighted draws, homogeneous and typed graphs.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from test_dist_graph_index import (N, P, _mesh, _parts, _typed_parts,
                                   uniform_sample_searched,
                                   weighted_sample_searched)


def _homo(weighted):
  rng = np.random.default_rng(21)
  node_pb = rng.integers(0, P, N).astype(np.int32)
  graph = glt.distributed.DistGraph(
      P, 0, _parts(rng, node_pb, weights=weighted), node_pb)
  seeds = rng.integers(0, N, (P, 12)).astype(np.int32)
  return graph, seeds, [4, 3]


def _typed(weighted):
  from graphlearn_tpu.typing import GraphPartitionData
  _, pbs, _, _, parts = _typed_parts()
  if weighted:
    rng = np.random.default_rng(22)
    parts = [{et: GraphPartitionData(
        edge_index=g.edge_index, eids=g.eids,
        weights=rng.uniform(0.1, 3.0, g.eids.shape[0]).astype(np.float32))
              for et, g in part.items()} for part in parts]
  graph = glt.distributed.DistHeteroGraph(P, 0, parts, pbs)
  seeds = np.random.default_rng(23).integers(0, N, (P, 8)).astype(np.int32)
  return graph, ('paper', seeds), [3, 2]


def _leaves(out):
  import jax
  fields = {k: getattr(out, k) for k in (
      'node', 'num_nodes', 'row', 'col', 'edge', 'edge_mask',
      'num_sampled_nodes', 'num_sampled_edges')}
  fields['seed_inverse'] = out.metadata['seed_inverse']
  fields['overflow'] = out.metadata['overflow']
  flat, _ = jax.tree_util.tree_flatten_with_path(fields)
  return [(jax.tree_util.keystr(path), np.asarray(leaf))
          for path, leaf in flat]


@pytest.mark.parametrize('typed', [False, True], ids=['homo', 'typed'])
@pytest.mark.parametrize('weighted', [False, True],
                         ids=['uniform', 'weighted'])
@pytest.mark.parametrize('mesh_kind', ['flat', 'slice_chip'])
def test_sampler_through_the_index_is_the_searched_sampler(
    mesh_kind, weighted, typed, monkeypatch):
  mesh = _mesh(mesh_kind)
  graph, seeds, fanouts = (_typed if typed else _homo)(weighted)

  def sample():
    sampler = glt.distributed.DistNeighborSampler(
        graph, fanouts, mesh, with_edge=True, with_weight=weighted, seed=9)
    return [_leaves(sampler.sample_from_nodes(seeds)) for _ in range(2)]

  got = sample()
  calls = []

  def searched(fn):
    def wrapped(*args):
      calls.append(fn.__name__)
      return fn(*args)
    return wrapped

  monkeypatch.setattr(glt.ops, 'uniform_sample_local',
                      searched(uniform_sample_searched))
  monkeypatch.setattr(glt.ops, 'weighted_sample_local',
                      searched(weighted_sample_searched))
  want = sample()
  # the reference ran in the sampler's place, the draw that was asked for
  assert set(calls) == {weighted_sample_searched.__name__ if weighted
                        else uniform_sample_searched.__name__}
  edges = 0
  for a, b in zip(got, want):
    assert [k for k, _ in a] == [k for k, _ in b]
    for (k, x), (_, y) in zip(a, b):
      assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), k
      if 'edge_mask' in k:
        edges += int(x.sum())
  assert edges > 0

"""The generator of family ``hetero_table_node``: a typed graph in which only
some node types carry feature rows — the others are given learned tables by
the program — from ``graph_seed``, as host arrays the benchmark keeps as the
plain reference's own data and hands copies of to the program.

It draws by ``datagen_hetero_node``'s laws (imported, not restated): per
stored relation Poisson out-degrees nudged to the exact edge count, targets
by a truncated power-law popularity, ``p_intra`` homophily where both ends
are the labelled type, reverses as exact transposes, rows made on the device
in pieces of one shape, labels uniform, the train split a uniform draw.
What it adds:

* a relation marked ``undirected`` (OGB-MAG's ``cites``, which the OGB and
  DGL R-GCN scripts make undirected before sampling) is stored as the drawn
  edges and their reverses concatenated: ``2 E`` edges, a node's drawn
  out-edges first, then the edges drawn into it (stable by source);
* rows are made only for the types named in ``feature_types``; every other
  type has none, and no bytes are spent on it.
"""
import time

import numpy as np

from perfbench.datagen_hetero_node import (_relation, _rng, _rows,
                                           _transpose, etype_of)


def undirected(indices, deg, n):
  """The CSR of a self relation with every drawn edge and its reverse: per
  node its drawn out-edges, then the sources of the edges drawn into it."""
  rows = np.repeat(np.arange(n, dtype=np.int32), deg)
  src = np.concatenate([rows, indices])
  dst = np.concatenate([indices, rows])
  order = np.argsort(src, kind='stable')
  indptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
  return indptr.astype(np.int64), dst[order].astype(np.int32)


def generate(dataset, graph_seed, log=lambda k, v: None):
  """The dataset a configuration's ``dataset`` group describes: ``csr``
  ``{edge type: (indptr int64, indices int32)}`` with the reverses and the
  undirected relations doubled, ``feat`` ``{node type: [n, F]}`` for the
  ``feature_types`` only, ``label`` (int32, of ``label_type``),
  ``train_idx`` and ``num_nodes``."""
  d = dataset
  sizes = {t: int(n) for t, n in d['node_types'].items()}
  t_lab, c = d['label_type'], int(d['num_classes'])
  comm = _rng(graph_seed, 0).integers(0, c, sizes[t_lab]).astype(np.int32)
  t0 = time.perf_counter()
  csr = {}
  for i, (name, rel) in enumerate(d['relations'].items()):
    s, _, dst = et = etype_of(name)
    both = s == t_lab and dst == t_lab
    indptr, indices, deg = _relation(
        _rng(graph_seed, 1, i), sizes[s], sizes[dst], rel['edges'],
        comm=comm if both else None, num_classes=c,
        p_intra=float(rel.get('p_intra', 0.0)) if both else 0.0)
    if rel.get('undirected'):
      if s != dst:
        raise ValueError(f'{name}: only a relation of one node type can be '
                         'made undirected')
      indptr, indices = undirected(indices, deg, sizes[s])
    csr[et] = (indptr, indices)
    if 'reverse' in rel:
      csr[etype_of(rel['reverse'])] = _transpose(indices, deg, sizes[dst])
  log('generate_graph_s', time.perf_counter() - t0)
  t0 = time.perf_counter()
  dtype = np.dtype(d['feature_dtype'])
  feat = {}
  for i, t in enumerate(sizes):
    if t not in d['feature_types']:
      continue
    lab = t == t_lab
    feat[t] = _rows(int(_rng(graph_seed, 2, i).integers(2 ** 31)), sizes[t],
                    int(d['feat_dim']), dtype, comm if lab else None, c,
                    float(d['feat_snr']) if lab else 0.0)
  log('generate_rows_s', time.perf_counter() - t0)
  train = _rng(graph_seed, 3).permutation(sizes[t_lab])[
      :int(d['num_train'])].astype(np.int32)
  return dict(csr=csr, feat=feat, label=comm, train_idx=train,
              num_nodes=sizes)

"""The typed generator of family ``hetero_node``: a graph of several node
types and typed relations, with the reverse of every bipartite relation, a
row table per node type and labels on one type, from ``graph_seed`` —
returned as host arrays the benchmark keeps as the plain reference's own
data and hands copies of to the program.

The graph is made on the HOST, in bulk ``numpy`` calls (no loop over
edges): seven relations of seven shapes are seven sets of device programs
whose compilation cost set-up 350-380 s on the chip (PERF.md section 6,
PR 30) where the arithmetic takes 20 s here. The row tables — 3.2 G
normal draws — are made on the device in fixed-size pieces of ONE shape,
as ``datagen.py`` makes the products rows.

The laws (a configuration states them under ``assumed``):

* a stored relation ``(s, r, d)`` with E edges: every source node draws its
  out-degree Poisson(E / n_s) — the leading nodes nudged by one until the
  degrees sum to exactly E, so the edges are born in CSR order — and every
  edge picks its target by an integer popularity weight, a truncated power
  law ``w = min(floor(u ** (-1 / (alpha - 1))), w_max)`` over the target
  type;
* a relation with ``p_intra`` (both ends of the labelled type) picks the
  target inside the source's class that share of the time, by the same
  weights: homophily;
* the reverse relation ``(d, rev_r, s)`` is the exact transpose, sorted by
  target (stable, so a target's sources come in source order);
* rows: unit normal noise, plus ``feat_snr`` times the class centre on the
  labelled type, rounded once to the table's dtype (float16 as the source
  stores them); labels uniform over the classes; the train split a uniform
  draw without replacement.
"""
import functools

import numpy as np

PIECE_ROWS = 65_536         # feature rows made per program call
ALPHA, W_MAX = 2.2, 10_000  # the popularity law


def _rng(graph_seed, *path):
  """An independent numpy stream per part of the dataset: the same
  arrays for the same ``graph_seed`` on every platform."""
  return np.random.default_rng([int(graph_seed), *path])


def _relation(rng, n_src, n_dst, num_edges, comm=None, num_classes=0,
              p_intra=0.0):
  """``(indptr[n_src+1] int64, indices[E] int32, out-degrees)``."""
  e = int(num_edges)
  intra = comm is not None and p_intra > 0
  u = np.maximum(rng.random(n_dst), 1e-7)
  w = np.minimum(np.floor(u ** (-1.0 / (ALPHA - 1.0))), W_MAX)
  w = w.astype(np.int64)
  deg = rng.poisson(e / n_src, n_src).astype(np.int64)
  diff = e - int(deg.sum())
  while diff:
    # nudge the leading nodes by one (down: the leading non-empty ones)
    idx = (np.arange(n_src) if diff > 0 else np.flatnonzero(deg > 0))
    idx = idx[:abs(diff)]
    deg[idx] += 1 if diff > 0 else -1
    diff = e - int(deg.sum())
  indptr = np.concatenate([[0], np.cumsum(deg)])
  if intra:
    # targets sorted by class: one cumulative weight vector serves the
    # global and the within-class draws
    order = np.argsort(comm, kind='stable')
    offs = np.concatenate([[0], np.cumsum(
        np.bincount(comm, minlength=num_classes))])
  else:
    order, offs = np.arange(n_dst), np.zeros(1, np.int64)
  cw = np.cumsum(w[order])
  pick = rng.integers(0, cw[-1], e)
  if intra:
    bounds = np.concatenate([[0], cw])[offs]
    rc = comm[np.repeat(np.arange(n_src), deg)]
    lo, hi = bounds[rc], bounds[rc + 1]
    inside = lo + (rng.random(e) * (hi - lo)).astype(np.int64)
    pick = np.where((rng.random(e) < p_intra) & (hi > lo), inside, pick)
  pos = np.searchsorted(cw, pick, side='right')
  return indptr, order[np.minimum(pos, n_dst - 1)].astype(np.int32), deg


def _transpose(indices, deg, n_dst):
  """The CSR of the reverse relation: edges sorted by target (stable, so a
  target's sources come in source order)."""
  rows = np.repeat(np.arange(deg.size, dtype=np.int32), deg)
  rptr = np.concatenate([[0], np.cumsum(
      np.bincount(indices, minlength=n_dst))])
  return rptr, rows[np.argsort(indices, kind='stable')]


@functools.lru_cache(maxsize=None)
def _rows_piece(feat_dim, dtype):
  """ONE program for every node type's pieces: noise + snr * centre."""
  import jax
  import jax.numpy as jnp

  @jax.jit
  def piece(k_noise, i, centers, snr, cls):
    x = jax.random.normal(jax.random.fold_in(k_noise, i),
                          (PIECE_ROWS, feat_dim), jnp.float32)
    return (x + snr * centers[cls]).astype(dtype)

  return piece


def _rows(seed, n, feat_dim, dtype, comm, num_classes, feat_snr):
  """``[n, feat_dim]`` host rows of ``dtype``, made on the device piece by
  piece by one program whatever the node type (a type without labels
  passes ``feat_snr`` 0), each piece fetched while the next is made."""
  import jax
  import jax.numpy as jnp
  k_cent, k_noise = jax.random.split(jax.random.PRNGKey(seed))
  centers = jax.random.normal(k_cent, (num_classes, feat_dim), jnp.float32)
  pieces = -(-n // PIECE_ROWS)
  cls = np.zeros(pieces * PIECE_ROWS, np.int32)
  if comm is not None:
    cls[:n] = comm
  piece = _rows_piece(feat_dim, np.dtype(dtype).name)

  def make(i):
    return piece(k_noise, jnp.int32(i), centers, jnp.float32(feat_snr),
                 cls[i * PIECE_ROWS:(i + 1) * PIECE_ROWS])

  out = np.empty((n, feat_dim), dtype)
  nxt = make(0)
  for i in range(pieces):
    cur, nxt = nxt, (make(i + 1) if i + 1 < pieces else None)
    lo = i * PIECE_ROWS
    out[lo:lo + PIECE_ROWS] = np.asarray(cur)[:n - lo]
  return out


def etype_of(name):
  """``'paper__cites__paper'`` -> ``('paper', 'cites', 'paper')``."""
  et = tuple(name.split('__'))
  if len(et) != 3:
    raise ValueError(f'an edge type is <src>__<rel>__<dst>, got {name!r}')
  return et


def generate(dataset, graph_seed, log=lambda k, v: None):
  """The dataset a configuration's ``dataset`` group describes, as host
  arrays: ``csr`` ``{edge type: (indptr int64, indices int32)}`` with the
  reverses, ``feat`` ``{node type: [n, F]}`` in ``feature_dtype``,
  ``label`` (int32, of ``label_type``) and ``train_idx``. The same graph,
  labels and split for the same arguments everywhere; the same rows on
  the same platform."""
  import time
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
    csr[et] = (indptr, indices)
    if 'reverse' in rel:
      csr[etype_of(rel['reverse'])] = _transpose(indices, deg, sizes[dst])
  log('generate_graph_s', time.perf_counter() - t0)
  t0 = time.perf_counter()
  dtype = np.dtype(d['feature_dtype'])
  feat = {}
  for i, t in enumerate(sizes):
    lab = t == t_lab
    feat[t] = _rows(int(_rng(graph_seed, 2, i).integers(2 ** 31)), sizes[t],
                    int(d['feat_dim']), dtype, comm if lab else None, c,
                    float(d['feat_snr']) if lab else 0.0)
  log('generate_rows_s', time.perf_counter() - t0)
  train = _rng(graph_seed, 3).permutation(sizes[t_lab])[
      :int(d['num_train'])].astype(np.int32)
  return dict(csr=csr, feat=feat, label=comm, train_idx=train,
              num_nodes=sizes)

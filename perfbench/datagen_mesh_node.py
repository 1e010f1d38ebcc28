"""The partitioned generator of family ``mesh_node``: every shard's CSR,
rows and labels made ON ITS OWN DEVICE from ``graph_seed``, so nothing of
size N x F or E is ever in host memory (at a third of papers100M the row
store is 19 GB and the edge list 2.2 GB).

The law is ``perfbench/datagen.py``'s (the products generator: a community
per node = its label, a popularity weight from a truncated power law,
Poisson out-degrees nudged to the exact edge count, every edge's target by
popularity, ``p_intra`` of the time inside the source's community), with
what a partitioned graph needs:

* **node -> partition is ``id % P``**: shard ``p`` owns ``p, p + P, ...``;
  its local row ``i`` is node ``p + P * i``. Each shard draws the degrees
  and the targets of its own rows, ``E // P`` edges of the total (the
  first ``E % P`` shards one more), from keys folded with its index.
* **a node's label and feature row are pure functions of ``graph_seed``
  and the node id** (:func:`labels_of`, :func:`rows_of`): integer hashes
  and ONE exact int -> float32 conversion, so ``numpy`` on the host and
  ``jax.numpy`` on the chip give the same bits. A row is ``(noise +
  centre[label]) * 2**-21`` with the noise uniform on ``[-2**22, 2**22)``
  (uniform on [-2, 2), variance 4/3, where the products generator draws a
  unit normal) and the centres ``feat_snr`` times such a draw, per class.
  The check regenerates any row on the host; the plain reference
  regenerates its batches' rows on its one device.
* the node-level tables the targets are drawn from (the class-sorted
  order and its cumulative popularity, N int32 each) are made once,
  replicated, and dropped before the rows are made; the in-degree every
  shard counts while it draws is summed over the mesh and kept: it ranks
  the hot cache.

``generate`` returns device arrays, sharded as ``DistGraph`` /
``DistFeature.from_device_shards`` take them, and the host's ``node_pb``
and train split (N and ``num_train`` int32: books, not tables).
"""
import functools

import numpy as np

from perfbench.datagen import fit_powerlaw_alpha

INT32_MAX = np.iinfo(np.int32).max
_M1, _M2, _GOLD = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9
NOISE_BITS = 23                 # noise in [-2**22, 2**22)
SCALE = 2.0 ** -21              # -> uniform on [-2, 2)


def _mix(xp, x):
  """murmur3's 32-bit finaliser, in wrapping uint32 arithmetic: the same
  bits from ``numpy`` and from ``jax.numpy``."""
  u = xp.uint32
  x = x ^ (x >> u(16))
  x = x * u(_M1)
  x = x ^ (x >> u(13))
  x = x * u(_M2)
  return x ^ (x >> u(16))


def labels_of(xp, ids, graph_seed, num_classes):
  """The community (= label) of nodes ``ids``: int32 in [0, C)."""
  u = xp.uint32
  h = _mix(xp, ids.astype(u) ^ u((int(graph_seed) * 2 + 1) & 0xFFFFFFFF))
  return (h % u(num_classes)).astype(xp.int32)


def _noise(xp, a, b, salt):
  """int32 in [-2**22, 2**22) for every pair of ``a[:, None]`` and
  ``b[None, :]`` (uint32 vectors)."""
  u = xp.uint32
  h = _mix(xp, _mix(xp, a ^ u(salt))[:, None] + b[None, :] * u(_GOLD))
  return (h >> u(32 - NOISE_BITS)).astype(xp.int32) - xp.int32(
      1 << (NOISE_BITS - 1))


def centres(graph_seed, num_classes, feat_dim, feat_snr):
  """The [C, F] int32 class centres, on the host: ``feat_snr`` times a
  draw of the noise's own law, floored to the integer grid."""
  salt = (int(graph_seed) * 7 + 3) & 0xFFFFFFFF
  n = _noise(np, np.arange(num_classes, dtype=np.uint32),
             np.arange(feat_dim, dtype=np.uint32), salt)
  return np.floor(n.astype(np.float64) * feat_snr).astype(np.int32)


def rows_of(xp, ids, graph_seed, num_classes, centre_table):
  """The float32 feature rows ``[len(ids), F]`` of nodes ``ids`` (any
  order, repeats allowed): noise(id, column) + centre[label(id)], both
  integers under 2**23 in magnitude, converted once and scaled by a power
  of two — exact in float32 whoever computes it."""
  salt = (int(graph_seed) * 5 + 1) & 0xFFFFFFFF
  feat_dim = centre_table.shape[1]
  n = _noise(xp, ids.astype(xp.uint32),
             xp.arange(feat_dim, dtype=xp.uint32), salt)
  c = centre_table[labels_of(xp, ids, graph_seed, num_classes)]
  return (n + c).astype(xp.float32) * xp.float32(SCALE)


def popularity_cdf(mean_deg, dmax):
  """(float32 cdf over 1..dmax, alpha) of the popularity weight."""
  alpha = fit_powerlaw_alpha(mean_deg, dmax)
  pmf = np.arange(1, dmax + 1, dtype=np.float64) ** -alpha
  return np.cumsum(pmf / pmf.sum()).astype(np.float32), alpha


def shard_sizes(num_nodes, num_edges, parts):
  """(rows of the fullest shard, edges of the fullest shard): shard p
  owns ``ceil((N - p) / P)`` rows and ``E // P`` (+1 below ``E % P``)
  edges."""
  return -(-num_nodes // parts), -(-num_edges // parts)


def programs(mesh, num_nodes, num_edges, num_classes, feat_dim, p_intra,
             feat_snr, num_train, graph_seed, powerlaw_dmax,
             edge_piece=1 << 21, row_piece=1 << 18):
  """The generator's three jitted programs over ``mesh``, the host's
  draw of the train split and the host constants they take, not yet run:
  ``dict(nodes, edges, rows, train, cdf, centres, n_max, e_max)``.
  ``generate`` runs them; ``scripts/lower_typed_cell.py mesh-generator``
  compiles the programs for a described chip at the cell's size."""
  import jax
  import jax.numpy as jnp
  from jax import lax
  from jax.sharding import NamedSharding, PartitionSpec as P

  # the program's own version shim for shard_map: a generator that
  # cannot place a shard has nothing to generate for
  from graphlearn_tpu.utils.compat import shard_map
  n, e, c = int(num_nodes), int(num_edges), int(num_classes)
  ax = tuple(mesh.axis_names)
  parts = int(np.prod([mesh.shape[a] for a in ax]))
  if e >= 2 ** 31 or n >= 2 ** 31:
    raise ValueError('int32 ids: the generator stops short of 2**31')
  n_max, e_top = shard_sizes(n, e, parts)
  pieces = -(-e_top // edge_piece)
  e_max = pieces * edge_piece
  cdf, _ = popularity_cdf(e / n, powerlaw_dmax)
  if n * (e / n) * 1.5 >= 2 ** 31:
    raise ValueError('the cumulative popularity would pass int32')
  k_w, k_deg, k_edge, _ = jax.random.split(
      jax.random.PRNGKey(int(graph_seed)), 4)
  repl = NamedSharding(mesh, P())
  centre_h = centres(graph_seed, c, feat_dim, feat_snr)

  # ---- node-level tables, replicated: N int32 each, gone after the edges
  shift = 5           # a coarse bucket of the cumulative popularity holds
                      # at most 2**shift nodes (every weight is >= 1)

  @functools.partial(jax.jit, out_shardings=repl)
  def nodes(cdf):
    ids = jnp.arange(n, dtype=jnp.int32)
    comm = labels_of(jnp, ids, graph_seed, c)
    w = 1 + jnp.searchsorted(cdf, jax.random.uniform(k_w, (n,))
                             ).astype(jnp.int32)
    w = jnp.minimum(w, cdf.shape[0])
    counts = jnp.zeros((c,), jnp.int32).at[comm].add(1)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])
    # order = argsort(comm, stable) as a counting sort, a class at a
    # time: a node's place is its class's offset plus its rank among the
    # class's earlier ids (a sort of N keys takes the chip's compiler
    # half a minute; this body, a second)
    place = lax.fori_loop(
        0, c, lambda k, at: jnp.where(
            comm == k, offs[k] + jnp.cumsum(comm == k, dtype=jnp.int32) - 1,
            at), jnp.zeros((n,), jnp.int32))
    order = jnp.zeros((n,), jnp.int32).at[place].set(ids)
    cw = jnp.cumsum(w[order])
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), cw])[offs]
    # start[b] = how many cumulative weights lie under b << shift: a
    # draw r then finds its node inside [start[r >> shift], start[+1]]
    # in shift + 1 halvings, not log2(N)
    # (the total weight is E in expectation; a total past the table's
    # end would land its draws in the last bucket: still nodes)
    nb = (min(n * cdf.shape[0], 2 * e + 4096) >> shift) + 2
    hist = jnp.zeros((nb,), jnp.int32).at[cw >> shift].add(1, mode='drop')
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(hist)]).at[-1].set(n)
    return order, cw, bounds, start

  # ---- every shard's CSR, on its device
  def edges(order, cw, bounds, start):
    me = jnp.int32(0)
    for a in ax:
      me = me * mesh.shape[a] + lax.axis_index(a)
    rows = (n - me + parts - 1) // parts          # rows this shard owns
    mine = e // parts + (me < e % parts)          # edges this shard draws
    local = jnp.arange(n_max, dtype=jnp.int32)
    owned = local < rows
    row_ids = jnp.where(owned, me + parts * local, INT32_MAX)
    deg = jnp.where(owned, jax.random.poisson(
        jax.random.fold_in(k_deg, me), e / n, (n_max,)).astype(jnp.int32), 0)
    # nudge the leading rows by one until the degrees sum to ``mine``
    diff = mine - deg.sum()
    deg = jnp.where(diff >= 0, deg + (owned & (local < diff)),
                    deg - ((jnp.cumsum(deg > 0) <= -diff) & (deg > 0)))
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(deg.astype(jnp.int32))])
    comm = labels_of(jnp, jnp.where(owned, row_ids, 0), graph_seed, c)
    # the source row of every edge slot: one mark per row end, summed
    src_of = jnp.cumsum(jnp.zeros((e_max,), jnp.int32).at[
        indptr[1:-1]].add(1, mode='drop'))

    def piece(i, carry):
      indices, indeg = carry
      kr, ki, kg = jax.random.split(
          jax.random.fold_in(jax.random.fold_in(k_edge, me), i), 3)
      eid = i * edge_piece + jnp.arange(edge_piece, dtype=jnp.int32)
      src = jnp.minimum(lax.dynamic_slice(src_of, (i * edge_piece,),
                                          (edge_piece,)), n_max - 1)
      rc = comm[src]
      lo, hi = bounds[rc], bounds[rc + 1]
      intra = jax.random.uniform(kr, (edge_piece,)) < p_intra
      r_in = jax.random.randint(ki, (edge_piece,), lo,
                                jnp.maximum(hi, lo + 1))
      r_gl = jax.random.randint(kg, (edge_piece,), 0, cw[-1])
      r = jnp.where(intra, r_in, r_gl)
      # pos = how many cumulative weights are <= r (searchsorted right)
      b = jnp.minimum(r >> shift, start.shape[0] - 2)
      plo, phi = start[b], start[b + 1]
      for _ in range(shift + 1):
        mid = (plo + phi) >> 1
        right = (cw[jnp.minimum(mid, n - 1)] <= r) & (mid < phi)
        plo = jnp.where(right, mid + 1, plo)
        phi = jnp.where(right, phi, mid)
      live = eid < mine
      cols = jnp.where(live, order[jnp.minimum(plo, n - 1)], -1)
      indeg = indeg.at[jnp.where(live, cols, n)].add(1, mode='drop')
      return (lax.dynamic_update_slice(indices, cols, (i * edge_piece,)),
              indeg)

    indices, indeg = lax.fori_loop(
        0, pieces, piece, (jnp.full((e_max,), -1, jnp.int32),
                           jnp.zeros((n,), jnp.int32)))
    return (row_ids[None], indptr[None], indices[None], comm[None],
            lax.psum(indeg, ax))

  edges_fn = jax.jit(shard_map(
      edges, mesh=mesh, in_specs=(P(), P(), P(), P()),
      out_specs=(P(ax), P(ax), P(ax), P(ax), P()),
      check_replication=False))

  # ---- every shard's rows, on its device, a piece of rows at a time
  row_piece = min(row_piece, n_max)
  r_pieces = -(-n_max // row_piece)

  def rows(row_ids, centre):
    ids = row_ids[0]

    def piece(i, feats):
      # the last piece starts early enough to end at n_max: a row is a
      # function of its id, so rows written twice are written the same
      at0 = jnp.minimum(i * row_piece, n_max - row_piece)
      at = lax.dynamic_slice(ids, (at0,), (row_piece,))
      live = at != INT32_MAX
      x = rows_of(jnp, jnp.where(live, at, 0), graph_seed, c, centre)
      return lax.dynamic_update_slice(
          feats, jnp.where(live[:, None], x, 0)[None], (0, at0, 0))

    # built with its shard axis: adding it afterwards would copy 4.7 GB
    return lax.fori_loop(0, r_pieces, piece,
                         jnp.zeros((1, n_max, feat_dim), jnp.float32))

  rows_fn = jax.jit(shard_map(rows, mesh=mesh, in_specs=(P(ax), P()),
                              out_specs=P(ax), check_replication=False))
  def train():
    """The uniform train split, ``num_train`` int32 ids: drawn on the
    host (a book, like ``node_pb``) — a device permutation of N is three
    sorts of N keys, most of a minute of the chip's compiler."""
    return np.random.default_rng([int(graph_seed), 4]).choice(
        n, int(num_train), replace=False).astype(np.int32)

  return dict(nodes=nodes, edges=edges_fn, rows=rows_fn, train=train,
              cdf=cdf, centres=centre_h, n_max=n_max, e_max=e_max,
              parts=parts, replicated=repl)


def generate(mesh, num_nodes, *args, log=lambda k, v: None, **kw):
  """The dataset on ``mesh`` (a flat axis; arguments as
  :func:`programs`): ``dict(graph=dict(row_ids, indptr, indices),
  features=dict(feat_ids, feats), labels [P, n_max], in_degree [N]
  replicated, node_pb, train_idx, centres)``; the last three on the host.
  ``indices`` is FILL-padded to a whole number of ``edge_piece``s."""
  import time

  import jax
  g = programs(mesh, num_nodes, *args, **kw)
  t0 = time.perf_counter()
  tables = g['nodes'](jax.device_put(g['cdf'], g['replicated']))
  row_ids, indptr, indices, labels, in_degree = g['edges'](*tables)
  jax.block_until_ready(indices)
  del tables
  log('generate_graph_s', time.perf_counter() - t0)
  t0 = time.perf_counter()
  feats = g['rows'](row_ids, jax.device_put(g['centres'], g['replicated']))
  jax.block_until_ready(feats)
  log('generate_rows_s', time.perf_counter() - t0)
  return dict(
      graph=dict(row_ids=row_ids, indptr=indptr, indices=indices),
      features=dict(feat_ids=row_ids, feats=feats), labels=labels,
      in_degree=in_degree, centres=g['centres'],
      node_pb=(np.arange(int(num_nodes), dtype=np.int32) %
               g['parts']).astype(np.int32),
      train_idx=g['train']())

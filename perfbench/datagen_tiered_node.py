"""The one-chip generator of family ``tiered_node``: the graph of
``perfbench/datagen_mesh_node.py`` AT ONE PARTITION — the same law, the same
keys, the same bits (``tests/perfbench_tests/test_tiered_node.py`` holds a toy
graph equal to ``datagen_mesh_node.generate`` over a one-device mesh) — made
at a cost one chip can pay inside a set-up.

What differs is what one chip cannot afford 257 times over (the mesh
generator's edge loop runs a quarter of its pieces on each of four chips):

* **the target search.** A draw ``r`` finds its node as "how many cumulative
  popularity weights are <= r". The mesh generator answers by two reads of a
  coarse bucket table and six halvings over ``cw`` — eight random reads of
  37 M-entry tables per edge, most of 95 s of ``generate_graph_s`` (my chip
  runs, PR 41). Here that count is tabulated ONCE for every possible ``r``
  (one histogram of ``cw`` and its cumulative sum: 4 B a unit of total
  weight, 2.2 GB at this family's size, gone after the edges) and a draw
  reads it once.
* **the in-degree.** The mesh generator scatter-adds every piece's 2 M
  targets into an N-wide counter INSIDE its edge loop (44 s on one chip).
  Here the loop only draws, and the in-degree is a ``bincount`` over the
  indices the family fetches to the host anyway (its caps are calibrated on
  the host CSR and its exact numbers counted against it).

Labels, rows, centres, the popularity tables and the train split are the
mesh generator's own functions.
"""
import concurrent.futures
import functools

import numpy as np

from perfbench import datagen_mesh_node as mesh_gen

COUNT_SLICE = 1 << 25       # indices one in-degree task counts


def generate(num_nodes, num_edges, num_classes, feat_dim, p_intra, feat_snr,
             num_train, graph_seed, powerlaw_dmax, edge_piece=1 << 21,
             log=lambda k, v: None):
  """``dict(indptr [N + 1] int32, indices [e_max] int32 (FILL-padded past
  E), in_degree [N] int64, centres, train_idx)``, all on the HOST: the CSR
  is drawn on the chip and fetched once; nothing of it stays on the
  device."""
  import time

  import jax
  import jax.numpy as jnp
  from jax import lax
  from jax.sharding import Mesh
  n, e, c = int(num_nodes), int(num_edges), int(num_classes)
  base = mesh_gen.programs(
      Mesh(np.array(jax.devices()[:1]), ('g',)), n, e, c, feat_dim, p_intra,
      feat_snr, num_train, graph_seed, powerlaw_dmax, edge_piece=edge_piece)
  e_max = base['e_max']
  pieces = e_max // edge_piece
  _, k_deg, k_edge, _ = jax.random.split(
      jax.random.PRNGKey(int(graph_seed)), 4)

  @functools.partial(jax.jit, static_argnums=1)
  def weights_up_to(cw, total):
    """``[total + 1]``: how many cumulative weights are ``<= r``, for every
    ``r`` a draw can be (``cw`` ends at ``total``)."""
    return jnp.cumsum(jnp.zeros((total + 1,), jnp.int32).at[cw].add(
        1, mode='drop'))

  @jax.jit
  def edges(order, cw, bounds, at_most):
    """``mesh_gen.programs``' ``edges`` for the shard that owns every row
    (partition 0 of 1), the target found by one read of ``at_most``,
    without the in-degree."""
    local = jnp.arange(n, dtype=jnp.int32)
    deg = jax.random.poisson(jax.random.fold_in(k_deg, 0), e / n,
                             (n,)).astype(jnp.int32)
    diff = e - deg.sum()
    deg = jnp.where(diff >= 0, deg + (local < diff),
                    deg - ((jnp.cumsum(deg > 0) <= -diff) & (deg > 0)))
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(deg.astype(jnp.int32))])
    comm = mesh_gen.labels_of(jnp, local, graph_seed, c)
    src_of = jnp.cumsum(jnp.zeros((e_max,), jnp.int32).at[
        indptr[1:-1]].add(1, mode='drop'))

    def piece(i, indices):
      kr, ki, kg = jax.random.split(
          jax.random.fold_in(jax.random.fold_in(k_edge, 0), i), 3)
      eid = i * edge_piece + jnp.arange(edge_piece, dtype=jnp.int32)
      src = jnp.minimum(lax.dynamic_slice(src_of, (i * edge_piece,),
                                          (edge_piece,)), n - 1)
      rc = comm[src]
      lo, hi = bounds[rc], bounds[rc + 1]
      intra = jax.random.uniform(kr, (edge_piece,)) < p_intra
      r_in = jax.random.randint(ki, (edge_piece,), lo,
                                jnp.maximum(hi, lo + 1))
      r_gl = jax.random.randint(kg, (edge_piece,), 0, cw[-1])
      r = jnp.where(intra, r_in, r_gl)
      pos = at_most[jnp.minimum(r, at_most.shape[0] - 1)]
      cols = jnp.where(eid < e, order[jnp.minimum(pos, n - 1)], -1)
      return lax.dynamic_update_slice(indices, cols, (i * edge_piece,))

    return indptr, lax.fori_loop(0, pieces, piece,
                                 jnp.full((e_max,), -1, jnp.int32))

  t0 = time.perf_counter()
  order, cw, bounds, _ = base['nodes'](
      jax.device_put(base['cdf'], base['replicated']))
  indptr, indices = edges(order, cw, bounds,
                          weights_up_to(cw, int(cw[-1])))
  del order, cw, bounds
  indptr, indices = np.asarray(indptr), np.asarray(indices)
  log('generate_graph_s', time.perf_counter() - t0)
  t0 = time.perf_counter()
  count = lambda a: np.bincount(indices[a:min(a + COUNT_SLICE, e)],
                                minlength=n)
  in_degree = np.zeros((n,), np.int64)
  with concurrent.futures.ThreadPoolExecutor(4) as pool:
    for part in pool.map(count, range(0, e, COUNT_SLICE)):
      in_degree += part
  log('in_degree_s', time.perf_counter() - t0)
  return dict(indptr=indptr, indices=indices, in_degree=in_degree,
              centres=base['centres'], train_idx=base['train']())

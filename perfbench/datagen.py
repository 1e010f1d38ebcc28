"""The benchmark's own copy of the products-matched generator, on the device.

The law is the one of ``examples/train_sage_ogbn_products.py``
``make_synthetic`` (copied, not imported: the yardstick must not move when
the example does): every node gets a community (= its label) and a
popularity weight drawn from a truncated power law fitted to the published
ogbn-products mean / max degree; every edge picks its source uniformly and
its target by popularity, ``p_intra`` of the time inside the source's
community; features are community centre * ``feat_snr`` + unit noise.

What differs, and why (``assumed`` in the configuration files says so too):

* the number of DIRECTED edges is an argument, so a configuration can ask
  for the published 123,718,280 and not the example's ``num_nodes * 25``;
* sources are not drawn and sorted: out-degrees are drawn Poisson(E/N) —
  the law a uniform source draw induces — and the first nodes are nudged
  by one until they sum to exactly E. The edges are then born in CSR
  order, so nothing of size E is ever sorted;
* popularity weights are kept as integers, so the 2.4 M-entry cumulative
  sum is exact in int32 (float32 loses the low bits at 1.2e8);
* it runs in ``jax.numpy`` from ``graph_seed`` in fixed-size pieces, so
  set-up's device peak stays far under the measured window's, and the
  host never loops over edges.

``generate`` returns host numpy arrays: the benchmark keeps them as the
plain reference's own data and hands copies to the program.
"""
import functools

import numpy as np

#: ogbn-products published summary statistics the degree law is fitted to
PRODUCTS_N = 2_449_029
PRODUCTS_MEAN_DEG = 50.5
PRODUCTS_MAX_DEG = 17_481


def fit_powerlaw_alpha(mean_deg, dmax):
  """Exponent of a truncated discrete power law P(d) ~ d^-alpha on
  [1, dmax] whose mean is ``mean_deg`` (bisection; products: ~1.68)."""
  d = np.arange(1, dmax + 1, dtype=np.float64)

  def mean_of(alpha):
    w = d ** -alpha
    return float((d * w).sum() / w.sum())

  lo, hi = 1.01, 4.0
  for _ in range(60):
    mid = 0.5 * (lo + hi)
    if mean_of(mid) > mean_deg:
      lo = mid
    else:
      hi = mid
  return 0.5 * (lo + hi)


def powerlaw_cdf(num_nodes, num_edges):
  """Cumulative distribution of the popularity weight over 1..dmax, the
  cutoff scaled with this graph's share of products' edges (at the
  published size it is the published maximum degree)."""
  share = num_edges / (PRODUCTS_N * PRODUCTS_MEAN_DEG)
  dmax = max(64, int(PRODUCTS_MAX_DEG * share))
  alpha = fit_powerlaw_alpha(PRODUCTS_MEAN_DEG, PRODUCTS_MAX_DEG)
  pmf = np.arange(1, dmax + 1, dtype=np.float64) ** -alpha
  return np.cumsum(pmf / pmf.sum()).astype(np.float32), alpha, dmax


def _pieces(num_edges, target=3_000_000):
  """Number of equal pieces the edge list is drawn in: the divisor of
  ``num_edges`` whose piece is nearest ``target`` edges."""
  best = 1
  for p in range(1, 4097):
    if num_edges % p == 0 and abs(num_edges // p - target) < abs(
        num_edges // best - target):
      best = p
  return best


def generate(num_nodes, num_edges, num_classes, feat_dim, p_intra, feat_snr,
             num_train, graph_seed):
  """(indptr[N+1] int64, indices[E] int32, feat[N,F] float32, label[N]
  int32, train_idx[num_train] int32) as host arrays, the same for the
  same arguments on the same platform."""
  import jax
  import jax.numpy as jnp
  n, e, c = int(num_nodes), int(num_edges), int(num_classes)
  if e >= 2 ** 31 or n >= 2 ** 31:
    raise ValueError('int32 ids: the generator stops short of 2**31')
  cdf, _, _ = powerlaw_cdf(n, e)
  k_comm, k_w, k_deg, k_edge, k_cent, k_feat, k_perm = jax.random.split(
      jax.random.PRNGKey(int(graph_seed)), 7)

  @jax.jit
  def nodes(cdf):
    comm = jax.random.randint(k_comm, (n,), 0, c, jnp.int32)
    w = 1 + jnp.searchsorted(cdf, jax.random.uniform(k_w, (n,)),
                             method='sort').astype(jnp.int32)
    w = jnp.minimum(w, cdf.shape[0])
    # nodes sorted by class: one cumulative weight vector serves the
    # global and the within-class popularity draws
    order = jnp.argsort(comm, stable=True).astype(jnp.int32)
    cw = jnp.cumsum(w[order])
    counts = jnp.zeros((c,), jnp.int32).at[comm].add(1)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), cw])[offs]
    deg = jax.random.poisson(k_deg, e / n, (n,)).astype(jnp.int32)
    # nudge the leading nodes by one until the degrees sum to exactly e
    diff = e - deg.sum()
    idx = jnp.arange(n)
    nudged = jnp.where(diff >= 0, deg + (idx < diff),
                       deg - ((jnp.cumsum(deg > 0) <= -diff) & (deg > 0)))
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(nudged.astype(jnp.int32))])
    return comm, order, cw, bounds, indptr

  comm, order, cw, bounds, indptr = nodes(jnp.asarray(cdf))
  pieces = _pieces(e)
  ch = e // pieces

  @functools.partial(jax.jit, donate_argnums=(0,))
  def piece(indices, i, comm, order, cw, bounds, indptr):
    kr, ki, kg = jax.random.split(jax.random.fold_in(k_edge, i), 3)
    eid = i * ch + jnp.arange(ch, dtype=jnp.int32)
    rows = jnp.searchsorted(indptr, eid, side='right', method='sort') - 1
    rc = comm[jnp.clip(rows, 0, n - 1)]
    lo, hi = bounds[rc], bounds[rc + 1]
    intra = jax.random.uniform(kr, (ch,)) < p_intra
    r_in = jax.random.randint(ki, (ch,), lo, jnp.maximum(hi, lo + 1))
    r_gl = jax.random.randint(kg, (ch,), 0, cw[-1])
    pos = jnp.searchsorted(cw, jnp.where(intra, r_in, r_gl), side='right',
                           method='sort')
    cols = order[jnp.minimum(pos, n - 1)]
    return jax.lax.dynamic_update_slice(indices, cols, (i * ch,))

  indices = jnp.zeros((e,), jnp.int32)
  for i in range(pieces):
    indices = piece(indices, jnp.int32(i), comm, order, cw, bounds, indptr)
  indptr_h = np.asarray(indptr).astype(np.int64)
  indices_h = np.asarray(indices)
  label_h = np.asarray(comm)
  del indices, order, cw, bounds, indptr

  @jax.jit
  def feats(comm):
    centers = jax.random.normal(k_cent, (c, feat_dim), jnp.float32)
    return centers[comm] * feat_snr + jax.random.normal(
        k_feat, (n, feat_dim), jnp.float32)

  feat_h = np.asarray(feats(comm))
  train_h = np.asarray(
      jax.random.permutation(k_perm, n)[:num_train].astype(jnp.int32))
  if int(indptr_h[-1]) != e:
    raise RuntimeError(f'generator made {int(indptr_h[-1])} edges, not {e}')
  return indptr_h, indices_h, feat_h, label_h, train_h

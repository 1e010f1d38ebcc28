"""SEAL link prediction: enclosing subgraphs + DRNL + DGCNN.

Counterpart of /root/reference/examples/seal_link_pred.py: for every
candidate link, extract the k-hop enclosing subgraph around (src, dst)
with the framework's ``NeighborSampler.subgraph`` (the reference's
subgraph_sampler.subgraph call, seal_link_pred.py:80-96), remove the
target link, compute Double-Radius Node Labeling (DRNL, :104-134), and
train a DGCNN (GCN stack + global sort-pooling + 1D convs, :151-198) to
classify links, reported as AUC.

TPU-shaped differences: subgraphs are padded to fixed (node, edge) caps
and the whole DGCNN step runs as ONE jitted program over a [B, N, ...]
batch (shared params via nn.vmap) — no per-graph dynamic shapes; the
k-hop expansion uses capped fanouts instead of the reference's [-1]
(all-neighbor) expansion, an explicit bound on celebrity vertices.
Cora isn't downloadable here (zero egress), so a Cora-scale SBM stands
in. DRNL/extraction is preprocessing; by default this example runs on
the CPU backend (small graphs; per-link extraction is dispatch-bound —
set --platform tpu on a directly-attached chip).

Run: python examples/seal_link_pred.py --epochs 3
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))


def drnl_node_labeling(rows, cols, num_nodes, src, dst):
  """DRNL z-labels (reference seal_link_pred.py:104-134): distances to
  src computed without dst (and vice versa), combined into a structural
  label; src/dst get 1, unreachable get 0."""
  import scipy.sparse as sp
  from scipy.sparse.csgraph import shortest_path
  adj = sp.coo_matrix((np.ones(len(rows)), (rows, cols)),
                      shape=(num_nodes, num_nodes)).tocsr()
  src, dst = (dst, src) if src > dst else (src, dst)
  idx_wo_src = list(range(src)) + list(range(src + 1, num_nodes))
  idx_wo_dst = list(range(dst)) + list(range(dst + 1, num_nodes))
  adj_wo_src = adj[idx_wo_src, :][:, idx_wo_src]
  adj_wo_dst = adj[idx_wo_dst, :][:, idx_wo_dst]
  d2src = shortest_path(adj_wo_dst, directed=False, unweighted=True,
                        indices=src)
  d2src = np.insert(d2src, dst, 0, axis=0)
  d2dst = shortest_path(adj_wo_src, directed=False, unweighted=True,
                        indices=dst - 1)
  d2dst = np.insert(d2dst, src, 0, axis=0)
  dist = d2src + d2dst
  with np.errstate(invalid='ignore'):   # inf distances -> nan -> z=0
    dist_over_2, dist_mod_2 = dist // 2, dist % 2
    z = 1 + np.minimum(d2src, d2dst)
    z += dist_over_2 * (dist_over_2 + dist_mod_2 - 1)
  z[src] = 1.0
  z[dst] = 1.0
  z[np.isnan(z)] = 0.0
  return z.astype(np.int64)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=3)
  ap.add_argument('--num-nodes', type=int, default=1000)
  ap.add_argument('--num-links', type=int, default=400,
                  help='positive links per split-source (same # negatives)')
  ap.add_argument('--batch-size', type=int, default=32)
  ap.add_argument('--fanout', type=int, nargs='+', default=[8, 8])
  ap.add_argument('--node-cap', type=int, default=96)
  ap.add_argument('--edge-cap', type=int, default=768)
  ap.add_argument('--sortpool-k', type=int, default=30)
  ap.add_argument('--platform', default='cpu', choices=['cpu', 'tpu', ''])
  args = ap.parse_args()

  import jax
  if args.platform == 'cpu':
    # must run before any backend use
    jax.config.update('jax_platforms', 'cpu')
  import jax.numpy as jnp
  import flax.linen as nn
  import optax
  import graphlearn_tpu as glt
  from graphlearn_tpu.models import GCNConv

  rng = np.random.default_rng(0)
  # Cora-scale community graph (communities = residue classes mod 8,
  # intra-heavy) => links are predictable from structure
  n = args.num_nodes
  e = n * 6
  rows = rng.integers(0, n, e)
  intra = rng.random(e) < 0.85
  cols = np.where(intra, (rows + 8 * rng.integers(0, n // 8, e)) % n,
                  rng.integers(0, n, e))
  keep = rows != cols
  und = np.stack([np.concatenate([rows[keep], cols[keep]]),
                  np.concatenate([cols[keep], rows[keep]])])

  # link split: held-out positive edges (removed from the graph) + random
  # negatives per split (reference RandomLinkSplit split_labels=True)
  e_und = und.shape[1] // 2
  perm = rng.permutation(e_und)
  n_test = args.num_links
  test_pos = und[:, perm[:n_test]]
  train_pos = und[:, perm[n_test:n_test + args.num_links]]
  graph_edges_idx = perm[n_test:]          # test edges removed from graph
  ge = np.concatenate([graph_edges_idx, graph_edges_idx + e_und])
  graph_ei = und[:, ge]

  edge_set = {(int(r), int(c)) for r, c in und.T}

  def sample_negs(k):
    out = []
    while len(out) < k:
      r, c = int(rng.integers(0, n)), int(rng.integers(0, n))
      if r != c and (r, c) not in edge_set:
        out.append((r, c))
    return np.array(out, np.int64).T

  train_neg = sample_negs(args.num_links)
  test_neg = sample_negs(n_test)

  graph = glt.data.Graph(glt.data.Topology(graph_ei, num_nodes=n), 'CPU')
  sampler = glt.sampler.NeighborSampler(graph, args.fanout, seed=0)

  z_cap = 64

  def extract(links, y):
    """Per-link enclosing subgraph -> padded (x, ei, em, nmask, y)."""
    from graphlearn_tpu.sampler import NodeSamplerInput
    xs, eis, ems, nms, ys = [], [], [], [], []
    for src, dst in links.T:
      out = sampler.subgraph(
          NodeSamplerInput(np.array([src, dst]))).trim()
      node = np.asarray(out.node)
      r = np.asarray(out.row)
      c = np.asarray(out.col)
      mapping = np.asarray(out.metadata['mapping'])
      s_l, d_l = int(mapping[0]), int(mapping[1])
      # remove the target link itself (both directions)
      m = ~(((r == s_l) & (c == d_l)) | ((r == d_l) & (c == s_l)))
      r, c = r[m], c[m]
      z = drnl_node_labeling(r, c, len(node), s_l, d_l)
      z = np.minimum(z, z_cap - 1)
      # pad to caps (truncate the rare overflow)
      nn_ = min(len(node), args.node_cap)
      ne = min(len(r), args.edge_cap)
      x = np.zeros((args.node_cap,), np.int32)
      x[:nn_] = z[:nn_]
      ei = np.full((2, args.edge_cap), -1, np.int32)
      sel = (r < nn_) & (c < nn_)
      r2, c2 = r[sel][:ne], c[sel][:ne]
      ei[0, :len(r2)] = r2
      ei[1, :len(r2)] = c2
      em = ei[0] >= 0
      nmask = np.arange(args.node_cap) < nn_
      xs.append(x)
      eis.append(ei)
      ems.append(em)
      nms.append(nmask)
      ys.append(y)
    return [np.stack(a) for a in (xs, eis, ems, nms, ys)]

  t0 = time.time()
  tr = [np.concatenate(p) for p in
        zip(extract(train_pos, 1), extract(train_neg, 0))]
  te = [np.concatenate(p) for p in
        zip(extract(test_pos, 1), extract(test_neg, 0))]
  extract_s = time.time() - t0

  class DGCNN(nn.Module):
    """Reference DGCNN (seal_link_pred.py:151-198): GCN stack -> sort
    pool top-k -> per-row conv (= the stride-|h| Conv1d) -> Conv1d(5) ->
    MLP head. Operates on ONE padded graph; vmapped over the batch."""
    hidden: int = 32
    num_layers: int = 3
    k: int = 30

    @nn.compact
    def __call__(self, z, ei, em, nmask):
      x = nn.Embed(z_cap, self.hidden, name='z_embed')(z)
      xs = []
      for i in range(self.num_layers):
        x = jnp.tanh(GCNConv(self.hidden, name=f'gcn{i}')(x, ei, em))
        xs.append(x)
      x = jnp.tanh(GCNConv(1, name='gcn_last')(x, ei, em))
      xs.append(x)
      h = jnp.concatenate(xs, axis=-1)              # [N, total]
      # global sort pool: order valid nodes by the last channel desc
      key = jnp.where(nmask, h[:, -1], -jnp.inf)
      idx = jnp.argsort(-key)[:self.k]
      pooled = h[idx] * nmask[idx][:, None]         # [k, total]
      # Conv1d(1, 16, kernel=total, stride=total) == per-row Dense(16)
      c = nn.relu(nn.Dense(16, name='conv1')(pooled))   # [k, 16]
      c = nn.max_pool(c[None], (2,), strides=(2,))[0]   # [k/2, 16]
      c = nn.relu(nn.Conv(32, (5,), name='conv2')(c[None])[0])
      f = c.reshape(-1)
      f = nn.relu(nn.Dense(128, name='mlp1')(f))
      return nn.Dense(1, name='mlp2')(f)[0]

  model = nn.vmap(DGCNN, in_axes=0, out_axes=0,
                  variable_axes={'params': None},
                  split_rngs={'params': False})(k=args.sortpool_k)

  sample = [jnp.asarray(a[:args.batch_size]) for a in tr[:4]]
  params = model.init(jax.random.PRNGKey(0), *sample)
  tx = optax.adam(1e-3)
  opt_state = tx.init(params)

  def loss_fn(params, batch):
    logits = model.apply(params, batch['z'], batch['ei'], batch['em'],
                         batch['nm'])
    return optax.sigmoid_binary_cross_entropy(
        logits, batch['y'].astype(jnp.float32)).mean()

  @jax.jit
  def step(params, opt_state, batch):
    loss, g = jax.value_and_grad(loss_fn)(params, batch)
    updates, opt_state = tx.update(g, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss

  @jax.jit
  def predict(params, batch):
    return model.apply(params, batch['z'], batch['ei'], batch['em'],
                       batch['nm'])

  shuffle_rng = np.random.default_rng(1)   # advances across epochs

  def batches(data, shuffle):
    z, ei, em, nm, y = data
    order = (shuffle_rng.permutation(len(y)) if shuffle
             else np.arange(len(y)))
    for i in range(0, len(y) - args.batch_size + 1, args.batch_size):
      sel = order[i:i + args.batch_size]
      yield dict(z=jnp.asarray(z[sel]), ei=jnp.asarray(ei[sel]),
                 em=jnp.asarray(em[sel]), nm=jnp.asarray(nm[sel]),
                 y=jnp.asarray(y[sel]))

  losses = []
  for _ in range(args.epochs):
    for b in batches(tr, shuffle=True):
      params, opt_state, loss = step(params, opt_state, b)
      losses.append(loss)

  scores, labels = [], []
  for b in batches(te, shuffle=False):
    scores.append(np.asarray(predict(params, b)))
    labels.append(np.asarray(b['y']))
  s = np.concatenate(scores)
  lab = np.concatenate(labels)
  order = np.argsort(s, kind='stable')
  ranks = np.empty_like(order, np.float64)
  ranks[order] = np.arange(1, len(s) + 1)
  n_pos = int((lab > 0.5).sum())
  n_neg = len(lab) - n_pos
  auc = (ranks[lab > 0.5].sum() - n_pos * (n_pos + 1) / 2) / \
      max(n_pos * n_neg, 1)

  print(json.dumps({
      'model': 'SEAL-DGCNN', 'num_nodes': n,
      'links_per_split': args.num_links, 'epochs': args.epochs,
      'extract_s': round(extract_s, 1),
      'first_loss': round(float(losses[0]), 4),
      'final_loss': round(float(losses[-1]), 4),
      'test_auc': round(float(auc), 4),
  }), flush=True)


if __name__ == '__main__':
  main()

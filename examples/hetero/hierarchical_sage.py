"""Hierarchical heterogeneous GraphSAGE (trim-per-layer) on MAG-shaped data.

Counterpart of /root/reference/examples/hetero/hierarchical_sage.py: its
HierarchicalHeteroGraphSage trims x/edge_index per layer with PyG's
trim_to_layer using num_sampled_nodes/edges. The TPU analog uses STATIC
typed prefixes instead of dynamic trims: hetero tree-mode batches lay
nodes/edges out in positional hop blocks, so
``sampler.hetero_tree_layout`` gives per-type hop offsets and the RGNN's
hierarchical forward slices fixed prefixes — one compile, no dynamic
shapes. Trains both the full and hierarchical forward and reports both
step timings plus the (identical) convergence.

Run: python examples/hetero/hierarchical_sage.py --epochs 2
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

import graphlearn_tpu as glt
from graphlearn_tpu.models import RGNN
from train_hgt_mag import AFFIL, CITES, TOPIC, WRITES, make_mag_like, rev


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=2)
  ap.add_argument('--n-paper', type=int, default=60_000)
  ap.add_argument('--batch-size', type=int, default=512)
  ap.add_argument('--hidden', type=int, default=64)
  ap.add_argument('--lr', type=float, default=3e-3)
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  import optax
  glt.utils.enable_compilation_cache()
  rng = np.random.default_rng(0)
  ncls = 8
  n_author, n_inst, n_field = args.n_paper // 2, 200, 500
  cites, writes, affil, topic, feats, label = make_mag_like(
      args.n_paper, n_author, n_inst, n_field, ncls, rng)

  edges = {CITES: cites, WRITES: writes, AFFIL: affil, TOPIC: topic,
           rev(WRITES): writes[::-1].copy(),
           rev(AFFIL): affil[::-1].copy(),
           rev(TOPIC): topic[::-1].copy()}
  nnodes = {'paper': args.n_paper, 'author': n_author,
            'institution': n_inst, 'field_of_study': n_field}
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph(edges, graph_mode='HBM',
                num_nodes={et: nnodes[et[0]] for et in edges})
  ds.init_node_features(feats)
  ds.init_node_labels({'paper': label})

  fan = {et: [8, 4] for et in edges}
  n_tr = int(args.n_paper * 0.2)

  def make_loader():
    # fresh loader per variant: the shuffle RNG is stateful, so sharing
    # one loader would feed the two variants different batch sequences
    # and invalidate the convergence comparison
    return glt.loader.NeighborLoader(
        ds, fan, ('paper', np.arange(n_tr)), batch_size=args.batch_size,
        shuffle=True, drop_last=True, seed=0, dedup='tree')

  model_etypes = tuple(rev(et) for et in edges)
  no, eo = glt.sampler.hetero_tree_layout(
      {'paper': args.batch_size}, tuple(edges), fan)
  variants = {
      'full': RGNN(etypes=model_etypes, hidden_dim=args.hidden,
                   out_dim=ncls, num_layers=2, out_ntype='paper'),
      'hierarchical': RGNN(etypes=model_etypes, hidden_dim=args.hidden,
                           out_dim=ncls, num_layers=2, out_ntype='paper',
                           hop_node_offsets=no, hop_edge_offsets=eo),
  }

  def bdict(batch):
    return dict(x=batch.x, ei=batch.edge_index, em=batch.edge_mask,
                y=batch.y['paper'],
                num_seed=batch.num_sampled_nodes['paper'][0])

  report = {'model': 'hierarchical-hetero-SAGE', 'n_paper': args.n_paper}
  for name, model in variants.items():
    loader = make_loader()
    first = bdict(next(iter(loader)))
    params = model.init(jax.random.PRNGKey(0), first['x'], first['ei'],
                        first['em'])
    tx = optax.adam(args.lr)
    opt_state = tx.init(params)

    def loss_fn(params, b, model=model):
      logits = model.apply(params, b['x'], b['ei'], b['em'])
      n = logits.shape[0]          # hierarchical emits a seed-side prefix
      y = b['y'][:n]
      seed_mask = jnp.arange(n) < b['num_seed']
      ce = optax.softmax_cross_entropy(logits, jax.nn.one_hot(y, ncls))
      loss = jnp.where(seed_mask, ce, 0.0).sum() / jnp.maximum(
          seed_mask.sum(), 1)
      acc = (((logits.argmax(-1) == y) & seed_mask).sum() /
             jnp.maximum(seed_mask.sum(), 1))
      return loss, acc

    @jax.jit
    def step(params, opt_state, b, loss_fn=loss_fn, tx=tx):
      (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
      updates, opt_state = tx.update(g, opt_state, params)
      return optax.apply_updates(params, updates), opt_state, loss, acc

    # compile outside the timed region
    params, opt_state, _, _ = step(params, opt_state, first)
    jax.block_until_ready(params)

    losses = []
    accs = []
    epoch_times = []
    for _ in range(args.epochs):
      t0 = time.perf_counter()
      for batch in loader:
        params, opt_state, loss, acc = step(params, opt_state,
                                            bdict(batch))
        losses.append(loss)
        accs.append(acc)
      jax.block_until_ready(losses[-1])
      epoch_times.append(time.perf_counter() - t0)
    # keep device handles; everything is fetched once, after the loop
    report[name] = {
        'first_loss': losses[0], 'final_loss': losses[-1],
        'final_acc': accs[-1],
        # dispatch wall only — device truth needs a trace (PERF.md)
        'epoch_time_s_dispatch': round(float(np.mean(epoch_times)), 3),
    }

  # the only host fetches in the program
  for name in variants:
    for k in ('first_loss', 'final_loss', 'final_acc'):
      report[name][k] = round(float(report[name][k]), 4)
  print(json.dumps(report), flush=True)


if __name__ == '__main__':
  main()

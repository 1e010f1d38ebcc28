"""Unsupervised GraphSAGE via link prediction.

Counterpart of /root/reference/examples/graph_sage_unsup_ppi.py: a
LinkNeighborLoader draws positive edges + binary negatives per batch, the
model embeds the sampled subgraph, and the loss is sigmoid BCE on
dot-product scores of the edge_label_index pairs. PPI isn't downloadable
here (zero egress), so the graph is a synthetic community graph — link
prediction on it is learnable exactly when the embeddings capture the
communities.

Training runs through ``glt.loader.ScanTrainer``: the epoch is a scanned
program over the link loader — a step gathers its 512 seed edges by
position in the epoch's keyed order, draws and tests its negatives, unions
the seeds, expands and trains, all inside one chunk, and the negative
sampler's ``link.negatives.*`` / ``link.seeds.unique`` counters come out
once an epoch (docs/observability.md). ``--per-batch`` keeps the loop GLT
users write: iterate the loader and step ``make_link_train_step`` batch by
batch (two dispatches a batch).

Run: python examples/graph_sage_unsup.py --epochs 2 [--per-batch]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import graphlearn_tpu as glt
from graphlearn_tpu.models import GraphSAGE, train as train_lib
from graphlearn_tpu.sampler import NegativeSampling


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=2)
  ap.add_argument('--num-nodes', type=int, default=50_000)
  ap.add_argument('--avg-deg', type=int, default=12)
  ap.add_argument('--batch-size', type=int, default=512)
  ap.add_argument('--hidden', type=int, default=128)
  ap.add_argument('--lr', type=float, default=3e-3)
  ap.add_argument('--chunk-size', type=int, default=16)
  ap.add_argument('--per-batch', action='store_true',
                  help='iterate the loader batch by batch instead of '
                       'scanning the epoch')
  args = ap.parse_args()

  import jax
  glt.utils.enable_compilation_cache()
  rng = np.random.default_rng(0)

  # community graph: 32 communities, 90% intra edges
  n, ncom = args.num_nodes, 32
  comm = rng.integers(0, ncom, n).astype(np.int32)
  order = np.argsort(comm, kind='stable').astype(np.int32)
  counts = np.bincount(comm, minlength=ncom)
  offsets = np.zeros(ncom + 1, np.int64)
  np.cumsum(counts, out=offsets[1:])
  e = n * args.avg_deg
  rows = rng.integers(0, n, e).astype(np.int32)
  intra = rng.random(e) < 0.9
  cols = np.empty(e, np.int32)
  rc = comm[rows[intra]]
  u = rng.random(intra.sum())
  cols[intra] = order[offsets[rc] + (u * counts[rc]).astype(np.int64)]
  cols[~intra] = rng.integers(0, n, (~intra).sum())
  # features carry a weak community signal (pure noise would leave the
  # encoder nothing to hang the link structure on)
  feat = (comm[:, None] == np.arange(64) % ncom).astype(np.float32) + \
      0.5 * rng.standard_normal((n, 64)).astype(np.float32)

  # hold 10% of edges out of BOTH the graph and the training supervision
  # so the reported link accuracy is on genuinely unseen pairs. Split on
  # CANONICAL UNDIRECTED pairs — a directed-only dedup would leave a
  # held-out edge's reverse twin (v, u) in the training graph, leaking
  # structure into the test metric — then re-emit BOTH directions of the
  # retained pairs (a lo->hi-only graph would be a DAG where high-id
  # nodes have no out-neighbors to sample).
  lo = np.minimum(rows, cols).astype(np.int64)
  hi = np.maximum(rows, cols).astype(np.int64)
  uniq = np.unique(lo * n + hi)
  rows = (uniq // n).astype(np.int32)
  cols = (uniq % n).astype(np.int32)
  e = rows.shape[0]
  perm = rng.permutation(e)
  tr_idx, te_idx = perm[: int(e * 0.9)], perm[int(e * 0.9):]
  g_rows = np.concatenate([rows[tr_idx], cols[tr_idx]])
  g_cols = np.concatenate([cols[tr_idx], rows[tr_idx]])

  ds = glt.data.Dataset()
  ds.init_graph(np.stack([g_rows, g_cols]), num_nodes=n, graph_mode='HBM')
  ds.init_node_features(feat)

  loader = glt.loader.LinkNeighborLoader(
      ds, [10, 5], np.stack([g_rows, g_cols]),
      neg_sampling=NegativeSampling('binary', 1),
      batch_size=args.batch_size, shuffle=True, drop_last=True, seed=0)
  # drop_last truncates < one batch of the holdout (noted, not padded)
  test_loader = glt.loader.LinkNeighborLoader(
      ds, [10, 5], np.stack([rows[te_idx], cols[te_idx]]),
      neg_sampling=NegativeSampling('binary', 1),
      batch_size=min(args.batch_size, len(te_idx)), shuffle=False,
      drop_last=True, seed=1)

  model = GraphSAGE(hidden_dim=args.hidden, out_dim=args.hidden,
                    num_layers=2)
  first = train_lib.link_batch_to_dict(next(iter(loader)))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first, lr=args.lr)
  train_step, eval_step = train_lib.make_link_train_step(model, tx)

  losses, accs, epoch_times = [], [], []
  # a link job is asked for no num_classes: the trainer reads the step
  # contract (seed pairs, negatives, the pair loss) off the loader's kind
  trainer = None if args.per_batch else glt.loader.ScanTrainer(
      loader, model, tx, chunk_size=args.chunk_size)
  for epoch in range(args.epochs):
    t0 = time.perf_counter()
    if trainer is not None:
      state, loss_e, acc_e = trainer.run_epoch(state)
      losses.extend(loss_e)     # device arrays: fetched once, at the end
      accs.extend(acc_e)
    else:
      for batch in loader:
        state, loss, acc = train_step(state,
                                      train_lib.link_batch_to_dict(batch))
        losses.append(loss)
        accs.append(acc)
    jax.block_until_ready(state)
    epoch_times.append(time.perf_counter() - t0)

  test_accs = [eval_step(state, train_lib.link_batch_to_dict(b))
               for b in test_loader]
  jax.block_until_ready(test_accs)

  print(json.dumps({
      'trainer': 'per-batch loop' if trainer is None else 'ScanTrainer',
      'negatives': glt.utils.trace.counters('link.'),
      'first_loss': round(float(losses[0]), 4),
      'final_loss': round(float(losses[-1]), 4),
      'final_train_link_acc': round(float(accs[-1]), 4),
      'test_link_acc': round(float(np.mean([float(a)
                                            for a in test_accs])), 4),
      'epoch_time_s': round(float(np.mean(epoch_times)), 3),
  }), flush=True)


if __name__ == '__main__':
  main()

"""Heterogeneous RGNN training (IGBH-shaped).

Counterpart of /root/reference/examples/igbh/train_rgnn.py: the IGBH
citation graph (paper/author/institute/fos node types) with a typed RGNN
classifying papers. IGBH isn't downloadable here (zero egress), so an
IGBH-shaped synthetic is generated: papers carry community labels, cites
edges are homophilous, authorship is random — classification requires
aggregating over the typed neighborhood.

The epoch runs as a program: exact dedup under calibrated typed caps, the
dense k-run typed aggregation, and ``ScanTrainer`` over the typed
``NeighborLoader`` (the typed sampler and the per-type collate are traced
into the scanned chunk).

Run: python examples/igbh/train_rgnn.py --epochs 2
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

import graphlearn_tpu as glt
from graphlearn_tpu.models import RGNN, train as train_lib

CITES = ('paper', 'cites', 'paper')
WRITES = ('author', 'writes', 'paper')
REV_WRITES = ('paper', 'rev_writes', 'author')


def make_igbh_like(n_paper, n_author, ncls, rng):
  comm = rng.integers(0, ncls, n_paper).astype(np.int32)
  order = np.argsort(comm, kind='stable').astype(np.int32)
  counts = np.bincount(comm, minlength=ncls)
  offsets = np.zeros(ncls + 1, np.int64)
  np.cumsum(counts, out=offsets[1:])
  # cites: 85% intra-community
  e = n_paper * 12
  rows = rng.integers(0, n_paper, e).astype(np.int32)
  intra = rng.random(e) < 0.85
  cols = np.empty(e, np.int32)
  rc = comm[rows[intra]]
  u = rng.random(intra.sum())
  cols[intra] = order[offsets[rc] + (u * counts[rc]).astype(np.int64)]
  cols[~intra] = rng.integers(0, n_paper, (~intra).sum())
  cites = np.stack([rows, cols])
  # writes: each author writes ~3 papers of one community
  ac = rng.integers(0, ncls, n_author).astype(np.int32)
  wa = np.repeat(np.arange(n_author, dtype=np.int32), 3)
  u = rng.random(wa.shape[0])
  wp = order[offsets[ac[wa]] + (u * counts[ac[wa]]).astype(np.int64)]
  writes = np.stack([wa, wp])
  feats = {
      'paper': rng.standard_normal((n_paper, 64)).astype(np.float32),
      'author': rng.standard_normal((n_author, 64)).astype(np.float32),
  }
  return cites, writes, feats, comm.astype(np.int64)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=2)
  ap.add_argument('--n-paper', type=int, default=100_000)
  ap.add_argument('--n-author', type=int, default=50_000)
  ap.add_argument('--batch-size', type=int, default=512)
  ap.add_argument('--hidden', type=int, default=128)
  ap.add_argument('--lr', type=float, default=3e-3)
  ap.add_argument('--model', default='rsage',
                  choices=['rsage', 'rgat'],
                  help="conv family (reference default is 'rgat' with "
                       '4 heads; rsage is the faster gate)')
  args = ap.parse_args()

  import jax
  glt.utils.enable_compilation_cache()
  rng = np.random.default_rng(0)
  ncls = 16
  cites, writes, feats, label = make_igbh_like(
      args.n_paper, args.n_author, ncls, rng)

  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph(
      {CITES: cites, WRITES: writes,
       REV_WRITES: writes[::-1].copy()},
      graph_mode='HBM',
      num_nodes={CITES: args.n_paper, WRITES: args.n_author,
                 REV_WRITES: args.n_paper})
  ds.init_node_features(feats)
  ds.init_node_labels({'paper': label})

  fanouts = {CITES: [10, 5], WRITES: [5, 3], REV_WRITES: [3, 2]}
  # small smoke runs: fewer train seeds than one batch would yield zero
  # batches under drop_last (and n_paper < 10 would yield zero seeds)
  n_tr = max(1, int(args.n_paper * 0.1))
  args.batch_size = min(args.batch_size, n_tr)
  train_idx = np.arange(n_tr)
  # the normal path for this job: exact dedup under CALIBRATED typed caps
  # (per (hop, edge type) new-node caps probed on this seed pool), the
  # dense k-run typed aggregation over that plan, and the epoch as a
  # program — ScanTrainer traces the typed sampler and the per-type
  # collate into its scanned chunk
  caps = glt.sampler.estimate_hetero_frontier_caps(
      ds.graph, fanouts, {'paper': args.batch_size},
      input_nodes={'paper': train_idx}, seed=0)
  loader = glt.loader.NeighborLoader(
      ds, fanouts, ('paper', train_idx),
      batch_size=args.batch_size, shuffle=True, drop_last=True, seed=0,
      dedup='merge', frontier_caps=caps)

  # --model rgat matches the reference default (4 heads, per-head dim =
  # hidden // heads)
  recs, no, eo = glt.sampler.hetero_tree_blocks(
      {'paper': args.batch_size}, tuple(fanouts), fanouts,
      etype_caps=caps)
  etypes = [glt.typing.reverse_edge_type(CITES),
            glt.typing.reverse_edge_type(WRITES),
            glt.typing.reverse_edge_type(REV_WRITES)]
  model = RGNN(etypes=tuple(etypes), hidden_dim=args.hidden,
               out_dim=ncls, num_layers=2, out_ntype='paper',
               conv=('gat' if args.model == 'rgat' else 'sage'),
               heads=(4 if args.model == 'rgat' else 1),
               hop_node_offsets=no, hop_edge_offsets=eo,
               merge_dense=True, tree_records=recs)

  # template batch from a throwaway loader, so the training loader's key
  # stream starts at the first trained batch
  template = train_lib.batch_to_dict(next(iter(glt.loader.NeighborLoader(
      ds, fanouts, ('paper', train_idx), batch_size=args.batch_size,
      seed=0, dedup='merge', frontier_caps=caps))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template, lr=args.lr)
  trainer = glt.loader.ScanTrainer(loader, model, tx, ncls, chunk_size=8)

  losses, accs, epoch_times = [], [], []
  for epoch in range(args.epochs):
    t0 = time.perf_counter()
    state, loss_e, acc_e = trainer.run_epoch(state)
    jax.block_until_ready(loss_e)
    epoch_times.append(time.perf_counter() - t0)
    losses.append(np.asarray(loss_e))
    accs.append(np.asarray(acc_e))
  losses, accs = np.concatenate(losses), np.concatenate(accs)

  print(json.dumps({
      'first_loss': round(float(losses[0]), 4),
      'final_loss': round(float(losses[-1]), 4),
      'final_train_acc': round(float(accs[-1]), 4),
      'epoch_time_s': round(float(np.mean(epoch_times)), 3),
  }), flush=True)


if __name__ == '__main__':
  main()

"""GraphSAGE at a features-exceed-HBM scale (papers100M-shaped).

Counterpart of /root/reference/examples/multi_gpu/train_sage_ogbn_papers100m.py:
the defining property of papers100M is that node features do NOT fit one
accelerator's memory, so the feature store must split hot rows in HBM from
cold rows in host RAM and ship only the misses. This example builds a
synthetic at a scale where the feature table exceeds the HBM budget you
give it (default: 10M nodes x 128 f32 = 5 GB against a 2 GB hot split),
trains with the degree-ordered hot split (sort_by_in_degree, so the hot
prefix catches most lookups), and reports the measured hit rate alongside
convergence.

NOTE: every mixed (hot+cold) lookup reads ids on host — a device->host
sync per batch — so the step walls printed here include that sync; THIS
per-batch path has not been measured on the chip. What the chip has
measured is the same job through the scanned epoch, where the misses are
planned and staged chunk by chunk with no sync in the loop
(``storage.TieredScanTrainer``; benchmark cell
``sage-papers-tiered.tiered-scan-exact``: a third of the papers100M shape,
an 18.95 GB table with 15 % of its rows hot on one v5e chip, 7,487.5 seeds/s
at a hit share of 85.25 % — the builder's chip runs of PR 41, PERF.md
sections 4-5). The design point being
demonstrated here is capability + hit-rate-proportional transfer, verified
by tests/test_feature.py::test_unified_tensor_ships_only_cold_rows.

Run: python examples/train_sage_papers_scale.py --steps 8
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


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--num-nodes', type=int, default=10_000_000)
  ap.add_argument('--avg-deg', type=int, default=8)
  ap.add_argument('--feat-dim', type=int, default=128)
  ap.add_argument('--hot-gb', type=float, default=2.0,
                  help='HBM budget for the hot feature prefix')
  ap.add_argument('--steps', type=int, default=8)
  ap.add_argument('--batch-size', type=int, default=256)
  ap.add_argument('--fanout', type=int, nargs='+', default=[5, 5])
  ap.add_argument('--spill-dir', default=None,
                  help='THREE-tier mode (docs/storage.md): spill the '
                       'cold tail to memory-mapped chunk files here and '
                       'run the scanned epoch over a TieredFeature with '
                       'chunk-boundary prefetch (TieredScanTrainer)')
  ap.add_argument('--warm-gb', type=float, default=1.0,
                  help='host-RAM budget for the warm tier (three-tier '
                       'mode only)')
  ap.add_argument('--chunk-size', type=int, default=8,
                  help='scan chunk K (three-tier mode only)')
  args = ap.parse_args()
  if args.spill_dir is not None:
    return main_tiered(args)

  import jax
  glt.utils.enable_compilation_cache()
  rng = np.random.default_rng(0)
  n, f = args.num_nodes, args.feat_dim
  ncls = 16

  t0 = time.time()
  e = n * args.avg_deg
  rows = rng.integers(0, n, e).astype(np.int32)
  # zipf head so the degree reorder concentrates lookups in the hot prefix
  cols = (rng.zipf(1.3, e) % n).astype(np.int32)
  feat = rng.standard_normal((n, f)).astype(np.float32)
  feat_gb = feat.nbytes / (1 << 30)
  split = min(1.0, args.hot_gb / feat_gb)
  print(f'# features {feat_gb:.1f} GB vs hot budget {args.hot_gb} GB '
        f'-> split_ratio {split:.3f}; built in {time.time()-t0:.1f}s',
        flush=True)
  assert split < 1.0, 'pick --num-nodes/--hot-gb so features exceed HBM'

  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='HBM')
  # graph-correlated labels (learnable from 1-hop aggregation): each
  # node's label is one of its out-neighbors' id class. Computed on the
  # HOST from the COO arrays already in hand — fetching the device CSR
  # here would be a huge D2H transfer.
  order = np.argsort(rows, kind='stable')
  uniq, first_pos = np.unique(rows[order], return_index=True)
  first_nbr = np.arange(n)                      # deg-0 nodes: self class
  first_nbr[uniq] = cols[order[first_pos]]
  label = (first_nbr % ncls).astype(np.int64)
  ds.init_node_features(feat, sort_func=glt.data.sort_by_in_degree,
                        split_ratio=split)
  ds.init_node_labels(label)

  # uniform-random seeds reach cold-tail nodes, so batches genuinely mix
  # hot HBM rows with host-spilled rows
  loader = glt.loader.NeighborLoader(
      ds, args.fanout, rng.integers(0, n, n // 100),
      batch_size=args.batch_size, shuffle=True, drop_last=True, seed=0,
      dedup='tree', strategy='block')
  no, eo = train_lib.tree_hop_offsets(args.batch_size, args.fanout)
  model = GraphSAGE(hidden_dim=64, out_dim=ncls,
                    num_layers=len(args.fanout), hop_node_offsets=no,
                    hop_edge_offsets=eo)
  it = iter(loader)
  first = train_lib.batch_to_dict(next(it))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  train_step, _ = train_lib.make_train_step(model, tx, ncls)
  # warmup/compile OUTSIDE the timed region
  state, loss0, _ = train_step(state, first)
  jax.block_until_ready(state)

  hot = int(n * split)
  id2idx = ds.node_features.id2index
  losses, node_sets = [], []
  t0 = time.perf_counter()
  for i, batch in enumerate(it):
    if i >= args.steps:
      break
    state, loss, acc = train_step(state, train_lib.batch_to_dict(batch))
    losses.append(loss)
    node_sets.append(batch.node)   # device handles; fetched after timing
  jax.block_until_ready(state)
  dt = time.perf_counter() - t0
  # hit accounting after the clock stops (PERF.md: no host fetch in the
  # hot region). Only REAL lookups count: padded -1 slots are excluded —
  # the store clamps them to storage row 0 (always hot), so including
  # them would inflate the rate with traffic that costs nothing.
  hits = total = 0
  for nd in node_sets:
    ids = np.asarray(nd)
    ids = ids[ids >= 0]
    hits += int((id2idx[ids] < hot).sum())
    total += ids.size

  print(json.dumps({
      'num_nodes': n, 'feat_gb': round(feat_gb, 2),
      'split_ratio': round(split, 3),
      'hot_hit_rate': round(hits / max(total, 1), 3),
      'steps': len(losses),
      'first_loss': round(float(loss0), 4),
      'final_loss': round(float(losses[-1]), 4),
      'secs_per_step_wall': round(dt / max(len(losses), 1), 3),
      'timing': 'wall, host id fetch per batch included',
  }), flush=True)


def main_tiered(args):
  """Three-tier mode: features span HBM -> host RAM -> disk, and the
  epoch runs as a TieredScanTrainer scanned program — the prologue
  plans the epoch's exact disk miss set and the staging worker feeds
  each chunk ahead of the device (docs/storage.md)."""
  import jax

  from graphlearn_tpu.storage import TieredFeature, TieredScanTrainer
  glt.utils.enable_compilation_cache()
  rng = np.random.default_rng(0)
  n, f = args.num_nodes, args.feat_dim
  ncls = 16
  t0 = time.time()
  e = n * args.avg_deg
  rows = rng.integers(0, n, e).astype(np.int32)
  cols = (rng.zipf(1.3, e) % n).astype(np.int32)
  feat = rng.standard_normal((n, f)).astype(np.float32)
  feat_gb = feat.nbytes / (1 << 30)
  row_gb = f * 4 / (1 << 30)
  hot = min(n, int(args.hot_gb / row_gb))
  warm = min(n - hot, int(args.warm_gb / row_gb))
  assert hot + warm < n, ('pick --num-nodes/--hot-gb/--warm-gb so the '
                          'disk tier is non-empty')
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='HBM')
  order = np.argsort(rows, kind='stable')
  uniq, first_pos = np.unique(rows[order], return_index=True)
  first_nbr = np.arange(n)
  first_nbr[uniq] = cols[order[first_pos]]
  label = (first_nbr % ncls).astype(np.int64)
  topo = glt.data.Topology(np.stack([rows, cols]), layout='CSR',
                           num_nodes=n)
  reordered, id2idx = glt.data.sort_by_in_degree(feat, hot / n, topo)
  del feat
  ds.node_features = TieredFeature(reordered, hot_rows=hot,
                                   warm_rows=warm, id2index=id2idx,
                                   spill_dir=args.spill_dir)
  del reordered
  ds.init_node_labels(label)
  occ = ds.node_features.tier_occupancy()
  print(f'# features {feat_gb:.1f} GB -> tiers hot={occ["hot"]} '
        f'warm={occ["warm"]} disk={occ["disk"]} rows; built in '
        f'{time.time()-t0:.1f}s', flush=True)

  loader = glt.loader.NeighborLoader(
      ds, args.fanout, rng.integers(0, n, n // 100),
      batch_size=args.batch_size, shuffle=True, drop_last=True, seed=0,
      dedup='tree')
  model = GraphSAGE(hidden_dim=64, out_dim=ncls,
                    num_layers=len(args.fanout))
  # template batch for model init: one reactive tiered batch (a second
  # all-RAM store just for shapes would defeat the point at this scale)
  first = train_lib.batch_to_dict(next(iter(loader)))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  trainer = TieredScanTrainer(loader, model, tx, ncls,
                              chunk_size=args.chunk_size)
  t0 = time.perf_counter()
  state, losses, _ = trainer.run_epoch(state, max_steps=args.steps)
  jax.block_until_ready(losses)
  dt = time.perf_counter() - t0
  from graphlearn_tpu import metrics
  c = metrics.default_registry().counters()
  staged = c.get('storage.staged_rows', 0)
  missed = c.get('storage.prefetch_miss', 0)
  print(json.dumps({
      'num_nodes': n, 'feat_gb': round(feat_gb, 2),
      'tiers': occ, 'steps': int(np.asarray(losses).shape[0]),
      'final_loss': round(float(np.asarray(losses)[-1]), 4),
      'epoch_wall_s': round(dt, 3),
      'staged_rows': int(staged), 'prefetch_miss': int(missed),
      'prefetch_hit_rate': round(staged / max(staged + missed, 1), 4),
      'plan': trainer.last_plan.stats(),
      'timing': 'wall',
  }), flush=True)
  trainer.close()


if __name__ == '__main__':
  main()

"""Distributed supervised GraphSAGE over the graph-partition mesh.

Counterpart of
/root/reference/examples/distributed/dist_train_sage_supervised.py: there,
N ranks each own a partition, sample via RPC, and train under DDP. Here
ONE SPMD program per step samples P per-shard batches (DistNeighborLoader)
and a data-parallel train step runs on the same mesh — gradients sync with
jax.lax.pmean over the 'g' axis instead of DDP allreduce.

Runs on any mesh: real TPU slice, or the virtual CPU mesh for a laptop
smoke test (--cpu-devices 8). Multi-host pods: call
glt.distributed.init_multihost first (see tests/test_multihost.py).

Run: python examples/distributed/dist_train_sage_supervised.py \
       --cpu-devices 4 --num-nodes 20000 --epochs 2
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=2)
  ap.add_argument('--num-nodes', type=int, default=20_000)
  ap.add_argument('--avg-deg', type=int, default=12)
  ap.add_argument('--batch-size', type=int, default=128)
  ap.add_argument('--fanout', type=int, nargs='+', default=[10, 5])
  ap.add_argument('--hidden', type=int, default=128)
  ap.add_argument('--lr', type=float, default=3e-3)
  ap.add_argument('--num-partitions', type=int, default=None)
  ap.add_argument('--cpu-devices', type=int, default=0,
                  help='force a virtual CPU mesh of this size')
  args = ap.parse_args()

  import jax
  if args.cpu_devices:
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', args.cpu_devices)
  import jax.numpy as jnp
  import optax
  import graphlearn_tpu as glt
  from graphlearn_tpu.models import GraphSAGE
  from graphlearn_tpu.typing import GraphPartitionData

  ctx = glt.distributed.init_worker_group(
      num_partitions=args.num_partitions)
  P = ctx.num_partitions
  mesh = ctx.mesh
  rng = np.random.default_rng(0)
  n, ncls = args.num_nodes, 16

  # community graph (label = community; homophilous edges)
  comm = rng.integers(0, ncls, n).astype(np.int32)
  order = np.argsort(comm, kind='stable').astype(np.int32)
  counts = np.bincount(comm, minlength=ncls)
  offsets = np.zeros(ncls + 1, np.int64)
  np.cumsum(counts, out=offsets[1:])
  e = n * args.avg_deg
  rows = rng.integers(0, n, e).astype(np.int32)
  intra = rng.random(e) < 0.85
  cols = np.empty(e, np.int32)
  rc = comm[rows[intra]]
  u = rng.random(intra.sum())
  cols[intra] = order[offsets[rc] + (u * counts[rc]).astype(np.int64)]
  cols[~intra] = rng.integers(0, n, (~intra).sum())
  feat = rng.standard_normal((n, 64)).astype(np.float32)

  # partition by node id hash; build the sharded dataset
  node_pb = (np.arange(n) % P).astype(np.int32)
  epb = node_pb[rows]
  parts, feats = [], []
  for p in range(P):
    m = epb == p
    parts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]),
        eids=np.arange(e)[m]))
    ids = np.nonzero(node_pb == p)[0]
    feats.append((ids.astype(np.int64), feat[ids]))
  dg = glt.distributed.DistGraph(P, 0, parts, node_pb)
  df = glt.distributed.DistFeature(P, feats, node_pb, mesh)
  ds = glt.distributed.DistDataset(P, 0, dg, df,
                                   node_labels=comm.astype(np.int64))

  loader = glt.distributed.DistNeighborLoader(
      ds, list(args.fanout), np.arange(n), batch_size=args.batch_size,
      shuffle=True, drop_last=True, seed=0, mesh=mesh, dedup='tree')

  # the sharded engine emits the SAME positional tree layout as the
  # local sampler, so each shard's forward can use the layered +
  # dense-tree aggregation (no gathers/segment scatters — PERF.md)
  from graphlearn_tpu.models import train as train_lib
  no, eo = train_lib.tree_hop_offsets(args.batch_size, args.fanout)
  model = GraphSAGE(hidden_dim=args.hidden, out_dim=ncls,
                    num_layers=len(args.fanout), hop_node_offsets=no,
                    hop_edge_offsets=eo, tree_dense=True,
                    fanouts=tuple(args.fanout))
  first = next(iter(loader))
  params = model.init(jax.random.PRNGKey(0),
                      np.asarray(first.x)[0], np.asarray(first.edge_index)[0],
                      np.asarray(first.edge_mask)[0])
  tx = optax.adam(args.lr)
  opt_state = tx.init(params)

  from graphlearn_tpu.utils.compat import shard_map
  from jax.sharding import PartitionSpec as PS

  def loss_fn(params, x, ei, em, y, nseed):
    logits = model.apply(params, x, ei, em)
    n = min(logits.shape[0], y.shape[0])   # layered seed-side prefix
    logits, y = logits[:n], y[:n]
    seed_mask = jnp.arange(n) < nseed
    ce = optax.softmax_cross_entropy(logits, jax.nn.one_hot(y, ncls))
    loss = jnp.where(seed_mask, ce, 0.0).sum() / jnp.maximum(
        seed_mask.sum(), 1)
    acc = (((logits.argmax(-1) == y) & seed_mask).sum() /
           jnp.maximum(seed_mask.sum(), 1))
    return loss, acc

  def dp_step(params, opt_state, x, ei, em, y, nseed):
    # per-shard grads -> pmean over the partition axis (the DDP allreduce)
    (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, x[0], ei[0], em[0], y[0], nseed[0])
    grads = jax.lax.pmean(grads, 'g')
    loss = jax.lax.pmean(loss, 'g')
    acc = jax.lax.pmean(acc, 'g')
    updates, opt_state = tx.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss, acc

  step = jax.jit(shard_map(
      dp_step, mesh=mesh,
      in_specs=(PS(), PS(), PS('g'), PS('g'), PS('g'), PS('g'), PS('g')),
      out_specs=(PS(), PS(), PS(), PS()),
      check_vma=False))

  # in-process CPU collectives can deadlock when several multi-device
  # programs are in flight (docs/get_started/dist_train.md "Testing
  # without hardware") — serialize steps on the CPU mesh. TPU
  # collectives need no barrier: the per-step mesh loop ran unserialized
  # on the four-chip v5e host (PERF.md "Bring-up (PR 21)")
  serialize = jax.default_backend() == 'cpu'
  losses, accs, epoch_times = [], [], []
  for epoch in range(args.epochs):
    t0 = time.perf_counter()
    for batch in loader:
      nseed = batch.num_sampled_nodes[:, 0]
      params, opt_state, loss, acc = step(
          params, opt_state, batch.x, batch.edge_index, batch.edge_mask,
          batch.y, nseed)
      losses.append(loss)
      accs.append(acc)
      if serialize:
        jax.block_until_ready(loss)
    jax.block_until_ready(params)
    epoch_times.append(time.perf_counter() - t0)

  print(json.dumps({
      'mesh_size': P,
      'first_loss': round(float(losses[0]), 4),
      'final_loss': round(float(losses[-1]), 4),
      'final_train_acc': round(float(accs[-1]), 4),
      'epoch_time_s': round(float(np.mean(epoch_times)), 3),
  }), flush=True)


if __name__ == '__main__':
  main()

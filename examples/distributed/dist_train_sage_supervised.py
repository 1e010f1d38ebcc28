"""Distributed supervised GraphSAGE over the graph-partition mesh.

Counterpart of
/root/reference/examples/distributed/dist_train_sage_supervised.py: there,
N ranks each own a partition, sample via RPC, and train under DDP. Here
ONE SPMD program per step samples P per-shard batches (DistNeighborLoader)
and a data-parallel train step runs on the same mesh — gradients sync with
jax.lax.pmean over the 'g' axis instead of DDP allreduce.

The shards are built where they live: every partition's CSR, rows and
labels are made one partition at a time and placed on that partition's
device (``DistDataset.from_device_shards``), the 5 % hottest rows by
in-degree are replicated on every device, and sampling is exact under caps
probed through the mesh sampler (``estimate_dist_frontier_caps``) — the
deployment ``perfbench`` measures as ``sage-papers.mesh-exact``.

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
  from jax.sharding import NamedSharding, PartitionSpec as PS
  from graphlearn_tpu.models import GraphSAGE

  ctx = glt.distributed.init_worker_group(
      num_partitions=args.num_partitions)
  P = ctx.num_partitions
  mesh = ctx.mesh
  n, ncls, fdim = args.num_nodes, 16, 64
  e_per = n * args.avg_deg // P            # edges a partition draws

  # community graph (label = community; homophilous edges). The books
  # are N-sized host arrays; everything of size E or N x F is made one
  # PARTITION at a time and put straight on that partition's device —
  # the way a graph too large for one host's memory has to be loaded
  # (DistDataset.from_device_shards). Node -> partition is id % P.
  rng = np.random.default_rng(0)
  comm = rng.integers(0, ncls, n).astype(np.int32)
  order = np.argsort(comm, kind='stable').astype(np.int32)
  counts = np.bincount(comm, minlength=ncls)
  offsets = np.zeros(ncls + 1, np.int64)
  np.cumsum(counts, out=offsets[1:])
  node_pb = (np.arange(n) % P).astype(np.int32)
  n_max = -(-n // P)
  shard = NamedSharding(mesh, PS('g'))
  devices = list(mesh.devices.flat)
  int_max = np.iinfo(np.int32).max

  def place(per_shard, fill, dtype):
    """[P, width, ...] on the mesh from P host pieces, one upload each."""
    width = max(a.shape[0] for a in per_shard)
    tail = per_shard[0].shape[1:]
    pieces = []
    for a, d in zip(per_shard, devices):
      full = np.full((1, width) + tail, fill, dtype)
      full[0, :a.shape[0]] = a
      pieces.append(jax.device_put(full, d))
    return jax.make_array_from_single_device_arrays(
        (P, width) + tail, shard, pieces)

  row_ids, indptr, indices, rows_f, labels = [], [], [], [], []
  in_degree = np.zeros(n, np.int64)
  for p in range(P):
    prng = np.random.default_rng(1000 + p)
    own = np.arange(p, n, P, dtype=np.int32)
    src = np.sort(prng.integers(0, own.size, e_per))     # local rows
    intra = prng.random(e_per) < 0.85
    rc = comm[own[src]]
    cols = np.where(
        intra, order[offsets[rc] + (prng.random(e_per) * counts[rc]
                                    ).astype(np.int64)],
        prng.integers(0, n, e_per)).astype(np.int32)
    in_degree += np.bincount(cols, minlength=n)
    ptr = np.zeros(n_max + 1, np.int32)
    ptr[1:own.size + 1] = np.cumsum(np.bincount(src, minlength=own.size))
    ptr[own.size + 1:] = ptr[own.size]
    row_ids.append(own)
    indptr.append(ptr)
    indices.append(cols)
    rows_f.append(prng.standard_normal((own.size, fdim)).astype(np.float32))
    labels.append(comm[own])
  feat_ids = place(row_ids, int_max, np.int32)
  ds = glt.distributed.DistDataset.from_device_shards(
      mesh, node_pb,
      dict(row_ids=feat_ids, indptr=place(indptr, 0, np.int32),
           indices=place(indices, -1, np.int32)),
      dict(feat_ids=feat_ids, feats=place(rows_f, 0, np.float32)),
      labels=place(labels, 0, np.int32),
      split_ratio=0.05, hotness=in_degree)     # 5 % hottest rows cached
  del indices, rows_f

  # exact dedup under caps probed through the mesh sampler itself: every
  # per-shard buffer shrinks from the worst case to what this graph needs
  train_idx = np.arange(n)
  caps = glt.sampler.estimate_dist_frontier_caps(
      ds.graph, mesh, list(args.fanout), args.batch_size,
      input_nodes=train_idx, num_probes=5, slack=1.5, seed=0)
  loader = glt.distributed.DistNeighborLoader(
      ds, list(args.fanout), train_idx, batch_size=args.batch_size,
      shuffle=True, drop_last=True, seed=0, mesh=mesh, dedup='merge',
      frontier_caps=caps, seed_labels_only=True)

  # the layered forward over the merge layout: each hop's new nodes and
  # edges are contiguous blocks under the caps, aggregated as k-runs
  from graphlearn_tpu.models import train as train_lib
  no, eo = train_lib.merge_hop_offsets(args.batch_size, args.fanout, None,
                                       caps)
  model = GraphSAGE(hidden_dim=args.hidden, out_dim=ncls,
                    num_layers=len(args.fanout), hop_node_offsets=no,
                    hop_edge_offsets=eo, merge_dense=True,
                    fanouts=tuple(args.fanout))
  first = next(iter(loader))
  params = model.init(jax.random.PRNGKey(0),
                      np.asarray(first.x)[0], np.asarray(first.edge_index)[0],
                      np.asarray(first.edge_mask)[0])
  tx = optax.adam(args.lr)
  opt_state = tx.init(params)

  from graphlearn_tpu.utils.compat import shard_map

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
  if loader.check_overflow():
    raise RuntimeError('a batch overflowed the calibrated caps')

  print(json.dumps({
      'mesh_size': P,
      'first_loss': round(float(losses[0]), 4),
      'final_loss': round(float(losses[-1]), 4),
      'final_train_acc': round(float(accs[-1]), 4),
      'frontier_caps': caps,
      # a host wall on whatever backend ran this (the CPU mesh in a smoke
      # test): not a device number — PERF.md has those
      'epoch_wall_s': round(float(np.mean(epoch_times)), 3),
  }), flush=True)


if __name__ == '__main__':
  main()

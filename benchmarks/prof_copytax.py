"""A/B trace of the dense convs' flat-layout forks (VERDICT r4 item 8 +
ISSUE 13c).

PERF.md's byte audit attributes ~3.8 ms copy + ~3.7 ms reshape per
step to XLA materialization between aggregation stages; the prime
suspect is the [f*k, F] -> [f, k, F] run view (k = 15/10/5 is never
tile-aligned, so the 3D view relayouts). models.RUN_MEAN_IMPL toggles
the kernel: 'reshape' (status quo) vs 'window' (flat-layout
lax.reduce_window, no 3D view). This script traces the bench train
step under BOTH impls and prints the per-op-class tables + program
ms, so one run on the chip decides which lands as default — bench.py
now runs the same pair every round and auto-records the winner as
``run_mean_impl_decision``.

``--softmax-ab`` additionally A/Bs models.RUN_SOFTMAX_IMPL (the dense
GAT convs' f32 [f, k, H] softmax chain — ISSUE 13's further
flat-layout rewrite) on a tree_dense GAT train step: same per-op-class
tables, same decision discipline.

Only the slice-fed tree convs consult models.RUN_MEAN_IMPL: the merge
(``exact``) convs gather their children k-major since PR 31 (PERF.md
section 6), so ``--variant exact`` traces the same program twice.

Run on TPU: python benchmarks/prof_copytax.py [--variant tree|exact]
                                              [--softmax-ab]
"""
import argparse
import shutil

import numpy as np


def _gat_softmax_ab(args):
  """Trace a tree_dense GAT train step under both RUN_SOFTMAX_IMPL
  settings (separate jit caches per impl: the flag is read at trace
  time, so each leg builds its model fns fresh)."""
  import jax
  import graphlearn_tpu as glt
  from graphlearn_tpu.models import models as M
  from graphlearn_tpu.models import train as train_lib
  import bench

  graph = bench.build_graph()
  rng = np.random.default_rng(3)
  feat = rng.standard_normal((bench.NUM_NODES, bench.E2E_FEAT_DIM),
                             dtype=np.float32)
  ds = glt.data.Dataset(graph=graph)
  ds.init_node_features(feat)
  ds.init_node_labels(rng.integers(0, bench.E2E_CLASSES,
                                   bench.NUM_NODES))
  train_idx = rng.integers(0, bench.NUM_NODES,
                           bench.BATCH * (args.iters + 6))
  for impl in ('reshape', 'window'):
    M.RUN_SOFTMAX_IMPL = impl
    loader = glt.loader.NeighborLoader(
        ds, bench.FANOUT, train_idx, batch_size=bench.BATCH,
        shuffle=True, drop_last=True, seed=0, dedup='tree',
        strategy='block', seed_labels_only=True)
    no, eo = train_lib.tree_hop_offsets(bench.BATCH, bench.FANOUT)
    import jax.numpy as jnp
    model = glt.models.GAT(hidden_dim=128, out_dim=bench.E2E_CLASSES,
                           num_layers=len(bench.FANOUT), heads=2,
                           hop_node_offsets=no, hop_edge_offsets=eo,
                           dtype=jnp.bfloat16, tree_dense=True,
                           fanouts=tuple(bench.FANOUT))
    it = iter(loader)
    first = train_lib.batch_to_dict(next(it))
    state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                             first)
    step, _ = train_lib.make_train_step(model, tx, bench.E2E_CLASSES)

    def run_step():
      nonlocal state
      state, loss, _ = step(state, train_lib.batch_to_dict(next(it)))
      return loss

    state, loss, _ = step(state, first)   # compile
    td = f'/tmp/glt_prof_copytax_gat_{impl}'
    shutil.rmtree(td, ignore_errors=True)
    tot, tr = bench._traced_step_ms(jax, run_step, td, 'jit_train_step')
    print(f'\n=== gat tree_dense / RUN_SOFTMAX_IMPL={impl}: '
          f'full {tot} ms, train program {tr} ms ===')
    for n, (ms, cnt) in glt.utils.device_op_ms(td, top=14,
                                               steps=args.iters).items():
      print(f'  {n[:56]:58s} {ms:8.3f} ms x{cnt}')
  M.RUN_SOFTMAX_IMPL = 'reshape'


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--variant', default='tree', choices=['exact', 'tree'])
  ap.add_argument('--iters', type=int, default=10)
  ap.add_argument('--softmax-ab', action='store_true',
                  help='also A/B models.RUN_SOFTMAX_IMPL on a '
                       'tree_dense GAT step (ISSUE 13c)')
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  import graphlearn_tpu as glt
  from graphlearn_tpu.models import models as M
  import bench
  glt.utils.enable_compilation_cache()
  bench.E2E_ITERS = args.iters

  graph = bench.build_graph()
  rng = np.random.default_rng(2)
  feat = rng.standard_normal((bench.NUM_NODES, bench.E2E_FEAT_DIM),
                             dtype=np.float32)
  labels = rng.integers(0, bench.E2E_CLASSES, bench.NUM_NODES)
  ds = glt.data.Dataset(graph=graph)
  ds.init_node_features(feat)
  ds.init_node_labels(labels)
  train_idx = rng.integers(0, bench.NUM_NODES,
                           bench.BATCH * (args.iters + 6))
  cal_caps = None
  if args.variant == 'exact':
    cal_caps = glt.sampler.estimate_frontier_caps(
        graph, bench.FANOUT, bench.BATCH, num_probes=5, slack=1.5)

  for impl in ('reshape', 'window'):
    M.RUN_MEAN_IMPL = impl
    td = f'/tmp/glt_prof_copytax_{args.variant}_{impl}'
    shutil.rmtree(td, ignore_errors=True)
    tot, tr = bench._run_e2e(ds, train_idx, jnp.bfloat16, jax, td,
                             variant=args.variant, cal_caps=cal_caps)
    print(f'\n=== {args.variant} / RUN_MEAN_IMPL={impl}: '
          f'full {tot} ms, train program {tr} ms ===')
    for n, (ms, cnt) in glt.utils.device_op_ms(td, top=14,
                                               steps=args.iters).items():
      print(f'  {n[:56]:58s} {ms:8.3f} ms x{cnt}')

  if args.softmax_ab:
    _gat_softmax_ab(args)


if __name__ == '__main__':
  import os
  import sys
  sys.path.insert(0, os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))))
  main()

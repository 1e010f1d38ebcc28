"""GraphSAGE on ogbn-products — the reference's MVP training gate.

Counterpart of /root/reference/examples/train_sage_ogbn_products.py
(3-layer SAGE, hidden 256, fanout [15,10,5], batch 1024, reported test
accuracy ~0.787 +- 0.004, line 16). Differences from the reference are
TPU-shaped, not semantic:

- the whole per-batch path (multi-hop sample -> feature/label gather ->
  SAGE fwd/bwd) is jitted device programs; the host only feeds seed ids;
- metrics accumulate on device and are fetched once at the end (the first
  device->host transfer would serialize dispatch — PERF.md);
- with no network egress in this environment, `--data-dir` loads a
  pre-staged copy of the real dataset (npz layout below); otherwise a
  products-scale synthetic with planted community structure is generated
  so convergence + epoch time are still demonstrated end to end. Labels
  are the community; features are a WEAK noisy label signal (a linear
  probe on raw features alone plateaus far below the graph-aware model),
  so good accuracy requires actual neighborhood aggregation.

Staged real-dataset layout (--data-dir): a single `ogbn_products.npz`
with edge_index [2, E] (directed, both directions present), feat [N, 100]
float32, label [N] int64, train_idx/valid_idx/test_idx int64 arrays.

Run: python examples/train_sage_ogbn_products.py --epochs 3
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


def load_staged(data_dir):
  path = os.path.join(data_dir, 'ogbn_products.npz')
  if not os.path.exists(path):
    return None
  z = np.load(path)
  return (z['edge_index'], z['feat'], z['label'],
          z['train_idx'], z['valid_idx'], z['test_idx'], int(z['label'].max()) + 1)


# ogbn-products published summary stats the degree model is fitted to.
# N comes from the reference itself
# (/root/reference/examples/pai/ogbn_products/data_preprocess.py:30);
# the edge count and max degree are the standard public OGB figures
# (61,859,140 undirected edges -> mean degree ~50.5; max degree 17,481).
# This environment has no network egress, so the real histogram cannot
# be fetched — the fit below targets these summary statistics (mean +
# max + N), the strongest offline-verifiable match available.
PRODUCTS_N = 2_449_029
PRODUCTS_MEAN_DEG = 50.5
PRODUCTS_MAX_DEG = 17_481


def fit_powerlaw_alpha(mean_deg, dmax):
  """Exponent of a truncated discrete power law P(d) ~ d^-alpha on
  [1, dmax] whose mean is ``mean_deg`` (bisection; the products fit
  alpha(50.5, 17481) ~= 1.68)."""
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


def powerlaw_degree_weights(num_nodes, avg_deg, rng):
  """Per-node popularity weights whose induced in-degree distribution is
  the products power-law fit, rescaled to this graph's size.

  The fit: alpha solves mean == PRODUCTS_MEAN_DEG at the published
  cutoff; the cutoff then scales with this graph's edge share so the
  tail keeps the same SHAPE at reduced N (a 17k-degree hub cannot exist
  in a 25M-edge graph).
  """
  e = num_nodes * avg_deg
  e_products = PRODUCTS_N * PRODUCTS_MEAN_DEG
  dmax = max(64, int(PRODUCTS_MAX_DEG * e / e_products))
  alpha = fit_powerlaw_alpha(PRODUCTS_MEAN_DEG, PRODUCTS_MAX_DEG)
  d = np.arange(1, dmax + 1, dtype=np.float64)
  pmf = d ** -alpha
  pmf /= pmf.sum()
  target = rng.choice(d, size=num_nodes, p=pmf)
  return target / target.sum(), alpha, dmax


def draw_class_targets(rows_comm, comm, w, p_intra, rng):
  """Power-law-weighted edge targets over ``comm``'s population,
  ``p_intra`` of them within the source's class: nodes sorted by class,
  one searchsorted over the class-ordered cumulative weights serves
  both the weighted-global and the weighted-within-class draws. Shared
  by this gate and the hetero gate (examples/igbh/train_rgnn_gate.py) —
  both gates' claimed 'same dedup/calibration properties' rest on this
  ONE generator."""
  n = comm.shape[0]
  num_classes = int(comm.max()) + 1
  order = np.argsort(comm, kind='stable').astype(np.int32)
  cw = np.cumsum(w[order])
  counts = np.bincount(comm, minlength=num_classes)
  offsets = np.zeros(num_classes + 1, np.int64)
  np.cumsum(counts, out=offsets[1:])
  bounds = np.concatenate([[0.0], cw])[offsets]     # [C+1] cum bounds
  base, total_c = bounds[:-1], np.diff(bounds)

  e = rows_comm.shape[0]
  intra = rng.random(e) < p_intra
  cols = np.empty(e, np.int32)
  rc = rows_comm[intra]
  u = rng.random(intra.sum())
  pos = np.searchsorted(cw, base[rc] + u * total_c[rc], side='right')
  cols[intra] = order[np.minimum(pos, n - 1)]
  u2 = rng.random((~intra).sum())
  pos2 = np.searchsorted(cw, u2 * cw[-1], side='right')
  cols[~intra] = order[np.minimum(pos2, n - 1)]
  return cols


def make_synthetic(num_nodes, avg_deg, num_classes, feat_dim, p_intra,
                   feat_snr, rng):
  """Products-matched community graph: learnable but not feature-trivial.

  Nodes get a community (= label). Edges: `p_intra` of endpoints stay in
  the source's community (homophily ~products' category structure), the
  rest are global. Edge TARGETS follow the products power-law degree fit
  (powerlaw_degree_weights) in both the intra- and global draws, so the
  in-degree distribution is heavy-tailed like the real graph — the
  property that drives dedup overlap, calibration tightness and padded
  truncation, which a uniform-degree synthetic would flatter.
  Features: community center * feat_snr + unit noise.
  """
  comm = rng.integers(0, num_classes, num_nodes).astype(np.int32)
  w, alpha, dmax = powerlaw_degree_weights(num_nodes, avg_deg, rng)
  e = num_nodes * avg_deg
  rows = rng.integers(0, num_nodes, e).astype(np.int32)
  cols = draw_class_targets(comm[rows], comm, w, p_intra, rng)

  # show the match: realized in-degree stats vs the fitted model
  indeg = np.bincount(cols, minlength=num_nodes)
  q = np.percentile(indeg, [50, 90, 99])
  print(f'# degree model: products power-law fit alpha={alpha:.3f} '
        f'(targets mean={PRODUCTS_MEAN_DEG} max={PRODUCTS_MAX_DEG} at '
        f'N={PRODUCTS_N}); this graph: scaled dmax={dmax}, realized '
        f'in-degree mean={indeg.mean():.1f} p50={q[0]:.0f} '
        f'p90={q[1]:.0f} p99={q[2]:.0f} max={indeg.max()}', flush=True)

  centers = rng.standard_normal((num_classes, feat_dim)).astype(np.float32)
  feat = centers[comm] * feat_snr + \
      rng.standard_normal((num_nodes, feat_dim)).astype(np.float32)

  # products-like split sizes: ~8% train / 2% valid / rest test
  perm = rng.permutation(num_nodes)
  n_tr, n_va = int(num_nodes * 0.08), int(num_nodes * 0.02)
  return (np.stack([rows, cols]), feat, comm.astype(np.int64),
          perm[:n_tr], perm[n_tr:n_tr + n_va], perm[n_tr + n_va:],
          num_classes)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--data-dir', default=os.environ.get('OGBN_DATA', ''))
  ap.add_argument('--epochs', type=int, default=3)
  ap.add_argument('--batch-size', type=int, default=1024)
  ap.add_argument('--fanout', type=int, nargs='+', default=[15, 10, 5])
  ap.add_argument('--hidden', type=int, default=256)
  ap.add_argument('--lr', type=float, default=3e-3)
  ap.add_argument('--num-nodes', type=int, default=2_449_029)
  ap.add_argument('--avg-deg', type=int, default=25)
  ap.add_argument('--feat-snr', type=float, default=0.1)
  ap.add_argument('--p-intra', type=float, default=0.58)
  ap.add_argument('--eval-batches', type=int, default=200,
                  help='cap on test batches (full test split is 90%% of '
                       'the graph; the reference evaluates it all, cap '
                       'keeps driver runs bounded; 0 = all)')
  ap.add_argument('--eval-epochs', default='',
                  help='comma-separated intermediate epochs to ALSO '
                       'evaluate at (one run reports several budgets in '
                       'test_acc_at); the final epoch is always '
                       'evaluated')
  ap.add_argument('--seed', type=int, default=0,
                  help='TRAINING-stream seed (loader shuffle/sampling + '
                       'model init). The synthetic graph stays fixed '
                       'across seeds, like re-running the reference on '
                       'the one real dataset — seed variance measures '
                       'the training pipeline, not dataset redraws')
  ap.add_argument('--bf16-features', action='store_true')
  ap.add_argument('--bf16-model', action='store_true',
                  help='bf16 compute in the convs (MXU at 2x f32 rate); '
                       'params/optimizer/loss stay f32')
  ap.add_argument('--dedup', default='tree',
                  choices=['auto', 'map', 'sort', 'merge', 'map_table',
                           'sort_legacy', 'tree'],
                  help="batch construction: 'map' = reference-parity "
                       "exact dedup (merge-sort engine); 'tree' "
                       '(default) = computation-tree batches (PERF.md)')
  ap.add_argument('--padded-window', type=int, default=None,
                  help='dense [N, W] padded adjacency sampling (rows '
                       'with deg > W sample a fixed W-subset; fastest '
                       'hops, disclosed truncation bias — PERF.md)')
  ap.add_argument('--calibrate', action='store_true',
                  help='estimate per-hop frontier caps from a numpy '
                       'probe simulation and run exact dedup with '
                       'calibrated buffers (PERF.md round 3); implies '
                       'the layered merge forward. The loader guards '
                       "overflow (overflow_policy='raise'): finished "
                       'train epochs certify no truncation; the '
                       "capped eval pass's flag is fetched and "
                       'reported explicitly')
  ap.add_argument('--node-budget', type=int, default=None,
                  help='clamp any hop frontier to this many nodes: '
                       'shrinks the padded batch buffers (and so the '
                       'feature gather + model compute) at the cost of '
                       'truncating expansion beyond the budget')
  ap.add_argument('--strategy', default='random',
                  choices=['random', 'block'],
                  help="'block' = cluster sampling over aligned CSR "
                       'blocks, ~1.7x faster hops with exact uniform '
                       'marginals (PERF.md)')
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  glt.utils.enable_compilation_cache()

  staged = load_staged(args.data_dir) if args.data_dir else None
  if staged is not None:
    src = 'ogbn-products (staged)'
    ei, feat, label, train_idx, valid_idx, test_idx, ncls = staged
  else:
    src = f'synthetic products-scale (N={args.num_nodes})'
    t0 = time.time()
    ei, feat, label, train_idx, valid_idx, test_idx, ncls = make_synthetic(
        args.num_nodes, args.avg_deg, 47, 100, args.p_intra, args.feat_snr,
        np.random.default_rng(0))
    print(f'# generated {src} E={ei.shape[1]} in {time.time()-t0:.1f}s',
          flush=True)

  t0 = time.time()
  ds = glt.data.Dataset()
  ds.init_graph(ei, num_nodes=feat.shape[0], graph_mode='HBM')
  ds.init_node_features(
      feat, dtype=(jnp.bfloat16 if args.bf16_features else None))
  ds.init_node_labels(label)
  print(f'# dataset built in {time.time()-t0:.1f}s', flush=True)

  cal_caps = None
  if args.calibrate:
    if args.dedup in ('tree', 'map_table', 'sort_legacy'):
      # calibrated caps are post-dedup sizes — only the merge-engine
      # exact modes consume them (the sampler rejects tree+caps)
      print(f"# --calibrate implies exact dedup; switching --dedup "
            f"{args.dedup} -> map", flush=True)
      args.dedup = 'map'
    t0 = time.time()
    cal_caps = glt.sampler.estimate_frontier_caps(
        ds.graph, args.fanout, args.batch_size, input_nodes=train_idx,
        num_probes=5, slack=1.5)
    print(f'# calibrated frontier caps {cal_caps} in '
          f'{time.time()-t0:.1f}s', flush=True)

  loader = glt.loader.NeighborLoader(
      ds, args.fanout, train_idx, batch_size=args.batch_size, shuffle=True,
      drop_last=True, seed=args.seed, dedup=args.dedup,
      strategy=args.strategy,
      node_budget=args.node_budget, padded_window=args.padded_window,
      frontier_caps=cal_caps)

  depth = len(args.fanout)
  mdtype = jnp.bfloat16 if args.bf16_model else None
  if args.dedup == 'tree':
    # layered forward: each conv only processes the tree depths it
    # needs — 2.4x device speedup on the train step; without a
    # node_budget the dense-tree aggregation (reshape over contiguous
    # child blocks, no gathers/scatters) adds another 2.8x on fwd/bwd
    # (PERF.md). Both are numerically exact.
    no, eo = train_lib.tree_hop_offsets(args.batch_size, args.fanout,
                                        args.node_budget)
    model = GraphSAGE(hidden_dim=args.hidden, out_dim=ncls,
                      num_layers=depth, hop_node_offsets=no,
                      hop_edge_offsets=eo, dtype=mdtype,
                      tree_dense=args.node_budget is None,
                      fanouts=tuple(args.fanout))
  elif args.dedup in ('auto', 'map', 'sort', 'merge'):
    # exact-dedup batches support the same layered trimming via the
    # merge layout (prefix-contiguous hop blocks), and merge_dense
    # replaces segment scatter-adds with k-run reshape-means — both
    # numerically exact (PERF.md round 3)
    no, eo = train_lib.merge_hop_offsets(args.batch_size, args.fanout,
                                         args.node_budget, cal_caps)
    model = GraphSAGE(hidden_dim=args.hidden, out_dim=ncls,
                      num_layers=depth, hop_node_offsets=no,
                      hop_edge_offsets=eo, dtype=mdtype,
                      merge_dense=True, fanouts=tuple(args.fanout))
  else:
    # legacy bisection engines: full (un-layered) forward
    model = GraphSAGE(hidden_dim=args.hidden, out_dim=ncls,
                      num_layers=depth, dtype=mdtype)
  first = train_lib.batch_to_dict(next(iter(loader)))
  state, tx = train_lib.create_train_state(model,
                                           jax.random.PRNGKey(args.seed),
                                           first, lr=args.lr)
  train_step, _ = train_lib.make_train_step(model, tx, ncls)
  eval_counts = train_lib.make_eval_counts(model)

  test_loader = glt.loader.NeighborLoader(
      ds, args.fanout, test_idx, batch_size=args.batch_size, shuffle=False,
      drop_last=False, seed=args.seed + 1, dedup=args.dedup,
      strategy=args.strategy,
      node_budget=args.node_budget, padded_window=args.padded_window,
      frontier_caps=cal_caps)

  def run_eval(params):
    """One capped eval pass; returns device scalars + loader (for the
    post-fetch overflow check — the cap BREAKS the iterator, so the
    automatic epoch-end check never runs for eval)."""
    correct = total = None
    t0 = time.perf_counter()
    for i, batch in enumerate(test_loader):
      if args.eval_batches and i >= args.eval_batches:
        break
      c, t = eval_counts(params, train_lib.batch_to_dict(batch))
      correct = c if correct is None else correct + c
      total = t if total is None else total + t
    return correct, total, time.perf_counter() - t0

  # ---- train: NO host fetch anywhere in this region (PERF.md).
  # --eval-epochs lets one run report several training budgets (the
  # accuracy matrix trains each seed ONCE at the largest budget instead
  # of once per budget); eval results stay on device until the end.
  eval_at = sorted(set(int(x) for x in args.eval_epochs.split(',')
                       if x)) if args.eval_epochs else []
  epoch_times, loss_hist, acc_hist = [], [], []
  evals = {}           # epoch -> (correct, total, secs) device scalars
  for epoch in range(args.epochs):
    t0 = time.perf_counter()
    for batch in loader:
      state, loss, acc = train_step(state, train_lib.batch_to_dict(batch))
      loss_hist.append(loss)
      acc_hist.append(acc)
    jax.block_until_ready(state)
    epoch_times.append(time.perf_counter() - t0)
    if epoch + 1 in eval_at and epoch + 1 < args.epochs:
      evals[epoch + 1] = run_eval(state.params)

  # ---- final eval on the held-out test split (device-accumulated) ----
  evals[args.epochs] = run_eval(state.params)
  jax.block_until_ready([v[0] for v in evals.values()])

  # ---- the only host fetches in the program ----
  test_acc_at = {e: round(float(c) / max(float(t), 1.0), 4)
                 for e, (c, t, _) in sorted(evals.items())}
  test_acc = test_acc_at[args.epochs]
  correct, total, eval_time = evals[args.epochs]
  if cal_caps is not None:
    # train epochs ran the iterator to exhaustion, so the loader's
    # epoch-end raise-guard certifies them; the eval loop BREAKS early
    # (eval_batches cap), so its verdict must be fetched explicitly
    eval_ovf = test_loader.check_overflow()
    print(f'# calibrated caps {cal_caps}: no overflow across '
          f'{args.epochs} train epochs (loader overflow guard); '
          f'eval batches overflow={eval_ovf}'
          + (' — test_acc may be truncation-biased, recalibrate on '
             'test_idx or raise slack' if eval_ovf else ''),
          flush=True)
  steps = len(loader)
  print(json.dumps({
      'source': src, 'epochs': args.epochs, 'steps_per_epoch': steps,
      'epoch_time_s': round(float(np.mean(epoch_times)), 3),
      'epoch_times': [round(t, 3) for t in epoch_times],
      'final_train_loss': round(float(loss_hist[-1]), 4),
      'final_train_acc': round(float(acc_hist[-1]), 4),
      'first_train_loss': round(float(loss_hist[0]), 4),
      'test_acc': test_acc,
      'test_acc_at': test_acc_at,
      'test_seeds_evaluated': int(float(total)),
      'eval_time_s': round(eval_time, 3),
      # epoch walls end in block_until_ready(state): wall time of the
      # whole epoch loop, compile included in the first epoch
      'timing': 'wall',
  }), flush=True)


if __name__ == '__main__':
  main()

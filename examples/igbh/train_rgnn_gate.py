"""Hetero accuracy GATE: IGBH-shaped synthetic RGNN/RGAT/HGT training.

The typed counterpart of examples/train_sage_ogbn_products.py's
discriminative gate (reference anchors: examples/igbh/train_rgnn.py
RGNN defaults, examples/hetero/train_hgt_mag.py HGT training loop).
Real IGBH/MAG are network-blocked in this image, so the gate is a
synthetic whose ACCURACY is sensitive to sampling-mode semantics:

- typed homophily: papers cite same-class papers and authors write
  same-class papers with prob ``--p-intra`` — class signal flows over
  BOTH etypes, so truncating either biases accuracy;
- power-law edge targets WITHIN each type (zipf-weighted, igbh-like
  heavy tail) — the property that drives dedup overlap, calibration
  tightness and padded truncation;
- low feature SNR (``--feat-snr``): features alone plateau far below
  the structural ceiling, and AUTHOR features carry an independent
  slice of the class signal that only 2-hop paper<-author paths
  deliver — a mode that cripples typed expansion loses it.

Modes (--mode): 'segment' = exact-dedup merge batches + per-etype
segment convs; 'tree_dense' = computation-tree batches + dense k-run
typed aggregation (TreeHeteroConv); 'merge_dense' = CALIBRATED
per-(hop,etype) caps + dense k-run aggregation on exact merge batches
(sampler.estimate_hetero_frontier_caps). Convs (--conv): sage / gat
(RGNN) / hgt (HGT) — every conv supports all three modes.

Prints ONE JSON line with test_acc_at per requested budget.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)
import graphlearn_tpu as glt  # noqa: E402

CITES = ('paper', 'cites', 'paper')
WRITES = ('author', 'writes', 'paper')
REV = ('paper', 'rev_writes', 'author')


def _products_gate():
  """The homo gate module — its draw_class_targets is the ONE
  power-law/searchsorted edge generator both gates share."""
  return glt.utils.load_module(
      os.path.join(_REPO, 'examples', 'train_sage_ogbn_products.py'))


def powerlaw_weights(n, rng, alpha=1.68, dmax_frac=0.005):
  """Per-node popularity weights with a zipf-like tail (igbh papers'
  citation in-degree is heavy-tailed; alpha matches the products fit
  used by the homo gate so the two gates stress the same dedup/
  calibration properties)."""
  dmax = max(64, int(n * dmax_frac))
  d = np.arange(1, dmax + 1, dtype=np.float64)
  pmf = d ** -alpha
  pmf /= pmf.sum()
  target = rng.choice(d, size=n, p=pmf)
  return target / target.sum()


def make_synthetic(n_paper, n_author, ncls, feat_dim, p_intra, feat_snr,
                   avg_cites, avg_writes, rng):
  draw_targets = _products_gate().draw_class_targets
  comm_p = rng.integers(0, ncls, n_paper).astype(np.int32)
  comm_a = rng.integers(0, ncls, n_author).astype(np.int32)
  w_p = powerlaw_weights(n_paper, rng)

  e_c = n_paper * avg_cites
  c_rows = rng.integers(0, n_paper, e_c).astype(np.int32)
  c_cols = draw_targets(comm_p[c_rows], comm_p, w_p, p_intra, rng)
  cites = np.stack([c_rows, c_cols])

  e_w = n_author * avg_writes
  w_rows = rng.integers(0, n_author, e_w).astype(np.int32)
  w_cols = draw_targets(comm_a[w_rows], comm_p, w_p, p_intra, rng)
  writes = np.stack([w_rows, w_cols])

  # independent bases: papers carry slice A of the class signal,
  # authors slice B — only typed 2-hop paths recover B for a paper
  cen_p = rng.standard_normal((ncls, feat_dim)).astype(np.float32)
  cen_a = rng.standard_normal((ncls, feat_dim)).astype(np.float32)
  feat_p = cen_p[comm_p] * feat_snr + \
      rng.standard_normal((n_paper, feat_dim)).astype(np.float32)
  feat_a = cen_a[comm_a] * feat_snr + \
      rng.standard_normal((n_author, feat_dim)).astype(np.float32)

  indeg = np.bincount(c_cols, minlength=n_paper)
  q = np.percentile(indeg, [50, 90, 99])
  print(f'# typed gate graph: papers={n_paper} authors={n_author} '
        f'cites={e_c} writes={e_w}; cites in-degree mean='
        f'{indeg.mean():.1f} p50={q[0]:.0f} p90={q[1]:.0f} '
        f'p99={q[2]:.0f} max={indeg.max()}', flush=True)

  perm = rng.permutation(n_paper)
  n_tr, n_va = int(n_paper * 0.3), int(n_paper * 0.1)
  return (cites, writes, feat_p, feat_a, comm_p.astype(np.int64),
          perm[:n_tr], perm[n_tr:n_tr + n_va], perm[n_tr + n_va:])


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=4)
  ap.add_argument('--eval-epochs', default='',
                  help='comma-separated earlier budgets to also eval at')
  ap.add_argument('--batch-size', type=int, default=1024)
  ap.add_argument('--fanout', type=int, nargs='+', default=[15, 10, 5])
  ap.add_argument('--hidden', type=int, default=128)
  ap.add_argument('--heads', type=int, default=4)
  ap.add_argument('--lr', type=float, default=2e-3)
  ap.add_argument('--n-paper', type=int, default=100_000)
  ap.add_argument('--n-author', type=int, default=50_000)
  ap.add_argument('--num-classes', type=int, default=8)
  ap.add_argument('--feat-dim', type=int, default=64)
  ap.add_argument('--feat-snr', type=float, default=0.1)
  ap.add_argument('--p-intra', type=float, default=0.6)
  ap.add_argument('--avg-cites', type=int, default=12)
  ap.add_argument('--avg-writes', type=int, default=6)
  ap.add_argument('--eval-batches', type=int, default=50)
  ap.add_argument('--seed', type=int, default=0)
  ap.add_argument('--conv', default='sage', choices=['sage', 'gat', 'hgt'])
  ap.add_argument('--mode', default='segment',
                  choices=['segment', 'tree_dense', 'merge_dense'])
  ap.add_argument('--bf16-model', action='store_true')
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  import optax
  glt.utils.enable_compilation_cache()

  t0 = time.time()
  (cites, writes, feat_p, feat_a, label_p, train_idx, valid_idx,
   test_idx) = make_synthetic(
      args.n_paper, args.n_author, args.num_classes, args.feat_dim,
      args.p_intra, args.feat_snr, args.avg_cites, args.avg_writes,
      np.random.default_rng(0))   # graph fixed across seeds; PRNG varies
  print(f'# generated in {time.time()-t0:.1f}s', flush=True)

  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph({CITES: cites, WRITES: writes,
                 REV: writes[::-1].copy()},
                graph_mode='HBM',
                num_nodes={CITES: args.n_paper, WRITES: args.n_author,
                           REV: args.n_paper})
  ds.init_node_features({'paper': feat_p, 'author': feat_a})
  ds.init_node_labels({'paper': label_p})
  fan = {et: list(args.fanout) for et in (CITES, WRITES, REV)}
  ncls = args.num_classes
  hb = args.batch_size
  mdtype = jnp.bfloat16 if args.bf16_model else None

  caps = None
  if args.mode == 'merge_dense':
    t0 = time.time()
    caps = glt.sampler.estimate_hetero_frontier_caps(
        ds.graph, fan, {'paper': hb},
        input_nodes={'paper': train_idx}, num_probes=4, slack=1.5)
    print(f'# calibrated hetero caps in {time.time()-t0:.1f}s: '
          f'{ {"/".join(et): v for et, v in caps.items()} }', flush=True)
  dedup = 'tree' if args.mode == 'tree_dense' else 'merge'

  def mk_loader(idx, shuffle, seed, drop_last):
    return glt.loader.NeighborLoader(
        ds, fan, ('paper', idx), batch_size=hb, shuffle=shuffle,
        drop_last=drop_last, seed=seed, dedup=dedup, frontier_caps=caps,
        overflow_policy='warn' if caps else 'raise')

  loader = mk_loader(train_idx, True, args.seed, True)
  test_loader = mk_loader(test_idx, False, args.seed + 1, False)

  recs, no, eo = glt.sampler.hetero_tree_blocks(
      {'paper': hb}, tuple(fan), fan, etype_caps=caps)
  rev_et = tuple(glt.typing.reverse_edge_type(et) for et in fan)
  depth = len(args.fanout)
  if args.conv == 'hgt':
    model = glt.models.HGT(
        ntypes=('paper', 'author'), etypes=rev_et,
        hidden_dim=args.hidden, out_dim=ncls, heads=args.heads,
        num_layers=depth, out_ntype='paper', dtype=mdtype,
        hop_node_offsets=no, hop_edge_offsets=eo,
        tree_records=recs if args.mode != 'segment' else None,
        merge_dense=args.mode == 'merge_dense')
  else:
    model = glt.models.RGNN(
        etypes=rev_et, hidden_dim=args.hidden, out_dim=ncls,
        conv=args.conv, heads=(args.heads if args.conv == 'gat' else 1),
        num_layers=depth, out_ntype='paper', dtype=mdtype,
        hop_node_offsets=no, hop_edge_offsets=eo,
        tree_dense=args.mode == 'tree_dense',
        merge_dense=args.mode == 'merge_dense',
        tree_records=recs if args.mode != 'segment' else None)

  def bdict(b):
    return dict(x=b.x, ei=b.edge_index, em=b.edge_mask,
                y=b.y['paper'], ns=b.num_sampled_nodes['paper'][0])

  first = bdict(next(iter(loader)))
  params = jax.jit(model.init)(jax.random.PRNGKey(args.seed),
                               first['x'], first['ei'], first['em'])
  tx = optax.adam(args.lr)
  opt_state = tx.init(params)

  def loss_fn(p, b):
    logits = model.apply(p, b['x'], b['ei'], b['em']).astype(jnp.float32)
    nl = logits.shape[0]
    sm = jnp.arange(nl) < b['ns']
    ce = optax.softmax_cross_entropy(
        logits, jax.nn.one_hot(b['y'][:nl], ncls))
    return jnp.where(sm, ce, 0.0).sum() / jnp.maximum(sm.sum(), 1)

  @jax.jit
  def train_step(p, o, b):
    loss, g = jax.value_and_grad(loss_fn)(p, b)
    updates, o = tx.update(g, o, p)
    return optax.apply_updates(p, updates), o, loss

  @jax.jit
  def eval_counts(p, b):
    logits = model.apply(p, b['x'], b['ei'], b['em'])
    nl = logits.shape[0]
    sm = jnp.arange(nl) < b['ns']
    ok = (logits.argmax(-1) == b['y'][:nl]) & sm
    return ok.sum(), sm.sum()

  import warnings
  eval_ovf_flags = []   # device scalars / bools; ONE fetch at the end

  def run_eval(p):
    correct = total = None
    # an EXHAUSTED eval pass fires the loader's epoch-end warning and
    # consumes the flag, so capture warnings too; an early break leaves
    # the device flag — bank it before the next __iter__ resets it.
    # Either way truncation in ANY eval pass survives to the verdict.
    with warnings.catch_warnings(record=True) as wl:
      warnings.simplefilter('always')
      for i, batch in enumerate(test_loader):
        if args.eval_batches and i >= args.eval_batches:
          break
        c, t = eval_counts(p, bdict(batch))
        correct = c if correct is None else correct + c
        total = t if total is None else total + t
    if test_loader._ovf_accum is not None:
      eval_ovf_flags.append(test_loader._ovf_accum)
    if any('overflowed' in str(w.message) for w in wl):
      eval_ovf_flags.append(True)
    return correct, total

  eval_at = sorted(set(int(x) for x in args.eval_epochs.split(',')
                       if x)) if args.eval_epochs else []
  # no host fetches in the train region (PERF.md dispatch rules).
  # Train-side overflow surfaces as the loader's epoch-end warning
  # (policy='warn'); the epoch-end check CONSUMES the flag, so count
  # the warnings to report a cross-epoch verdict at the end.
  import warnings
  loss_hist = []
  epoch_times = []
  evals = {}
  train_ovf_epochs = 0
  for epoch in range(args.epochs):
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as wlist:
      warnings.simplefilter('always')
      for batch in loader:
        params, opt_state, loss = train_step(params, opt_state,
                                             bdict(batch))
        loss_hist.append(loss)
    train_ovf_epochs += any('overflowed' in str(w.message)
                            for w in wlist)
    jax.block_until_ready(loss)
    epoch_times.append(time.perf_counter() - t0)
    if epoch + 1 in eval_at and epoch + 1 < args.epochs:
      evals[epoch + 1] = run_eval(params)
  evals[args.epochs] = run_eval(params)
  jax.block_until_ready([v[0] for v in evals.values()])

  test_acc_at = {e: round(float(c) / max(float(t), 1.0), 4)
                 for e, (c, t) in sorted(evals.items())}
  if caps is not None:
    # eval loops BREAK early (eval_batches cap), so their verdicts were
    # banked per pass; train epochs report via counted warnings
    eval_ovf = any(bool(np.asarray(f)) for f in eval_ovf_flags)
    print(f'# calibrated-caps overflow: train_epochs='
          f'{train_ovf_epochs}/{args.epochs} eval={eval_ovf}',
          flush=True)
  print(json.dumps({
      'conv': args.conv, 'mode': args.mode, 'epochs': args.epochs,
      'steps_per_epoch': len(loader),
      'epoch_time_s': round(float(np.mean(epoch_times)), 3),
      'first_train_loss': round(float(loss_hist[0]), 4),
      'final_train_loss': round(float(loss_hist[-1]), 4),
      'test_acc': test_acc_at[args.epochs],
      'test_acc_at': test_acc_at,
      'timing': 'dispatch-wall',
  }), flush=True)


if __name__ == '__main__':
  main()

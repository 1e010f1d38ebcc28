"""What decides ``correct``: the timed path's first steps against the plain
reference, each number beside a limit of its own (``perfbench/limits/``).

Two kinds of number.

Exact ones, limit 0, on the first ``validated_batches`` replayed batches,
against the generator's own host arrays:

``bad_edges``      sampled (neighbour, node) pairs that are no edge of the
                   graph (the neighbour is not in the node's CSR row)
``fanout_misses``  expanded nodes whose number of sampled edges is not
                   ``min(degree, fan-out of their hop)``, plus nodes of the
                   inner hops that were not expanded at all
``dup_nodes``      node ids that appear twice in a batch (exact dedup),
                   plus valid non-seed nodes that no edge brings in
``bad_rows``       feature rows / labels of the batch that are not the
                   table's rows for those node ids
``overflow``       batches whose calibrated caps overflowed (truncated)

Measured ones, over the first chunk the program keeps a state for; a cell's
``limits`` file names those it compares (the others are printed on an
earlier line — PERF.md §2 says which have no upper reading, and why):

``loss_gap_step1`` the relative gap between the first step's loss and the
                   reference's: forward and loss at the timed sizes, before
                   any update — the one number rounding alone decides
``loss_gap``       the widest such gap over the followed steps
``dparam_gap``     by the worst leaf: |‖Δp‖_program − ‖Δp‖_reference| over
                   the reference's norm of that leaf or of the median leaf,
                   whichever is larger, Δp the parameters' change over the
                   followed steps; leaves whose first reference gradient is
                   under a thousandth of the median leaf's are left out
                   (they move under Adam by round-off alone)
``moment_gap``     the same measure on Adam's first moment after the
                   followed steps: the decayed sum of the gradients as the
                   optimizer got them
"""
import numpy as np


def _leaves(tree):
  import jax
  return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def _worst_leaf_gap(prog, ref, keep=None):
  pn = np.array([np.linalg.norm(x) for x in prog])
  rn = np.array([np.linalg.norm(x) for x in ref])
  gap = np.abs(pn - rn) / np.maximum(rn, np.median(rn))
  if keep is not None:
    gap = gap[keep]
  return float(gap.max())


def compare_training(first, params0, ref_losses, ref_g0, ref_params, ref_mu):
  """The measured numbers, from the program's first call (``first``:
  per-step losses and the state kept at step ``first['steps']``) and the
  reference's run over the same steps."""
  n = len(ref_losses)
  lp = np.asarray(first['losses'][:n], np.float64)
  lr = np.asarray(ref_losses, np.float64)
  p0 = _leaves(params0)
  dp_prog = [a - b for a, b in zip(_leaves(first['state'].params), p0)]
  dp_ref = [a - b for a, b in zip(_leaves(ref_params), p0)]
  g0 = np.array([np.linalg.norm(x) for x in _leaves(ref_g0)])
  moved = g0 >= 1e-3 * np.median(g0)
  mu_prog = _leaves(first['state'].opt_state[0].mu)
  rel = np.abs(lp - lr) / np.abs(lr)
  return dict(
      loss_gap_step1=float(rel[0]), loss_gap=float(rel.max()),
      dparam_gap=_worst_leaf_gap(dp_prog, dp_ref, moved),
      moment_gap=_worst_leaf_gap(mu_prog, _leaves(ref_mu)))


def validate_batches(cell, batches, n):
  """The exact numbers over the first ``n`` replayed batches."""
  indptr, indices = cell.indptr, cell.indices
  num_nodes = indptr.shape[0] - 1
  eo = (0,) + tuple(cell.edge_offsets)
  out = dict(bad_edges=0, fanout_misses=0, dup_nodes=0, bad_rows=0,
             overflow=int(sum(bool(np.any(b['overflow'])) for b in batches)))
  for b in batches[:n]:
    node = np.asarray(b['node']).astype(np.int64)
    nsn = np.asarray(b['num_sampled_nodes']).astype(np.int64)
    valid = int(nsn.sum())
    ids = node[:valid]
    out['dup_nodes'] += int(valid - np.unique(ids).size) + int(
        (ids < 0).sum() + (ids >= num_nodes).sum())
    ei, em = np.asarray(b['edge_index']), np.asarray(b['edge_mask'])
    brought = np.zeros(valid, bool)
    brought[:int(nsn[0])] = True
    expanded = 0
    for h, k in enumerate(cell.fanout):
      m = em[eo[h]:eo[h + 1]]
      src = ei[0, eo[h]:eo[h + 1]][m].astype(np.int64)
      tgt = ei[1, eo[h]:eo[h + 1]][m].astype(np.int64)
      inside = (src >= 0) & (src < valid) & (tgt >= 0) & (tgt < valid)
      out['bad_edges'] += int((~inside).sum())
      src, tgt = src[inside], tgt[inside]
      brought[src] = True
      # hop h expands exactly the nodes hop h-1 added (the seeds at h=0)
      lo, hi = expanded, expanded + int(nsn[h])
      expanded = hi
      out['bad_edges'] += int(((tgt < lo) | (tgt >= hi)).sum())
      front = ids[lo:hi]
      deg = indptr[front + 1] - indptr[front]
      got = np.bincount(tgt - lo, minlength=hi - lo)[:hi - lo]
      out['fanout_misses'] += int((got != np.minimum(deg, k)).sum())
      # membership: sort the expanded nodes' own CSR rows once as
      # (node, neighbour) keys, then look every sampled pair up
      seg = np.repeat(indptr[front], deg) + (
          np.arange(int(deg.sum())) - np.repeat(np.cumsum(deg) - deg, deg))
      keys = np.sort(np.repeat(front, deg) * num_nodes + indices[seg])
      want = ids[tgt] * num_nodes + ids[src]
      pos = np.minimum(np.searchsorted(keys, want), max(keys.size - 1, 0))
      found = keys[pos] == want if keys.size else np.zeros(want.size, bool)
      out['bad_edges'] += int((~found).sum())
    out['dup_nodes'] += int((~brought).sum())
    out['bad_rows'] += int(
        (np.asarray(b['x'])[:valid] != cell.feat[ids]).any(1).sum())
    y = np.asarray(b['y'])
    nlab = min(y.shape[0], valid)
    out['bad_rows'] += int((y[:nlab] != cell.label[ids[:nlab]]).sum())
  return out


def verdict(numbers, limits):
  """(correct, {name: {'value', 'limit'}}) over the numbers the limits
  name; a limit with no number is a fault of the benchmark and raises."""
  missing = set(limits) - set(numbers)
  if missing:
    raise RuntimeError(f'check: limits for {sorted(missing)} but no such '
                       f'number among {sorted(numbers)}')
  table = {k: {'value': numbers[k], 'limit': limits[k]} for k in limits}
  ok = all(np.isfinite(v['value']) and v['value'] <= v['limit']
           for v in table.values())
  return bool(ok), table

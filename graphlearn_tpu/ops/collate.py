"""Fused batch collation: mask/edge_index/feature/label gathers in ONE
jitted dispatch.

The reference collates on the host driver (loader/node_loader.py:85-113
gathers features via UnifiedTensor then builds PyG Data). Here collation
must be a single device program for a different reason: every eager op
on a sampler output is one more program launch the host pays per batch,
so the loader does not touch the sampler's outputs eagerly. All arrays enter as arguments (never closures),
and optional stores are trace-time ``None`` branches.
"""
import functools

import jax
import jax.numpy as jnp

from ..metrics.registry_names import SCOPE_COLLATE, collate_scope


@functools.partial(jax.jit, static_argnames=('label_cap',))
@jax.named_scope(SCOPE_COLLATE)
def collate_batch(node, num_nodes, row, col, feats, id2index, labels,
                  edge_feats, edge, label_cap=None):
  """Build the derived batch payloads on device.

  Args:
    node: [cap_n] global ids (FILL=-1 padded).
    num_nodes: scalar valid count.
    row / col: [cap_e] relabeled endpoints (or None).
    feats: [N, F] device feature table (or None).
    id2index: [N] hotness-reorder map applied before the gather (or None).
    labels: [N] device label table (or None).
    edge_feats: [E, F_e] device edge-feature table (or None).
    edge: [cap_e] global edge ids (needed when edge_feats given).
    label_cap: static; gather labels only for the first ``label_cap``
      node slots (the seed block leads the buffer, and supervision uses
      seed slots only — a full-buffer label gather is a per-element
      random access over the whole node capacity, ~5 ms/batch at
      products scale). None = full buffer (reference-parity y shape).

  Returns dict with node_mask, edge_index (or None), x, y, edge_attr —
  padded slots gather row/label 0 (masked downstream by node_mask).
  """
  out = {}
  out['node_mask'] = jnp.arange(node.shape[0]) < num_nodes
  out['edge_index'] = (jnp.stack([row, col]) if row is not None else None)
  safe = jnp.maximum(node, 0)
  if feats is not None:
    fidx = id2index[safe] if id2index is not None else safe
    out['x'] = feats[fidx]
  else:
    out['x'] = None
  lsafe = safe if label_cap is None else safe[:label_cap]
  out['y'] = labels[lsafe] if labels is not None else None
  if edge_feats is not None and edge is not None:
    out['edge_attr'] = edge_feats[jnp.maximum(edge, 0)]
  else:
    out['edge_attr'] = None
  return out


@jax.jit
def valid_mask(node, num_nodes):
  """arange(len(node)) < num_nodes, as a jitted dispatch."""
  return jnp.arange(node.shape[0]) < num_nodes


@jax.jit
def stack2(a, b):
  """Jitted 2-row stack (edge_index assembly without an eager op)."""
  return jnp.stack([a, b])


@jax.jit
def stack2_batched(a, b):
  """[P, E] x 2 -> [P, 2, E] (sharded edge_index assembly)."""
  return jnp.stack([a, b], axis=1)


@jax.jit
def gather_rows(table, id2index, ids):
  """Single fused gather with padding clamp (hetero per-type collate)."""
  safe = jnp.maximum(ids, 0)
  if id2index is not None:
    safe = id2index[safe]
  return table[safe]


def collate_typed_batch(node, row, col, feats, id2index, labels,
                        input_type, label_cap=None, id_types=()):
  """The typed counterpart of :func:`collate_batch`, as a traced body
  (the scanned chunk's; not jitted on its own): per node type the row
  gather from that type's device table under ``glt.collate/<ntype>``,
  the seed type's labels and the per-edge-type ``edge_index`` under
  ``glt.collate``. The same clamped gathers as the per-batch typed
  loader's (:func:`gather_rows`), so the batches are the same bits.

  ``node`` / ``row`` / ``col``: the typed sampler's dicts; ``feats`` /
  ``id2index``: ``{ntype: table}`` for the types that carry rows
  (``id2index[t]`` may be None); ``labels``: the seed type's table;
  ``id_types``: the node types whose rows are learned tables
  (``models/tables.py``), for which ``x`` carries the node ids themselves
  (pads -1) and the model gathers. Returns ``(x, edge_index, y)``."""
  x = {}
  for t, table in feats.items():
    with jax.named_scope(collate_scope(t)):
      x[t] = gather_rows(table, id2index[t], node[t])
  for t in id_types:
    x[t] = node[t]
  with jax.named_scope(SCOPE_COLLATE):
    ids = node[input_type]
    y = gather_rows(labels, None,
                    ids if label_cap is None else ids[:label_cap])
    edge_index = {et: jnp.stack([r, col[et]]) for et, r in row.items()}
  return x, edge_index, y

"""Learned node tables: rows for the node types of a typed graph that have
no feature rows (OGB-MAG's author / institution / field_of_study, a
recommender's user and item ids).

A table is a PARAMETER, not an input: ``NodeTables`` (a submodule of
``models.RGNN``, named :data:`NODE_TABLES`) declares one ``[rows, width]``
float32 leaf per such type in the train state's ``params``, initialised
from the init key like every other weight, and gathers a batch's rows from
it by the node ids the collate emits where it would emit rows (pads -1).
The gradient comes back by the gather's transpose — a scatter-add of row
gradients into a table-shaped zero — and the optimizer steps over the
table as over every other leaf: with optax's dense Adam every row's
moments decay on every step, so a row read once keeps moving by its
momentum after the step that read it (the OGB baselines' dense
``torch.optim.Adam``; a lazy update of the read rows alone is a different
result). ``docs/capacity_plans.md`` says who takes a loader with tables.

Pads add nothing anywhere: the forward reads no row for them (a zero row
comes back) and the backward drops them (no clamp lands their zero
gradient on row 0, and no negative index wraps onto row ``rows - 1``).
"""
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..metrics.registry_names import SCOPE_GATHER, SCOPE_SCATTER, SCOPE_TABLE

#: the submodule name, and so the params key, every table leaf sits under
NODE_TABLES = 'node_tables'


def _slots(ids, num_rows):
  """The gather / scatter index of every slot: the id, or ``num_rows`` (one
  past the table: out of bounds, so filled on read and dropped on write)
  for a pad."""
  return jnp.where(ids >= 0, ids, num_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather(num_rows, table, ids):
  with jax.named_scope(SCOPE_GATHER):
    return table.at[_slots(ids, num_rows)].get(mode='fill', fill_value=0)


def _gather_fwd(num_rows, table, ids):
  return _gather(num_rows, table, ids), ids


def _gather_bwd(num_rows, ids, g):
  # the transpose, written out so that it runs under a name of its own
  # (glt.train/fwd_bwd/.../table/scatter): one scatter-add of the row
  # gradients into a zero table, the pads' slots dropped
  with jax.named_scope(SCOPE_SCATTER):
    dt = jnp.zeros((num_rows, g.shape[-1]), g.dtype)
    return dt.at[_slots(ids, num_rows)].add(g, mode='drop'), None


_gather.defvjp(_gather_fwd, _gather_bwd)


def table_gather(table, ids):
  """``table[ids]`` with a zero row for every pad (``ids < 0``); its
  gradient is a scatter-add of the row gradients into a ``table``-shaped
  zero that no pad touches."""
  return _gather(table.shape[0], jnp.asarray(table), ids)


class NodeTables(nn.Module):
  """The learned tables of a typed model: ``shapes`` ``((ntype, rows,
  width), ...)``. Called on the batch's ``x_dict``, it replaces each table
  type's node ids by that table's rows and passes every other type
  through."""
  shapes: Any

  @nn.compact
  def __call__(self, x_dict):
    out = dict(x_dict)
    with jax.named_scope(SCOPE_TABLE):
      for t, rows, width in self.shapes:
        if t not in x_dict:
          continue
        # the OGB baselines' init (torch xavier_uniform_ on [rows, width])
        table = self.param(t, nn.initializers.glorot_uniform(),
                           (rows, width), jnp.float32)
        out[t] = table_gather(table, x_dict[t])
    return out


def table_shapes(node_tables):
  """``{ntype: (rows, width)}`` -> the sorted ``((ntype, rows, width),
  ...)`` a ``NodeTables`` takes."""
  return tuple((t, int(r), int(w))
               for t, (r, w) in sorted(dict(node_tables).items()))


def is_table_path(path) -> bool:
  """Whether a pytree path (``jax.tree_util`` keys) leads into the learned
  tables: a params leaf under :data:`NODE_TABLES`, or an optimizer-state
  leaf shaped after one."""
  return any(getattr(k, 'key', None) == NODE_TABLES for k in path)


def has_tables(tree) -> bool:
  return any(is_table_path(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0])

"""Training utilities: jitted supervised train/eval steps.

The reference leaves the training loop to user code
(/root/reference/examples/train_sage_ogbn_products.py:120-150: DDP +
cross-entropy on the seed slots). Here the step is a single jitted function
over the padded batch: loss is masked cross-entropy on the seed-node slots
(local indices [0, num_seed_nodes)), so the same compiled step serves every
batch of an epoch.
"""
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ..metrics.registry_names import (SCOPE_FWD_BWD, SCOPE_PAIRS,
                                      SCOPE_TRAIN, SCOPE_UPDATE)


class TrainState(NamedTuple):
  params: Any
  opt_state: Any
  step: jnp.ndarray


def create_train_state(model, rng, sample_batch, lr: float = 3e-3,
                       optimizer=None):
  params = model.init(rng, sample_batch['x'], sample_batch['edge_index'],
                      sample_batch['edge_mask'])
  tx = optimizer or optax.adam(lr)
  return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32)), tx


def make_forward_fn(model):
  """THE forward definition: ``(params, batch) -> model output`` over
  the flat batch dict (homo arrays or hetero per-type dicts — the model
  owns the signature). Training loss (:func:`make_loss_fn`), evaluation
  (:func:`make_eval_counts`), link prediction, the serving tier's
  full-graph layer materialization and its final-layer refresh
  (graphlearn_tpu/serving/) ALL resolve through this one function, so a
  trained checkpoint and the embeddings served from it can never drift.
  Extra keyword arguments pass through to ``model.apply`` (the layer
  slice below uses this)."""

  def forward(params, batch, **kwargs):
    return model.apply(params, batch['x'], batch['edge_index'],
                       batch['edge_mask'], **kwargs)

  return forward


def make_layer_slice_fn(model, lo: int, hi: int, **fixed):
  """Layer-slice view of :func:`make_forward_fn`: run only conv layers
  ``[lo, hi)`` of the SAME forward definition (``layers=(lo, hi)`` on
  the model call — models supporting it: GraphSAGE/GCN/GAT/RGNN).
  ``fixed`` forwards extra static call kwargs (RGNN's ``embed``/
  ``head``). This is the serving tier's materialization/refresh hook:
  layer l of the offline embedding program and the online final-layer
  refresh are slices of the training forward, not re-implementations."""
  fwd = make_forward_fn(model)

  def slice_fwd(params, batch):
    return fwd(params, batch, layers=(lo, hi), **fixed)

  return slice_fwd


def make_loss_fn(model, num_classes: int):
  """Masked seed-slot cross-entropy ``(params, batch) -> (loss, acc)``
  — ONE definition shared by the local jitted step and the distributed
  per-step/scanned epoch programs (loader/pipeline.py), so the
  scanned-vs-per-step bit-equivalence bar can never drift on the loss.
  Works for homo batches (array x/edge_index/edge_mask) and hetero
  batches (per-type dicts, seed-type logits/y) alike — the model owns
  the signature (the forward resolves through make_forward_fn, the same
  definition the serving tier materializes from)."""
  forward = make_forward_fn(model)

  def loss_fn(params, batch):
    logits = forward(params, batch)
    logits = logits.astype(jnp.float32)  # loss in f32 under bf16 compute
    # seed slots lead both buffers; y may be seed-block-sized
    # (seed_labels_only loaders) or full-buffer-sized — either way only
    # the common prefix carries supervision
    n = min(logits.shape[0], batch['y'].shape[0])
    logits = logits[:n]
    y = batch['y'][:n]
    seed_mask = jnp.arange(n) < batch['num_seed_nodes']
    labels = jax.nn.one_hot(y, num_classes)
    ce = optax.softmax_cross_entropy(logits, labels)
    ce = jnp.where(seed_mask, ce, 0.0)
    loss = ce.sum() / jnp.maximum(seed_mask.sum(), 1)
    correct = (logits.argmax(-1) == y) & seed_mask
    acc = correct.sum() / jnp.maximum(seed_mask.sum(), 1)
    return loss, acc

  return loss_fn


def _jit_train_step(loss_fn, tx):
  """The jitted optimizer step over ``loss_fn`` (``jit_train_step`` on a
  profiler timeline), its device work under ``glt.train``: ``fwd_bwd``
  (the value_and_grad) and ``update`` (the optimizer)."""

  @jax.jit
  @jax.named_scope(SCOPE_TRAIN)
  def train_step(state: TrainState, batch):
    with jax.named_scope(SCOPE_FWD_BWD):
      (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
          state.params, batch)
    with jax.named_scope(SCOPE_UPDATE):
      updates, opt_state = tx.update(grads, state.opt_state, state.params)
      params = optax.apply_updates(state.params, updates)
    return TrainState(params, opt_state, state.step + 1), loss, acc

  return train_step


def make_train_step(model, tx, num_classes: int):
  """Build the jitted supervised step. The batch dict carries padded
  x/edge_index/edge_mask/y plus num_seed_nodes (seed slots lead the node
  list by inducer construction)."""

  loss_fn = make_loss_fn(model, num_classes)

  train_step = _jit_train_step(loss_fn, tx)

  @jax.jit
  def eval_step(state: TrainState, batch):
    return loss_fn(state.params, batch)[1]

  return train_step, eval_step


def make_eval_counts(model):
  """Jitted exact-count evaluation: (params, batch) -> (correct, total)
  over the batch's seed slots. Counts stay on device so epoch-level
  accuracy can be accumulated without host fetches (PERF.md rules) and
  aggregated exactly across uneven batches."""

  forward = make_forward_fn(model)

  @jax.jit
  def eval_counts(params, batch):
    logits = forward(params, batch)
    # common prefix (see make_train_step loss_fn)
    n = min(logits.shape[0], batch['y'].shape[0])
    seed_mask = jnp.arange(n) < batch['num_seed_nodes']
    correct = (logits[:n].argmax(-1) == batch['y'][:n]) & seed_mask
    return correct.sum(), seed_mask.sum()

  return eval_counts


def tree_hop_offsets(batch_cap: int, fanouts, node_budget=None):
  """(hop_node_offsets, hop_edge_offsets) for the layered forward over
  dedup='tree' batches — delegates to the sampler's layout plan so the
  two can never diverge."""
  from ..sampler.neighbor_sampler import tree_layout
  return tree_layout(batch_cap, list(fanouts), node_budget)  # shared plan


def merge_hop_offsets(batch_cap: int, fanouts, node_budget=None,
                      frontier_caps=None):
  """(hop_node_offsets, hop_edge_offsets) for the layered forward over
  exact-dedup ('map'/'sort'/'merge') batches.

  The merge inducer appends each hop's new unique nodes as a contiguous
  block (prefix widths = cumulative clamped frontier caps) and emits
  each hop's edges as a contiguous ``caps[i] * k`` block, so the same
  layer-trimming the tree layout enables applies: layer ``l`` only needs
  the node prefix reachable in ``L - l`` hops and the edge blocks of
  hops ``<= L - l``. Exactness holds because dedup expands every node at
  most once — each target's in-edges live entirely in the single hop
  block that expanded it (equivalence-tested against the full forward).
  Delegates to the sampler's capacity plan so the two can never diverge.
  """
  from ..sampler.neighbor_sampler import (capacity_plan,
                                          merge_layout_from_caps)
  caps = capacity_plan(batch_cap, list(fanouts), node_budget,
                       frontier_caps)
  return merge_layout_from_caps(caps, list(fanouts))


def make_link_train_step(model, tx):
  """Jitted unsupervised/link-prediction step: dot-product scores on the
  batch's ``edge_label_index`` pairs, sigmoid BCE against ``edge_label``
  (1 for positives, 0 for the sampled negatives — the reference's
  unsupervised SAGE objective, examples/graph_sage_unsup_ppi.py loss).
  Pairs with -1 indices (masked negatives / pad seeds) are excluded."""
  forward = make_forward_fn(model)

  def loss_fn(params, batch):
    h = forward(params, batch).astype(jnp.float32)
    # what the link loss adds to a step: glt.train/…/pairs on a timeline
    with jax.named_scope(SCOPE_PAIRS):
      eli = batch['edge_label_index']
      lab = batch['edge_label'].astype(jnp.float32)
      valid = (eli[0] >= 0) & (eli[1] >= 0)
      src = h[jnp.maximum(eli[0], 0)]
      dst = h[jnp.maximum(eli[1], 0)]
      score = (src * dst).sum(-1)
      bce = optax.sigmoid_binary_cross_entropy(score, lab)
      bce = jnp.where(valid, bce, 0.0)
      loss = bce.sum() / jnp.maximum(valid.sum(), 1)
      hit = ((score > 0) == (lab > 0.5)) & valid
      acc = hit.sum() / jnp.maximum(valid.sum(), 1)
    return loss, acc

  train_step = _jit_train_step(loss_fn, tx)

  @jax.jit
  def eval_step(state: TrainState, batch):
    return loss_fn(state.params, batch)[1]

  return train_step, eval_step


def link_batch_to_dict(batch):
  """`loader.Data` from a Link(Neighbor)Loader -> jitted-step dict."""
  return dict(x=batch.x, edge_index=batch.edge_index,
              edge_mask=batch.edge_mask,
              edge_label_index=batch.metadata['edge_label_index'],
              edge_label=batch.metadata['edge_label'])


def batch_to_dict(batch):
  """`loader.Data` -> the flat dict the jitted step consumes. A typed
  batch (`loader.HeteroData` of a node loader) keeps its per-type dicts
  and supervises the seed type: its labels, its seed count."""
  if isinstance(batch.x, dict):
    t = batch.metadata['input_type']
    return dict(x=batch.x, edge_index=batch.edge_index,
                edge_mask=batch.edge_mask, y=batch.y[t],
                num_seed_nodes=batch.num_sampled_nodes[t][0])
  num_seed = (batch.num_sampled_nodes[0]
              if batch.num_sampled_nodes is not None else batch.batch_size)
  return dict(x=batch.x, edge_index=batch.edge_index,
              edge_mask=batch.edge_mask, y=batch.y,
              num_seed_nodes=num_seed)

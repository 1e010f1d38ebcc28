"""The plain reference of family ``mesh_node``: what data-parallel training
over P partitions COMPUTES, with nothing of how the mesh computes it.

For each step it takes the P shard batches the program trained on (node
ids, local edge list, mask — the only things read from the program, and
``families/mesh_node.py`` checks their membership in the graph
separately), regenerates every batch's rows from the node ids on its ONE
device (a row is a function of the dataset's seed and the id; the caller
hands the function in), computes each shard's loss and gradient by a plain
mean-GraphSAGE in straight ``jax.numpy`` — full-width segment sums over
the batch's valid edges, this file's own masked cross-entropy, ``jax.grad``
— takes THEIR MEAN, which is what DDP's all-reduce (here a ``pmean``)
hands every replica, and applies one step of its own Adam. No
``shard_map``, no collective, no second device; it imports nothing of the
program and nothing of the benchmark.

``compute_dtype=float32`` runs every matmul at ``precision='highest'``;
the lower-precision control runs the same code with weights and rows cast
to ``bfloat16`` (loss, the gradients' mean and Adam stay float32).

Layer equation (what the configuration states): SAGE, mean aggregator,
``h_i' = W_self h_i + b + W_nbr mean_{j in N(i)} h_j``, ReLU between
layers, none after the last; loss: mean cross-entropy over a shard
batch's seed rows (they lead its node buffer); Adam(b1 0.9, b2 0.999, eps
1e-8), bias-corrected. Departures from the source are the configuration
file's (``departures``): no dropout.
"""
import functools

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def layer_dims(model):
  """[(d_in, d_out)] per layer of a model description (``in_dim``,
  ``hidden``, ``out_dim``, ``layers``)."""
  dims, d_in = [], model['in_dim']
  for i in range(model['layers']):
    d = model['out_dim'] if i == model['layers'] - 1 else model['hidden']
    dims.append((d_in, d))
    d_in = d
  return dims


def init_params(model, seed):
  """The cell's initial weights, made on the device in one jitted call
  from the seed, float32, named as flax names them (so the same tree
  drops into the program's ``TrainState``): variance-scaled normals for
  the kernels, zeros for the biases."""
  import jax
  import jax.numpy as jnp
  dims = layer_dims(model)

  @jax.jit
  def make(key):
    out = {}
    for i, (d_in, d) in enumerate(dims):
      ks = jax.random.split(jax.random.fold_in(key, i), 2)
      norm = lambda k, shape, fan: (
          jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan))
      out[f'conv{i}'] = {
          'lin_self': {'kernel': norm(ks[0], (d_in, d), d_in),
                       'bias': jnp.zeros((d,), jnp.float32)},
          'lin_nbr': {'kernel': norm(ks[1], (d_in, d), d_in)}}
    return {'params': out}

  return make(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def _forward(model, params, x, src, tgt, emask, dtype):
  import jax
  import jax.numpy as jnp
  n = x.shape[0]
  seg = functools.partial(jax.ops.segment_sum, segment_ids=tgt,
                          num_segments=n)
  p = jax.tree.map(lambda a: a.astype(dtype), params['params'])
  h = x.astype(dtype)
  layers = model['layers']
  for i in range(layers):
    c = p[f'conv{i}']
    msg = jnp.where(emask[:, None], h[src], 0)
    cnt = seg(emask.astype(jnp.float32))
    agg = (seg(msg.astype(jnp.float32)) /
           jnp.maximum(cnt, 1)[:, None]).astype(dtype)
    h = (h @ c['lin_self']['kernel'] + c['lin_self']['bias'] +
         agg @ c['lin_nbr']['kernel'])
    if i < layers - 1:
      h = jax.nn.relu(h)
  return h.astype(jnp.float32)


def shard_loss(model, seeds, rows_of, dtype):
  """``loss(params, b)`` of ONE shard batch ``dict(ids, live, y, src, tgt,
  emask)``: rows regenerated from the ids, the plain forward, the mean
  cross-entropy over the first ``seeds`` rows."""
  import jax
  import jax.numpy as jnp

  def loss_fn(params, b):
    x = jnp.where(b['live'][:, None], rows_of(b['ids']), 0)
    logits = _forward(model, params, x, b['src'], b['tgt'], b['emask'],
                      dtype)[:seeds]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, b['y'][:seeds, None], 1).mean()

  return loss_fn


def make_step(model, lr, batch_size, rows_of, compute_dtype='float32',
              half_batch=False, precision='highest'):
  """``step(params, mu, nu, t, shards) -> (params, mu, nu, loss, grads)``
  over one step's list of shard batches ``dict(ids, live, y, src, tgt,
  emask)``: the mean of the shards' losses and of their gradients, one
  Adam update. ``half_batch`` plants the fault "half of every shard's
  seeds left out, the mean taken over the rest"."""
  import jax
  import jax.numpy as jnp
  seeds = batch_size // 2 if half_batch else batch_size
  shard_grad = jax.jit(jax.value_and_grad(
      shard_loss(model, seeds, rows_of, jnp.dtype(compute_dtype))))

  @jax.jit
  def update(params, mu, nu, t, g):
    t = t + 1
    mu = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + EPS),
        params, mu, nu)
    return params, mu, nu

  def step(params, mu, nu, t, shards):
    # float32 matmuls on a TPU run in bf16 passes unless told otherwise
    with jax.default_matmul_precision(precision):
      parts = [shard_grad(params, b) for b in shards]
      loss = sum(l for l, _ in parts) / len(parts)
      g = jax.tree.map(lambda *gs: sum(gs) / len(gs),
                       *[g for _, g in parts])
      params, mu, nu = update(params, mu, nu, jnp.float32(t), g)
    return params, mu, nu, loss, g

  return step


def follow(model, lr, batch_size, params0, steps, rows_of,
           compute_dtype='float32', half_batch=False, precision='highest'):
  """Drive the reference through ``steps`` (a list, per step the list of
  its P shard batches) from ``params0``. Returns host values: losses
  [n], the first step's mean gradient, and the parameter / first-moment
  trees after the last step."""
  import jax
  import jax.numpy as jnp
  step = make_step(model, lr, batch_size, rows_of, compute_dtype,
                   half_batch, precision)
  params = jax.tree.map(jnp.asarray, params0)
  mu = jax.tree.map(jnp.zeros_like, params)
  nu = jax.tree.map(jnp.zeros_like, params)
  losses, g0 = [], None
  for t, shards in enumerate(steps):
    params, mu, nu, loss, g = step(params, mu, nu, t, shards)
    losses.append(loss)
    if t == 0:
      g0 = g
  return (np.asarray(jnp.stack(losses)), jax.device_get(g0),
          jax.device_get(params), jax.device_get(mu))

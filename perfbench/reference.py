"""The plain reference: GraphSAGE and GAT on one sampled batch, in straight
``jax.numpy`` — full-width segment sums over the batch's valid edges, no
layered forward, no k-run reshapes, no kernels — with its own loss, its own
gradients (``jax.grad`` of this file's loss) and its own Adam.

It imports nothing of the program and takes nothing the program made: the
weights come from :func:`init_params` (the harness hands the same tree to
the program), the feature and label rows are gathered from the generator's
own host arrays by node id, and the only thing read from the program is the
sampled subgraph itself (node ids, local edge list, mask), whose membership
in the graph ``perfbench/check.py`` verifies separately.

``compute_dtype=float32`` runs every matmul at ``precision='highest'``; the
lower-precision control runs the same code with weights and features cast
to ``bfloat16`` (loss, gradients' accumulation into the optimizer and Adam
stay float32 — the shape of the program's own bf16 option).

Layer equations (what the configurations state):

* SAGE (mean): ``h_i' = W_self h_i + b + W_nbr mean_{j in N(i)} h_j``,
  ReLU between layers, none after the last.
* GAT (no self loops, no bias — ``departures`` in the configuration):
  ``w = h W`` split in H heads; ``e_ij = leaky_relu(a_src.w_j + a_dst.w_i,
  0.2)``; softmax over the in-edges of i; ``h_i' = sum_j alpha_ij w_j``,
  heads concatenated and ELU between layers, one head on the last layer.
* loss: mean cross-entropy over the batch's seed rows (they lead the node
  buffer); Adam(b1 0.9, b2 0.999, eps 1e-8), bias-corrected.
"""
import functools

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def layer_dims(model):
  """[(d_in, d_out_total, heads)] per layer of a model description
  (``kind``, ``in_dim``, ``hidden``, ``out_dim``, ``layers``, ``heads``)."""
  dims, d_in = [], model['in_dim']
  for i in range(model['layers']):
    last = i == model['layers'] - 1
    heads = 1 if (last or model['kind'] == 'sage') else model['heads']
    d = model['out_dim'] if last else model['hidden']
    dims.append((d_in, d, heads))
    d_in = d * heads
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
    for i, (d_in, d, h) in enumerate(dims):
      ks = jax.random.split(jax.random.fold_in(key, i), 3)
      norm = lambda k, shape, fan: (
          jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan))
      if model['kind'] == 'sage':
        out[f'conv{i}'] = {
            'lin_self': {'kernel': norm(ks[0], (d_in, d), d_in),
                         'bias': jnp.zeros((d,), jnp.float32)},
            'lin_nbr': {'kernel': norm(ks[1], (d_in, d), d_in)}}
      else:
        out[f'conv{i}'] = {
            'lin': {'kernel': norm(ks[0], (d_in, h * d), d_in)},
            'att_src': norm(ks[1], (h, d), d),
            'att_dst': norm(ks[2], (h, d), d)}
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
  dims = layer_dims(model)
  for i, (_, d, heads) in enumerate(dims):
    c = p[f'conv{i}']
    if model['kind'] == 'sage':
      msg = jnp.where(emask[:, None], h[src], 0)
      cnt = seg(emask.astype(jnp.float32))
      agg = (seg(msg.astype(jnp.float32)) /
             jnp.maximum(cnt, 1)[:, None]).astype(dtype)
      h = (h @ c['lin_self']['kernel'] + c['lin_self']['bias'] +
           agg @ c['lin_nbr']['kernel'])
    else:
      w = (h @ c['lin']['kernel']).reshape(n, heads, d)
      wf = w.astype(jnp.float32)
      a_s = (wf * c['att_src'].astype(jnp.float32)).sum(-1)
      a_d = (wf * c['att_dst'].astype(jnp.float32)).sum(-1)
      e = jax.nn.leaky_relu(a_s[src] + a_d[tgt], 0.2)
      e = jnp.where(emask[:, None], e, -jnp.inf)
      mx = jax.ops.segment_max(e, tgt, num_segments=n)
      mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
      ex = jnp.where(emask[:, None], jnp.exp(e - mx[tgt]), 0.0)
      alpha = ex / jnp.maximum(seg(ex), 1e-9)[tgt]
      out = seg(wf[src] * alpha[..., None]).astype(dtype)
      h = out.reshape(n, heads * d) if i < len(dims) - 1 else out.mean(1)
    if i < len(dims) - 1:
      h = jax.nn.relu(h) if model['kind'] == 'sage' else jax.nn.elu(h)
  return h.astype(jnp.float32)


def make_step(model, lr, batch_size, compute_dtype='float32',
              half_batch=False, precision='highest'):
  """The jitted reference step ``(params, mu, nu, t, batch) -> (params,
  mu, nu, loss, grads)`` with ``batch = dict(x, y, src, tgt, emask)``.
  ``half_batch`` plants the fault "half of the batch left out, the mean
  taken over the rest" (for reading the limits; never used by a run)."""
  import jax
  import jax.numpy as jnp
  dtype = jnp.dtype(compute_dtype)
  seeds = batch_size // 2 if half_batch else batch_size

  def loss_fn(params, b):
    logits = _forward(model, params, b['x'], b['src'], b['tgt'],
                      b['emask'], dtype)[:seeds]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, b['y'][:seeds, None], 1).mean()

  def step(params, mu, nu, t, b):
    loss, g = jax.value_and_grad(loss_fn)(params, b)
    t = t + 1
    mu = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + EPS),
        params, mu, nu)
    return params, mu, nu, loss, g

  jitted = jax.jit(step)

  def run(params, mu, nu, t, b):
    # float32 matmuls on a TPU run in bf16 passes unless told otherwise
    with jax.default_matmul_precision(precision):
      return jitted(params, mu, nu, jnp.float32(t), b)

  return run


def follow(model, lr, batch_size, params0, batches, compute_dtype='float32',
           half_batch=False, precision='highest'):
  """Drive the reference through ``batches`` from ``params0``. Returns
  host values: losses [n], the first step's gradient tree, and the
  parameter / first-moment trees after the last step."""
  import jax
  import jax.numpy as jnp
  step = make_step(model, lr, batch_size, compute_dtype, half_batch,
                   precision)
  params = jax.tree.map(jnp.asarray, params0)
  mu = jax.tree.map(jnp.zeros_like, params)
  nu = jax.tree.map(jnp.zeros_like, params)
  losses, g0 = [], None
  for t, b in enumerate(batches):
    params, mu, nu, loss, g = step(params, mu, nu, t, b)
    losses.append(loss)
    if t == 0:
      g0 = g
  return (np.asarray(jnp.stack(losses)), jax.device_get(g0),
          jax.device_get(params), jax.device_get(mu))

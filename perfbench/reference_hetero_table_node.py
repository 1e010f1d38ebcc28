"""The plain reference of family ``hetero_table_node``: a relational GCN
(R-GCN, as ``models.RGNN(conv='sage')`` builds it) on one sampled typed
batch whose node types other than the labelled one have no feature rows but
LEARNED TABLES, in straight ``jax.numpy`` — per relation the mean of the
source rows over each target's sampled in-edges of that relation (segment
sums), a ``Linear`` of that mean plus a ``Linear`` (with bias) of the
target's own row, summed over relations per target type; ReLU between
layers; a final ``Linear`` to the classes on the seeds; its own masked
cross-entropy, ``jax.grad`` over the weights AND the tables, and its own
DENSE Adam over every leaf — every table row's moments decay on every
step, and a row read once keeps moving by its momentum, as the OGB
baseline's ``torch.optim.Adam`` over ``model.parameters()`` does.

It imports nothing of the program and takes nothing the program made: the
weights and the tables come from :func:`init_params` (the harness hands the
same tree to the program), the labelled type's rows are gathered here from
the generator's own host table by node id, a table type's rows from this
file's own table parameter by node id, and the only thing read from the
program is the sampled subgraph itself (per-type node ids, per-relation
local edge lists), whose membership in the graph the family's exact numbers
verify separately. No kernel, no capacity, no tree record: the layout's
static prefixes (``row_bounds``, ``edge_bounds``) are the harness's own
arithmetic from caps and fan-out, as for ``reference_hetero_node``.

A batch reaches this file as its VALID rows and edges only, padded here to
one size per node type and per relation over the batches followed
(``PAD_TO``), so that they all run one compiled step; each relation is
recomputed for the gradient (``jax.checkpoint``). ``compute_dtype=float32``
runs every matmul at ``precision='highest'``; the lower-precision control
casts weights, tables and rows to ``bfloat16`` for the forward (the sums
over edges, loss, gradients and Adam stay float32).

A model description: ``in_dims`` ``{type: row width}`` (the labelled
type's feature width, a table type's table width), ``hidden``,
``out_dim``, ``layers``, ``out_ntype``, ``ntypes`` (every type with rows or
a table), ``tables`` ``{type: [rows, width]}``, ``relations`` ``{name:
(message source type, target type)}`` under the STORED relation's name
``<src>__<rel>__<dst>``, ``hop_relations``, ``row_bounds``,
``edge_bounds`` — as ``reference_hetero_node`` reads them.
"""
import functools

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
PAD_TO = 32_768      # a batch's rows and edges are padded to a multiple, so
#                      that a chunk's batches share one compiled step
TABLES = 'node_tables'


def layer_relations(model):
  """Per layer, the relations that pass messages there: layer ``i`` sees
  the relations sampled in hops ``< L - i`` whose two ends still hold rows,
  and keeps their target types."""
  depth = model['layers']
  have = set(model['ntypes'])
  out = []
  for i in range(depth):
    names = []
    for hop in model['hop_relations'][:depth - i]:
      for name in hop:
        s, d = model['relations'][name]
        if name not in names and s in have and d in have:
          names.append(name)
    names.sort()
    have = {model['relations'][n][1] for n in names}
    out.append(names)
  return out


def init_params(model, seed):
  """The cell's initial weights and tables, made on the device in one
  jitted call from the seed, float32, named as flax names them in
  ``models.RGNN`` (so the same tree drops into the program's
  ``TrainState``): variance-scaled normals for kernels, zeros for biases,
  and for each table the OGB baseline's ``xavier_uniform_``: uniform on
  ``+-sqrt(6 / (rows + width))``."""
  import jax
  import jax.numpy as jnp
  hid = model['hidden']

  @jax.jit
  def make(key):
    norm = lambda k, shape: (jax.random.normal(k, shape, jnp.float32) /
                             np.sqrt(shape[0]))
    out = {TABLES: {}}
    for j, t in enumerate(sorted(model['tables'])):
      rows, width = model['tables'][t]
      bound = np.sqrt(6.0 / (rows + width))
      out[TABLES][t] = jax.random.uniform(
          jax.random.fold_in(key, 3000 + j), (rows, width), jnp.float32,
          -bound, bound)
    for i, names in enumerate(layer_relations(model)):
      layer = {}
      for j, name in enumerate(names):
        src_t, dst_t = model['relations'][name]
        d_src = model['in_dims'][src_t] if i == 0 else hid
        d_dst = model['in_dims'][dst_t] if i == 0 else hid
        ks = jax.random.split(jax.random.fold_in(key, 100 * i + j), 2)
        layer[f'lin_self_{name}'] = {
            'kernel': norm(ks[0], (d_dst, hid)),
            'bias': jnp.zeros((hid,), jnp.float32)}
        layer[f'lin_nbr_{name}'] = {'kernel': norm(ks[1], (d_src, hid))}
      out[f'hetero{i}'] = layer
    out['lin_out'] = {
        'kernel': norm(jax.random.fold_in(key, 2000), (hid, model['out_dim'])),
        'bias': jnp.zeros((model['out_dim'],), jnp.float32)}
    return {'params': out}

  return make(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def _sage(h_src, h_dst, self_k, self_b, nbr_k, src, tgt, emask, n_out):
  """One relation's mean-SAGE conv into the first ``n_out`` target rows:
  ``h_dst @ self_k + self_b + mean_{e -> v}(h_src[src[e]]) @ nbr_k``."""
  import jax
  import jax.numpy as jnp
  seg = jnp.where(emask, tgt, n_out)        # a padding edge sums nowhere
  s = jax.ops.segment_sum(h_src[src].astype(jnp.float32), seg,
                          num_segments=n_out + 1)[:n_out]
  c = jax.ops.segment_sum(emask.astype(jnp.float32), seg,
                          num_segments=n_out + 1)[:n_out]
  mean = (s / jnp.maximum(c, 1.0)[:, None]).astype(h_src.dtype)
  out = h_dst[:n_out] @ self_k + self_b + mean @ nbr_k
  return out.astype(jnp.float32)


def _forward(model, params, x, ids, edges, dtype):
  import jax
  import jax.numpy as jnp
  p = jax.tree.map(lambda a: a.astype(dtype), params['params'])
  depth = model['layers']
  rb, eb = model['row_bounds'], model['edge_bounds']
  # the batch's rows: the labelled type's from the host table (``x``), a
  # table type's from this file's own table by node id (a pad row is 0)
  h = {t: v.astype(dtype) for t, v in x.items()}
  for t, (i, n) in ids.items():
    valid = jnp.arange(i.shape[0]) < n
    h[t] = jnp.where(valid[:, None], p[TABLES][t][i], 0)
  for li, names in enumerate(layer_relations(model)):
    hops = depth - li
    c = p[f'hetero{li}']
    out = {}
    for name in names:
      s_t, d_t = model['relations'][name]
      ed = edges[name]
      ne = min(eb[name][hops], ed['src'].shape[0])
      n_out = min(rb[d_t][hops - 1], h[d_t].shape[0])
      conv = jax.checkpoint(functools.partial(_sage, n_out=n_out))
      o = conv(h[s_t][:rb[s_t][hops]], h[d_t][:rb[d_t][hops]],
               c[f'lin_self_{name}']['kernel'], c[f'lin_self_{name}']['bias'],
               c[f'lin_nbr_{name}']['kernel'], ed['src'][:ne],
               ed['tgt'][:ne], jnp.arange(ne) < ed['hops'][hops])
      out[d_t] = out[d_t] + o if d_t in out else o
    h = ({t: jax.nn.relu(v).astype(dtype) for t, v in out.items()}
         if li < depth - 1 else out)
  logits = (h[model['out_ntype']].astype(dtype) @ p['lin_out']['kernel'] +
            p['lin_out']['bias'])
  return logits.astype(jnp.float32)


def make_step(model, lr, batch_size, compute_dtype='float32',
              half_batch=False, precision='highest'):
  """The jitted reference step ``(params, mu, nu, t, batch) -> (params,
  mu, nu, loss, grads)`` with ``batch = dict(x={labelled type: rows},
  ids={table type: (ids, valid count)}, y, edges={name: dict(src, tgt,
  hops)})``. ``half_batch`` plants the fault "half of the batch left out,
  the mean taken over the rest" (for reading the limits; never used by a
  run)."""
  import jax
  import jax.numpy as jnp
  dtype = jnp.dtype(compute_dtype)
  seeds = batch_size // 2 if half_batch else batch_size

  def loss_fn(params, b):
    logits = _forward(model, params, b['x'], b['ids'], b['edges'],
                      dtype)[:seeds]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, b['y'][:seeds, None], 1).mean()

  def step(params, mu, nu, t, b):
    loss, g = jax.value_and_grad(loss_fn)(params, b)
    t = t + 1
    # dense Adam: every leaf, every table row, every step
    mu = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + EPS),
        params, mu, nu)
    return params, mu, nu, loss, g

  jitted = jax.jit(step, donate_argnums=(0, 1, 2))

  def run(params, mu, nu, t, b):
    # float32 matmuls on a TPU run in bf16 passes unless told otherwise
    with jax.default_matmul_precision(precision):
      return jitted(params, mu, nu, jnp.float32(t), b)

  run.jitted = jitted      # for a compile-only rehearsal (scripts/)
  return run


def _padded(n):
  return -(-max(int(n), 1) // PAD_TO) * PAD_TO


def padded_sizes(batches):
  """One size per node type and per relation for all of ``batches``."""
  rows = {t: _padded(max(len(b['node'][t]) for b in batches))
          for t in batches[0]['node']}
  edges = {r: _padded(max(len(b['edges'][r]['src']) for b in batches))
           for r in batches[0]['edges']}
  return rows, edges


def pad_batch(b, tables, rows, edges):
  """A batch as the step takes it: the rows of each type with a host
  table (``tables``) read by node id and zero rows up to ``rows[type]``;
  a table type's node ids padded with 0 and its valid count; per relation
  ``src`` / ``tgt`` padded with 0 up to ``edges[name]``."""
  x, ids = {}, {}
  for t, v in b['node'].items():
    v = np.asarray(v)
    if t in tables:
      x[t] = np.zeros((rows[t], tables[t].shape[1]), tables[t].dtype)
      x[t][:len(v)] = np.take(tables[t], v, axis=0)
    else:
      ids[t] = (np.pad(v.astype(np.int32), (0, rows[t] - len(v))),
                np.int32(len(v)))
  ed = {}
  for r, e in b['edges'].items():
    grow = lambda a: np.pad(np.asarray(a, np.int32), (0, edges[r] - len(a)))
    ed[r] = dict(src=grow(e['src']), tgt=grow(e['tgt']),
                 hops=np.asarray(e['hops'], np.int32))
  return dict(x=x, ids=ids, y=np.asarray(b['y'], np.int32), edges=ed)


def follow(model, lr, batch_size, params0, batches, tables,
           compute_dtype='float32', half_batch=False, precision='highest'):
  """Drive the reference through ``batches`` (``dict(node={type: valid
  ids}, y, edges={name: dict(src, tgt, hops)})``) from ``params0``,
  reading the labelled type's rows from the host ``tables`` by node id.
  Returns host values: losses [n], the first step's gradient tree, and the
  parameter / first-moment trees after the last step."""
  import jax
  import jax.numpy as jnp
  step = make_step(model, lr, batch_size, compute_dtype, half_batch,
                   precision)
  params = jax.tree.map(jnp.array, params0)
  mu = jax.tree.map(jnp.zeros_like, params)
  nu = jax.tree.map(jnp.zeros_like, params)
  rows, edges = padded_sizes(batches)
  losses, g0 = [], None
  for t, b in enumerate(batches):
    params, mu, nu, loss, g = step(params, mu, nu, t,
                                   pad_batch(b, tables, rows, edges))
    losses.append(loss)
    if t == 0:
      g0 = jax.device_get(g)
    del g
  return (np.asarray(jnp.stack(losses)), g0, jax.device_get(params),
          jax.device_get(mu))

"""The plain reference of family ``hetero_node``: a relational GAT (R-GAT)
on one sampled typed batch, in straight ``jax.numpy`` segment ops — per
edge type a GAT conv (one linear map, per-head attention logits, leaky
ReLU 0.2, softmax over each target's sampled in-edges of that type,
weighted sum), summed per target node type — with its own masked
cross-entropy on the seed rows, ``jax.grad`` of this file's loss and its
own Adam.

It imports nothing of the program and takes nothing the program made: the
weights come from :func:`init_params` (the harness hands the same tree to
the program), the feature rows are gathered here from the generator's own
host tables by node id, and the only thing read from the program is the
sampled subgraph itself (per-type node ids, per-edge-type local edge lists
and masks), whose membership in the graph the family's exact numbers
verify separately.

The layer structure is the source's (GLT ``examples/igbh/rgnn.py``, with
``models.RGNN``'s ``departures`` as the configuration lists them): a
``Linear`` per node type brings the rows to ``hidden``; every layer keeps
``hidden`` as ``heads`` x ``hidden // heads`` concatenated, ReLU between
layers; a final ``Linear`` maps the seed type to the classes. Like the
source (``trim_to_layer``), layer ``i`` of ``L`` reads only the rows within
``L - i`` hops of the seeds and the edges of hops ``< L - i``: the model
description carries those static prefixes (``row_bounds``, ``edge_bounds``)
— a layout, not a result.

**Computed in blocks, so that it fits the chip at the timed size**
(model-configs section 3.3): a batch reaches this file as its VALID rows and
edges only (a 512-seed IGBH batch: 403 k of 765 k buffer rows, 1.0 M of
2.1 M edge slots), padded here to one size per node type and per relation
over the batches followed, so that they all run one compiled step; the
input ``Linear``s go over row blocks (:func:`_embed`) and each relation's
messages over edge blocks (:func:`_weighted_sum`), every block recomputed
in the backward pass (``jax.checkpoint``), and a relation as a whole is
recomputed too. Plain code, no kernel, no cache: the blocks change the
order of two sums and nothing else, and ``EMBED_BLOCK`` / ``EDGE_BLOCK``
set larger than the batch give the one-piece computation (the tests
compare the two).

``compute_dtype=float32`` runs every matmul at ``precision='highest'``; the
lower-precision control runs the same code with weights and rows cast to
``bfloat16`` (attention logits, softmax, the sums over edges, loss,
gradients' accumulation into the optimizer and Adam stay float32 — the
shape of the program's own bf16 option).

A model description: ``kind`` 'rgat', ``in_dim``, ``hidden``, ``heads``,
``out_dim``, ``layers``, ``out_ntype``, ``ntypes`` (those with rows),
``relations`` ``{name: (message source type, target type)}`` under the
STORED relation's name ``<src>__<rel>__<dst>`` (messages flow against a
relation stored by its source), ``hop_relations`` (per hop, the names
sampled there), ``row_bounds`` ``{type: [o_0..o_L]}`` and ``edge_bounds``
``{name: [e_0..e_L]}`` (prefix of the name's edge list holding hops
``< h``; ``e_0 = 0``) — the static caps of those prefixes; a batch's own
valid counts are below them and arrive with it (``hops``).

A batch, as :func:`follow` takes it: ``node`` ``{type: valid ids, in the
buffer's order}``, ``y`` (the seeds' labels), ``edges`` ``{name:
dict(src, tgt, hops)}`` — the VALID edges in hop order as positions in the
two ends' ``node`` lists, and ``hops`` ``[L + 1]``, the number of them in
hops ``< h``.
"""
import functools

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
PAD_TO = 32_768          # a batch's rows and edges are padded to a multiple:
#                          coarse, so that most seeds' chunks share one
#                          compiled step (a compile takes two minutes and
#                          10 GB of host memory on the chip's machine)
EMBED_BLOCK = 8_192      # rows of a table cast and projected at a time (a
#                          divisor of PAD_TO: no padded copy of a table)
EDGE_BLOCK = 32_768      # edges of a relation whose messages are alive at once


def layer_relations(model):
  """Per layer, the relations that pass messages there and the node types
  that hold rows after it: layer ``i`` sees the relations sampled in hops
  ``< L - i`` whose two ends still hold rows, and keeps the target types."""
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
  """The cell's initial weights, made on the device in one jitted call
  from the seed, float32, named as flax names them in ``models.RGNN`` (so
  the same tree drops into the program's ``TrainState``): variance-scaled
  normals for kernels and attention vectors, zeros for the biases."""
  import jax
  import jax.numpy as jnp
  hid, heads = model['hidden'], model['heads']
  d_head = hid // heads
  rels = layer_relations(model)

  @jax.jit
  def make(key):
    norm = lambda k, shape, fan: (
        jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan))
    out = {}
    for j, t in enumerate(sorted(model['ntypes'])):
      out[f'embed_{t}'] = {
          'kernel': norm(jax.random.fold_in(key, 1000 + j),
                         (model['in_dim'], hid), model['in_dim']),
          'bias': jnp.zeros((hid,), jnp.float32)}
    for i, names in enumerate(rels):
      layer = {}
      for j, name in enumerate(names):
        ks = jax.random.split(jax.random.fold_in(key, 100 * i + j), 3)
        layer[f'lin_{name}'] = {'kernel': norm(ks[0], (hid, hid), hid)}
        layer[f'att_src_{name}'] = norm(ks[1], (heads, d_head), d_head)
        layer[f'att_dst_{name}'] = norm(ks[2], (heads, d_head), d_head)
      out[f'hetero{i}'] = layer
    out['lin_out'] = {
        'kernel': norm(jax.random.fold_in(key, 2000),
                       (hid, model['out_dim']), hid),
        'bias': jnp.zeros((model['out_dim'],), jnp.float32)}
    return {'params': out}

  return make(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def _embed(x, kernel, bias, dtype):
  """``x @ kernel + bias`` a block of rows at a time, each block cast
  from the table's dtype inside the block and recomputed for the
  gradient: a whole table's rows in float32 would not fit."""
  import jax
  import jax.numpy as jnp
  n = x.shape[0]
  blk = min(EMBED_BLOCK, n)
  pad = (-n) % blk
  xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, blk, x.shape[1])
  f = jax.checkpoint(lambda b: b.astype(dtype) @ kernel + bias)
  return jax.lax.map(f, xb).reshape(-1, kernel.shape[1])[:n]


def _weighted_sum(w_s, src, tgt, alpha, n_out):
  """``sum_e alpha[e] * w_s[src[e]]`` into row ``tgt[e]`` of ``[n_out,
  heads, d]``, ``EDGE_BLOCK`` edges at a time: one block's gathered
  messages ([block, heads * d] float32) are alive at once, and each block
  is gathered again for the gradient. A padding edge has ``alpha`` 0."""
  import jax
  import jax.numpy as jnp
  e = src.shape[0]
  blk = min(EDGE_BLOCK, e)
  pad = (-e) % blk
  cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
      (-1, blk) + a.shape[1:])
  part = jax.checkpoint(lambda w, s, t, a: jax.ops.segment_sum(
      w[s] * a[..., None], t, num_segments=n_out))
  # the sum so far is no input of a block, so no block keeps a copy of it
  out, _ = jax.lax.scan(
      lambda acc, b: (acc + part(w_s, *b), None),
      jnp.zeros((n_out,) + w_s.shape[1:], w_s.dtype),
      (cut(src), cut(tgt), cut(alpha)))
  return out


def _gat(h_src, h_dst, lin, att_src, att_dst, src, tgt, emask, n_out, heads):
  """One relation's GAT conv: messages from ``h_src`` rows to the first
  ``n_out`` rows of the target type, heads concatenated."""
  import jax
  import jax.numpy as jnp
  d = lin.shape[1] // heads
  w_s = (h_src @ lin).reshape(-1, heads, d).astype(jnp.float32)
  # every target is among the first n_out rows of its type
  w_d = (h_dst[:n_out] @ lin).reshape(-1, heads, d).astype(jnp.float32)
  a_s = (w_s * att_src.astype(jnp.float32)).sum(-1)
  a_d = (w_d * att_dst.astype(jnp.float32)).sum(-1)
  e = jax.nn.leaky_relu(a_s[src] + a_d[tgt], 0.2)
  e = jnp.where(emask[:, None], e, -jnp.inf)
  mx = jax.ops.segment_max(e, tgt, num_segments=n_out)
  mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
  ex = jnp.where(emask[:, None], jnp.exp(e - mx[tgt]), 0.0)
  den = jax.ops.segment_sum(ex, tgt, num_segments=n_out)
  alpha = ex / jnp.maximum(den, 1e-9)[tgt]
  out = _weighted_sum(w_s, src, tgt, alpha, n_out)
  return out.reshape(n_out, heads * d).astype(h_src.dtype)


def _forward(model, params, x, edges, dtype):
  import jax
  import jax.numpy as jnp
  p = jax.tree.map(lambda a: a.astype(dtype), params['params'])
  depth, heads = model['layers'], model['heads']
  rb, eb = model['row_bounds'], model['edge_bounds']
  h = {t: _embed(x[t], p[f'embed_{t}']['kernel'], p[f'embed_{t}']['bias'],
                 dtype) for t in sorted(x)}
  for i, names in enumerate(layer_relations(model)):
    hops = depth - i
    c = p[f'hetero{i}']
    out = {}
    for name in names:
      s_t, d_t = model['relations'][name]
      ed = edges[name]
      # the static prefixes, no longer than what the batches hold; the
      # batch's own count of edges in hops < `hops` masks the rest
      ne = min(eb[name][hops], ed['src'].shape[0])
      n_out = min(rb[d_t][hops - 1], h[d_t].shape[0])
      # one relation at a time, its intermediates recomputed for the
      # gradient: what one relation keeps alive is gigabytes
      conv = jax.checkpoint(functools.partial(_gat, n_out=n_out,
                                              heads=heads))
      o = conv(h[s_t][:rb[s_t][hops]], h[d_t][:rb[d_t][hops]],
               c[f'lin_{name}']['kernel'], c[f'att_src_{name}'],
               c[f'att_dst_{name}'], ed['src'][:ne], ed['tgt'][:ne],
               jnp.arange(ne) < ed['hops'][hops])
      out[d_t] = out[d_t] + o if d_t in out else o
    h = {t: jax.nn.relu(v) for t, v in out.items()} if i < depth - 1 else out
  logits = (h[model['out_ntype']] @ p['lin_out']['kernel'] +
            p['lin_out']['bias'])
  return logits.astype(jnp.float32)


def make_step(model, lr, batch_size, compute_dtype='float32',
              half_batch=False, precision='highest'):
  """The jitted reference step ``(params, mu, nu, t, batch) -> (params,
  mu, nu, loss, grads)`` with ``batch = dict(x={type: rows}, y, edges=
  {name: dict(src, tgt, hops)})``, every array padded as :func:`follow`
  pads it. ``half_batch`` plants the fault "half
  of the batch left out, the mean taken over the rest" (for reading the
  limits; never used by a run)."""
  import jax
  import jax.numpy as jnp
  dtype = jnp.dtype(compute_dtype)
  seeds = batch_size // 2 if half_batch else batch_size

  def loss_fn(params, b):
    logits = _forward(model, params, b['x'], b['edges'], dtype)[:seeds]
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

  jitted = jax.jit(step, donate_argnums=(0, 1, 2))

  def run(params, mu, nu, t, b):
    # float32 matmuls on a TPU run in bf16 passes unless told otherwise
    with jax.default_matmul_precision(precision):
      return jitted(params, mu, nu, jnp.float32(t), b)

  run.jitted = jitted      # for a builder's compile-only rehearsal
  return run


def _padded(n):
  return -(-max(int(n), 1) // PAD_TO) * PAD_TO


def padded_sizes(batches):
  """One size per node type and per relation for all of ``batches``:
  the largest valid count among them, rounded up to ``PAD_TO`` — so the
  batches of a chunk run one compiled step."""
  rows = {t: _padded(max(len(b['node'][t]) for b in batches))
          for t in batches[0]['node']}
  edges = {r: _padded(max(len(b['edges'][r]['src']) for b in batches))
           for r in batches[0]['edges']}
  return rows, edges


def pad_batch(b, tables, rows, edges):
  """A batch as the step takes it: per node type its rows read from the
  host ``tables`` by node id — the reference's own collate — and zero rows
  up to ``rows[type]``; per relation ``src`` / ``tgt`` padded with 0 up to
  ``edges[name]`` (``hops`` says how many are edges)."""
  x = {}
  for t, ids in b['node'].items():
    x[t] = np.zeros((rows[t], tables[t].shape[1]), tables[t].dtype)
    x[t][:len(ids)] = np.take(tables[t], np.asarray(ids), axis=0)
  ed = {}
  for r, e in b['edges'].items():
    grow = lambda a: np.pad(np.asarray(a, np.int32), (0, edges[r] - len(a)))
    ed[r] = dict(src=grow(e['src']), tgt=grow(e['tgt']),
                 hops=np.asarray(e['hops'], np.int32))
  return dict(x=x, y=np.asarray(b['y'], np.int32), edges=ed)


def follow(model, lr, batch_size, params0, batches, tables,
           compute_dtype='float32', half_batch=False, precision='highest'):
  """Drive the reference through ``batches`` (``dict(node={type: valid
  ids}, y, edges={name: dict(src, tgt, hops)})``) from ``params0``,
  reading each batch's rows from the host ``tables`` by node id. Returns
  host values: losses [n], the first step's gradient tree, and the
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

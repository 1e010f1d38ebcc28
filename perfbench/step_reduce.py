"""The layer clock of the PER-BATCH loop: self time per layer over every
program of the ``step`` executor's traced slice.

Under ``step`` sampling, collate and the model are separate device programs
(``jit_sample_*``, ``jit_collate_batch``, ``jit_train_step``), each launched
once a batch, and their ops carry the same ``glt.*`` scopes as the scanned
chunk's (``docs/observability.md``). So there is no one program to cut the
slice by: ``layers(run)`` sums ``scope_reduce``'s self time over ALL the ops
of the slice — ``glt.sample``, ``glt.collate``, ``glt.train``, and
*unscoped* for the rest (the relayout copies at each program's boundary,
key folding, the overflow flag's small programs) — so the four
``step_*_ms`` add up to the slice's busy time. It reduces once per run,
keeps the result in ``run`` and prints one ``perfbench:`` line with the
sub-scope split and the unscoped time by op class. With a program that
names no ``glt.`` scope every reader finds nothing and returns ``None``.
"""
import json

from perfbench import scope_reduce


def layers(run):
  """``{glt.sample, glt.collate, glt.train, unscoped}`` in ms/step over
  the traced slice's every op, or None where none carries a scope."""
  if 'step_reduce' in run:
    return run['step_reduce']
  a = run['scan']
  out = None
  if a['steps']:
    scopes, loose = scope_reduce.by_scope(a['device'], None,
                                          scope_reduce.timed_of(a))
    per_step = lambda s: 1e3 * s / a['steps']
    layer_s = scope_reduce.by_layer(scopes)
    if layer_s is not None:
      out = {k: per_step(s) for k, s in layer_s.items()}
      print('perfbench: ' + json.dumps({'step_reduce': dict(
          step_ms_per_step=dict(out, sum=sum(out.values()),
                                busy=per_step(a['busy_s'])),
          step_sub_scopes_ms={k: per_step(s)
                              for k, s in sorted(scopes.items())},
          step_unscoped_ops_ms={k: per_step(s)
                                for k, s in list(loose.items())[:8]})}),
            flush=True)
  run['step_reduce'] = out
  return out


def layer_ms(run, layer):
  ms = layers(run)
  return None if ms is None else ms[layer]

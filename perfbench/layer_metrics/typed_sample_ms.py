"""Device ms per step of sampling — the typed hop loop: every edge type's draw
and induce, the per-type frontier merge — INSIDE the typed window's own chunk
program: self time of the ``XLA Ops`` events of ``jit_scan_epoch_chunk``
whose ``op_name`` is under ``glt.sample`` (perfbench/scope_reduce.py), over
the traced slice. The split by edge type and node type goes on
``typed_reduce``'s ``perfbench:`` line. None with a program that has no such
scope."""
from perfbench import scope_reduce, typed_reduce

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  typed_reduce.split(run)
  return scope_reduce.layer_ms(run, 'glt.sample')

"""Device ms per step of sampling INSIDE the link window's own chunk
program: self time of the ``XLA Ops`` events of ``jit_scan_epoch_chunk``
whose ``op_name`` is under ``glt.sample`` — the seed positions and pair
gather, the negative sampler, the seed union and every hop's draw and
induce (perfbench/scope_reduce.py; ``scan_sample_ms``' body over another
cell). The new scopes' share goes on ``link_reduce``'s ``perfbench:`` line,
the hops' on ``scope_reduce``'s. None with a program that has no such
scope."""
from perfbench import link_reduce, scope_reduce

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  link_reduce.split(run)
  return scope_reduce.layer_ms(run, 'glt.sample')

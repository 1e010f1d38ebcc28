"""Device ms per step of the miss-only row exchange, whole: self time of the
``XLA Ops`` events under ``glt.collate/exchange`` in the mesh chunk
program, per chip, mean over the chips (perfbench/mesh_parts_reduce.py;
equal to that scope's figure on the ``mesh_reduce`` line). Its eight parts
and ``row_exchange_unsplit_ms`` add up to it; the line has every part.
None with a program that names no part."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return parts.ms(run, parts.EXCHANGE, (parts.WHOLE,))

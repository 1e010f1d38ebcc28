"""Share of the row exchange's request slots that held a valid unique
miss, over the window: the program's counter ``dist_feature.unique_misses``
(published once an epoch; the executor takes the difference) over steps x
partitions x the gauge ``dist_feature.exchange_slots`` (set once when the
lookup body is built: the slots a shard packs, sends, looks up, gathers and
returns a step whatever the valid count). None where the program sets no
such gauge."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'collate'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  return parts.fill_share(run)

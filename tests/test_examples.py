"""Integration tests for the example entry points.

The staged-data test writes a TINY dataset in the exact npz layout the
products example documents for real ogbn-products staging
(`--data-dir`/ogbn_products.npz: edge_index, feat, label, train_idx,
valid_idx, test_idx) and drives the script end to end through that
path — so the day real data is staged, the loader path is already
exercised.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
EXAMPLE = os.path.join(REPO, 'examples', 'train_sage_ogbn_products.py')


SMOKE = os.path.join(REPO, 'chip_smoke.py')


def test_chip_smoke_refuses_cpu():
  """`python chip_smoke.py` needs the chip: on a CPU-only machine it
  exits non-zero within seconds, names the platform it found, and
  prints no result record."""
  env = dict(os.environ, JAX_PLATFORMS='cpu')
  out = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, timeout=120, env=env)
  assert out.returncode != 0
  assert "jax found 'cpu'" in out.stderr, out.stderr[-2000:]
  assert 'platform=cpu' in out.stdout
  assert not any(ln.startswith('{') for ln in out.stdout.splitlines())


def test_chip_smoke_body_tiny_on_cpu_mesh(tmp_path):
  """The smoke's body at a tiny shape on the virtual CPU mesh: every
  phase that can run off-chip passes (XLA:CPU scans bit-identically),
  the mesh phase spreads over 4 devices, and the TPU-only phases say
  why they did not run."""
  import chip_smoke
  tiny = dict(num_nodes=3000, avg_deg=8, feat_dim=16, num_classes=5,
              fanout=(3, 2), batch=16, hidden=16, steps=4, chunk=2,
              gather_rows=256, gather_ids=64, hop_seeds=16,
              mesh_parts=4, mesh_steps=2, mesh_chunk=2)
  res = chip_smoke.run_smoke(tiny, 'cpu', str(tmp_path / 'trace'))
  assert res['platform'] == 'cpu' and res['n_devices'] >= 4
  assert res['phases']['data'] == res['phases']['per_step'] == \
      res['phases']['scan'] == res['phases']['mesh'] == 'passed'
  assert res['phases']['kernels'].startswith('skipped')
  assert res['phases']['trace'].startswith('skipped')
  assert res['bit_identical'] and res['steady_state_compiles'] == 0
  assert res['mesh']['batch_x_devices'] == 4
  assert res['mesh']['steady_state_compiles'] == 0
  assert list(res)[-1] == 'claim' and res['claim'] is None
  json.dumps(res)   # the record is one JSON object
  # the last stdout line: exactly the keys the driver's chip check reads
  last = json.loads(chip_smoke.verdict_line(res))
  assert last == {'ok': True, 'device': {
      'platform': 'cpu', 'kind': res['device_kind'],
      'count': res['n_devices']}}


def test_compilation_cache_is_placed_from_outside(tmp_path, monkeypatch):
  """enable_compilation_cache(): with JAX_COMPILATION_CACHE_DIR set the
  program sets no directory in code; unset, the cache is the one fixed
  in-checkout path, the same in every process."""
  import jax
  import graphlearn_tpu as glt
  placed = str(tmp_path / 'placed')
  monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', placed)
  before = jax.config.jax_compilation_cache_dir
  assert glt.utils.enable_compilation_cache() == placed
  assert jax.config.jax_compilation_cache_dir == before
  assert not os.path.exists(placed)    # nothing created in code either

  device_py = os.path.join(REPO, 'graphlearn_tpu', 'utils', 'device.py')
  code = ('import importlib.util, sys, jax\n'
          'spec = importlib.util.spec_from_file_location("d", sys.argv[1])\n'
          'mod = importlib.util.module_from_spec(spec)\n'
          'spec.loader.exec_module(mod)\n'
          'print(mod.enable_compilation_cache())\n'
          'print(jax.config.jax_compilation_cache_dir)')
  env = {k: v for k, v in os.environ.items()
         if k != 'JAX_COMPILATION_CACHE_DIR'}
  fixed = os.path.join(REPO, '.xla_cache')
  for _ in range(2):
    out = subprocess.run([sys.executable, '-c', code, device_py],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [fixed, fixed]


def test_compilation_cache_never_serves_another_scopes_executable(tmp_path):
  """The layer clock lives in instruction metadata, which jax's default
  cache key strips: two programs that differ only in a named scope would
  share one cached executable, and a profile of the second would show
  the first's names. enable_compilation_cache() keys the cache with
  metadata, and with source paths relative to the checkout."""
  code = (
      'import os, sys\n'
      'import jax, jax.numpy as jnp\n'
      'import graphlearn_tpu as glt\n'
      'assert glt.utils.enable_compilation_cache(0.0) == sys.argv[1]\n'
      'def make(scope):\n'
      '  @jax.jit\n'
      '  def f(x):\n'
      '    with jax.named_scope(scope):\n'
      '      return jnp.sort(x) * 2\n'
      '  return f\n'
      'for scope in ("glt.a", "glt.b"):\n'
      '  f = make(scope)\n'
      '  f(jnp.arange(8.0)).block_until_ready()\n'
      '  text = f.lower(jnp.arange(8.0)).compile().as_text()\n'
      '  assert "/" + scope + "/" in text, (scope, text)\n'
      'print(jax.config.jax_hlo_source_file_canonicalization_regex)\n')
  placed = str(tmp_path / 'cache')
  env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=placed,
             JAX_PLATFORMS='cpu', PYTHONPATH=REPO)
  out = subprocess.run([sys.executable, '-c', code, placed],
                       capture_output=True, text=True, timeout=180,
                       env=env, cwd=str(tmp_path))
  assert out.returncode == 0, out.stderr[-2000:]
  import re
  assert re.sub(out.stdout.strip(), '',
                os.path.join(REPO, 'graphlearn_tpu', 'ops', 'collate.py')
                ) == os.path.join('graphlearn_tpu', 'ops', 'collate.py')
  assert len(os.listdir(placed)) >= 2   # one entry per scope, not one


def test_compilation_cache_survives_an_entry_without_access_time(tmp_path):
  """Under a size limit jax reads ``<key>-atime`` of every entry before
  it writes one, so one entry that lost that file stops all caching (the
  chip machine's cache was found so, PERF.md section 6, PR 31).
  enable_compilation_cache() gives such an entry a new access time, and
  the next program is written."""
  placed = tmp_path / 'cache'
  placed.mkdir()
  (placed / 'jit_lost-0123-cache').write_bytes(b'x' * 64)
  code = ('import sys, warnings\n'
          'warnings.simplefilter("error")\n'
          'import jax, jax.numpy as jnp\n'
          'import graphlearn_tpu as glt\n'
          'assert glt.utils.enable_compilation_cache(0.0) == sys.argv[1]\n'
          'jax.jit(lambda x: jnp.sort(x) * 3)(jnp.arange(8.0))'
          '.block_until_ready()\n')
  env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(placed),
             JAX_COMPILATION_CACHE_MAX_SIZE=str(10 ** 9),
             JAX_PLATFORMS='cpu', PYTHONPATH=REPO)
  out = subprocess.run([sys.executable, '-c', code, str(placed)],
                       capture_output=True, text=True, timeout=180,
                       env=env, cwd=str(tmp_path))
  assert out.returncode == 0, out.stderr[-2000:]
  names = sorted(os.listdir(placed))
  assert 'jit_lost-0123-atime' in names
  assert [n for n in names if n.endswith('-cache')
          and not n.startswith('jit_lost')], names


@pytest.mark.slow  # tier-1 budget (PR 19): staged-npz example variant
# — the sub-second example tests stay tier-1, full run already slow
def test_products_staged_npz_path(tmp_path):
  rng = np.random.default_rng(0)
  n, e, ncls, f = 400, 4000, 5, 16
  comm = rng.integers(0, ncls, n)
  rows = rng.integers(0, n, e)
  cols = rng.integers(0, n, e)
  # homophily: rewire 70% of edges to a same-community target so a few
  # epochs actually learn something
  for j in np.flatnonzero(rng.random(e) < 0.7):
    members = np.flatnonzero(comm == comm[rows[j]])
    cols[j] = members[rng.integers(0, len(members))]
  centers = rng.standard_normal((ncls, f)).astype(np.float32)
  feat = centers[comm] * 0.5 + \
      rng.standard_normal((n, f)).astype(np.float32)
  perm = rng.permutation(n)
  np.savez(tmp_path / 'ogbn_products.npz',
           edge_index=np.stack([rows, cols]).astype(np.int64),
           feat=feat, label=comm.astype(np.int64),
           train_idx=perm[:200].astype(np.int64),
           valid_idx=perm[200:250].astype(np.int64),
           test_idx=perm[250:].astype(np.int64))

  env = dict(os.environ, JAX_PLATFORMS='cpu')
  out = subprocess.run(
      [sys.executable, EXAMPLE, '--data-dir', str(tmp_path),
       '--epochs', '8', '--lr', '0.01', '--batch-size', '32', '--fanout', '4', '3',
       '--hidden', '16', '--eval-batches', '3', '--dedup', 'map',
       '--calibrate'],
      capture_output=True, text=True, timeout=600, env=env)
  assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
  line = [ln for ln in out.stdout.splitlines() if ln.startswith('{')][-1]
  res = json.loads(line)
  assert res['source'] == 'ogbn-products (staged)'
  assert res['epochs'] == 8
  assert np.isfinite(res['final_train_loss'])
  assert 0.0 <= res['test_acc'] <= 1.0
  # the staged graph is homophilous + features carry signal: a few epochs
  # must beat chance (1/5) by a wide margin or the staged path is broken
  assert res['test_acc'] > 0.4, res


GATE = os.path.join(REPO, 'examples', 'igbh', 'train_rgnn_gate.py')


@pytest.mark.slow  # tier-1 budget (ROADMAP 870s): full training run
def test_hetero_gate_discriminative_merge_dense():
  """The hetero accuracy gate end to end on its hardest path
  (calibrated caps + dense k-run typed aggregation): a few epochs on
  the typed-homophily synthetic must clear 2x chance by a wide margin
  (observed ~0.38 at this config; chance = 1/8). A semantics bug in
  typed sampling, the calibrated clamps, or the dense hetero conv
  drags accuracy toward chance — this is the hetero counterpart of the
  homo products gate threshold."""
  env = dict(os.environ, JAX_PLATFORMS='cpu')
  out = subprocess.run(
      [sys.executable, GATE, '--conv', 'sage', '--mode', 'merge_dense',
       '--n-paper', '8000', '--n-author', '4000', '--batch-size', '128',
       '--fanout', '6', '4', '--epochs', '6', '--hidden', '48',
       '--feat-dim', '24', '--eval-batches', '15', '--bf16-model'],
      capture_output=True, text=True, timeout=900, env=env)
  assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
  line = [ln for ln in out.stdout.splitlines() if ln.startswith('{')][-1]
  res = json.loads(line)
  assert res['mode'] == 'merge_dense'
  assert np.isfinite(res['final_train_loss'])
  assert res['final_train_loss'] < res['first_train_loss']
  assert res['test_acc'] > 0.27, res   # chance = 0.125


def test_dist_example_builds_its_shards_on_the_devices():
  """examples/distributed/dist_train_sage_supervised.py end to end on a
  4-device virtual CPU mesh: shards placed one partition at a time
  (DistDataset.from_device_shards), caps probed through the mesh sampler,
  exact dedup without overflow, a finite falling loss."""
  script = os.path.join(REPO, 'examples', 'distributed',
                        'dist_train_sage_supervised.py')
  out = subprocess.run(
      [sys.executable, script, '--cpu-devices', '4', '--num-nodes', '3000',
       '--epochs', '2', '--batch-size', '32', '--hidden', '32'],
      capture_output=True, text=True, timeout=280, cwd=REPO)
  assert out.returncode == 0, out.stderr[-2000:]
  res = json.loads(out.stdout.strip().splitlines()[-1])
  assert res['mesh_size'] == 4 and len(res['frontier_caps']) == 2
  assert np.isfinite(res['final_loss'])
  assert res['final_loss'] < res['first_loss']
  assert 'epoch_wall_s' in res


@pytest.mark.parametrize('per_batch', [False, True],
                         ids=['scanned', 'per-batch'])
def test_unsup_example_trains_through_the_scanned_epoch(per_batch):
  """examples/graph_sage_unsup.py end to end at a small size: by default
  the epoch is ScanTrainer's scanned program over the link loader (its
  negative-sampler counters published once an epoch), ``--per-batch`` keeps
  the documented loop; both learn the communities' links."""
  script = os.path.join(REPO, 'examples', 'graph_sage_unsup.py')
  env = dict(os.environ, JAX_PLATFORMS='cpu')
  out = subprocess.run(
      [sys.executable, script, '--epochs', '2', '--num-nodes', '1500',
       '--avg-deg', '8', '--batch-size', '64', '--hidden', '16',
       '--chunk-size', '8'] + (['--per-batch'] if per_batch else []),
      capture_output=True, text=True, timeout=280, cwd=REPO, env=env)
  assert out.returncode == 0, out.stderr[-2000:]
  res = json.loads(out.stdout.strip().splitlines()[-1])
  assert np.isfinite(res['final_loss'])
  assert res['final_loss'] < res['first_loss']
  assert res['test_link_acc'] > 0.55, res        # chance = 0.5
  if per_batch:
    assert res['trainer'] == 'per-batch loop' and not res['negatives']
  else:
    assert res['trainer'] == 'ScanTrainer'
    neg = res['negatives']
    assert neg['link.negatives.tested'] > 0
    assert neg['link.negatives.rejected'] < neg['link.negatives.tested']

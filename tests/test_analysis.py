"""graftlint (graphlearn_tpu/analysis) + guard-rail tests.

Each of the five rules gets positive (seeded violation) AND negative
(contract-following) fixture snippets, then the suppression layers
(pragma, baseline) round-trip, the CLI exit codes, the GLT_STRICT
runtime guards, the bench --validate schema check, and — the gate the
whole PR exists for — a tier-1 run of graftlint over the shipped
package asserting ZERO unsuppressed findings against the (empty)
checked-in baseline.

Fixture files live in tmp_path (no package __init__), so their
package-relative path is just the basename; Config module patterns here
name fixtures by that basename.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from graphlearn_tpu.analysis import core
from graphlearn_tpu.analysis.core import Config, run_lint
from graphlearn_tpu.analysis.lint import main as lint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, 'graphlearn_tpu')


def _write(tmp_path, name, source):
  path = tmp_path / name
  path.write_text(textwrap.dedent(source))
  return str(path)


def _lint(paths, **cfg):
  findings, n_pragma, n_base, modules = run_lint(
      [paths] if isinstance(paths, str) else paths, Config(**cfg))
  return findings, n_pragma, n_base, modules


def _rules(findings):
  return [f.rule for f in findings]


# ----------------------------------------------------------------- host-sync

class TestHostSync:

  def test_item_in_jitted_function_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        @jax.jit
        def step(x):
            v = x.item()
            return v
        ''')
    findings, _, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert _rules(findings) == ['host-sync']
    assert 'item' in findings[0].message
    assert findings[0].symbol == 'step'

  def test_cast_and_device_get_in_scan_body_flagged(self, tmp_path):
    # lax.scan body + np.asarray / int(traced) / jax.device_get: the
    # scan-body root comes from the call-argument form, not a decorator
    p = _write(tmp_path, 'fix.py', '''
        import jax
        import numpy as np
        from jax import lax

        def run(xs, carry):
            def body(c, x):
                n = int(x)
                h = np.asarray(c)
                g = jax.device_get(c)
                return c, (n, h, g)
            return lax.scan(body, carry, xs)
        ''')
    findings, _, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert _rules(findings) == ['host-sync'] * 3

  def test_host_side_and_constant_casts_not_flagged(self, tmp_path):
    # .item() in an untraced host helper, int() of a constant at trace
    # time, and jnp.asarray (device-side) are all fine
    p = _write(tmp_path, 'fix.py', '''
        import jax
        import jax.numpy as jnp

        def host_summary(arr):
            return arr.item()

        @jax.jit
        def step(x):
            width = int(128)
            return jnp.asarray(x) * width
        ''')
    findings, _, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert findings == []

  def test_builtin_map_is_not_a_tracing_root(self, tmp_path):
    # bare builtins (map/filter) must not suffix-match TRACING_CALLS
    # entries like 'lax.map' and mint false traced scopes
    p = _write(tmp_path, 'fix.py', '''
        def summarize(arr):
            return arr.item()

        def host_loop(chunks):
            return list(map(summarize, chunks))
        ''')
    findings, _, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert findings == []

  def test_out_of_scope_module_ignored(self, tmp_path):
    p = _write(tmp_path, 'elsewhere.py', '''
        import jax

        @jax.jit
        def step(x):
            return x.item()
        ''')
    findings, _, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert findings == []


# ----------------------------------------------------------- prng-discipline

class TestPrngDiscipline:

  def test_split_and_carry_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        class Sampler:
            def draw(self):
                self._key, sub = jax.random.split(self._key)
                return sub
        ''')
    findings, _, _, _ = _lint(p, prng_modules=('fix.py',))
    assert _rules(findings) == ['prng-discipline']
    assert 'split-and-carry' in findings[0].message

  def test_prngkey_in_loop_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        def redraw(n):
            out = []
            for i in range(n):
                out.append(jax.random.PRNGKey(0))
            return out
        ''')
    findings, _, _, _ = _lint(p, prng_modules=('fix.py',))
    assert _rules(findings) == ['prng-discipline']
    assert 'inside a loop' in findings[0].message

  def test_key_reuse_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        def two_draws(key, shape):
            a = jax.random.uniform(key, shape)
            b = jax.random.normal(key, shape)
            return a, b
        ''')
    findings, _, _, _ = _lint(p, prng_modules=('fix.py',))
    assert _rules(findings) == ['prng-discipline']
    assert 'key reuse' in findings[0].message

  def test_numpy_host_rng_not_flagged(self, tmp_path):
    # np.random twice on one array is the established loader idiom
    # (node_loader/dist_loader epoch permutations), not jax key reuse
    p = _write(tmp_path, 'fix.py', '''
        import numpy as np

        def two_perms(order):
            a = np.random.permutation(order)
            b = np.random.permutation(order)
            return a, b
        ''')
    findings, _, _, _ = _lint(p, prng_modules=('fix.py',))
    assert findings == []

  def test_counter_pattern_not_flagged(self, tmp_path):
    # the contract pattern: fold_in(base, count) per call, fresh name
    # per draw — the exact _keys_for shape DistNeighborSampler uses
    p = _write(tmp_path, 'fix.py', '''
        import jax

        class Sampler:
            def _keys_for(self, count, nparts):
                k = jax.random.fold_in(self._key, count)
                return jax.random.split(k, nparts)

            def draw(self, key, shape):
                ka = jax.random.fold_in(key, 1)
                a = jax.random.uniform(ka, shape)
                kb = jax.random.fold_in(key, 2)
                b = jax.random.uniform(kb, shape)
                return a, b
        ''')
    findings, _, _, _ = _lint(p, prng_modules=('fix.py',))
    assert findings == []


# --------------------------------------------- dispatch-instrumentation

class TestDispatchInstrumentation:

  def test_uninstrumented_jit_dispatch_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        def _body(x):
            return x + 1

        class Runner:
            def __init__(self):
                self._fn = jax.jit(_body)

            def run(self, x):
                return self._fn(x)
        ''')
    findings, _, _, _ = _lint(p, dispatch_modules=('fix.py',))
    assert _rules(findings) == ['dispatch-instrumentation']
    assert findings[0].symbol == 'Runner.run'

  def test_record_dispatch_before_call_ok(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax
        from graphlearn_tpu.utils.trace import record_dispatch

        def _body(x):
            return x + 1

        class Runner:
            def __init__(self):
                self._fn = jax.jit(_body)

            def run(self, x):
                record_dispatch('runner')
                return self._fn(x)
        ''')
    findings, _, _, _ = _lint(p, dispatch_modules=('fix.py',))
    assert findings == []

  def test_wrap_dispatch_product_ok(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax
        from graphlearn_tpu.utils.trace import wrap_dispatch

        def _body(x):
            return x + 1

        class Runner:
            def __init__(self):
                self._fn = wrap_dispatch('runner', jax.jit(_body))

            def run(self, x):
                return self._fn(x)
        ''')
    findings, _, _, _ = _lint(p, dispatch_modules=('fix.py',))
    assert findings == []

  def test_jit_of_jit_composition_ok(self, tmp_path):
    # calling a jitted handle INSIDE a traced function composes into
    # the outer program — instrumenting there would miscount
    p = _write(tmp_path, 'fix.py', '''
        import jax
        from graphlearn_tpu.utils.trace import record_dispatch

        inner = jax.jit(lambda x: x * 2)

        @jax.jit
        def outer(x):
            return inner(x) + 1

        def launch(x):
            record_dispatch('outer')
            return outer(x)
        ''')
    findings, _, _, _ = _lint(p, dispatch_modules=('fix.py',))
    assert findings == []


# ----------------------------------------------------------- compat-shard-map

class TestCompatShardMap:

  @pytest.mark.parametrize('src', [
      'from jax.experimental.shard_map import shard_map\n',
      'from jax.experimental import shard_map\n',
      'import jax.experimental.shard_map as shard_map\n',
      'import jax\nfn = jax.shard_map\n',
  ])
  def test_direct_shard_map_flagged(self, tmp_path, src):
    p = _write(tmp_path, 'fix.py', src)
    findings, _, _, _ = _lint(p)
    assert 'compat-shard-map' in _rules(findings)

  def test_compat_module_itself_exempt(self, tmp_path):
    p = _write(tmp_path, 'compat_fix.py',
               'from jax.experimental.shard_map import shard_map\n')
    findings, _, _, _ = _lint(p, compat_module='compat_fix.py')
    assert findings == []

  def test_compat_import_ok(self, tmp_path):
    p = _write(tmp_path, 'fix.py',
               'from graphlearn_tpu.utils.compat import shard_map\n')
    findings, _, _, _ = _lint(p)
    assert findings == []


# ------------------------------------------------------ fault-point-coverage

class TestFaultPointCoverage:

  def _registry(self, tmp_path, names):
    body = ',\n            '.join(f'{n!r}' for n in names)
    return _write(tmp_path, 'faults_fix.py', f'''
        REGISTERED_SITES = frozenset({{
            {body}
        }})
        ''')

  def _doc(self, tmp_path, names):
    doc_dir = tmp_path / 'docs'
    doc_dir.mkdir(exist_ok=True)
    rows = '\n'.join(f'| `{n}` | somewhere | raise |' for n in names)
    (doc_dir / 'failure_model.md').write_text(
        f'# Failure model\n\n| Site | Location | Arming |\n'
        f'| --- | --- | --- |\n{rows}\n')

  def _cfg(self, tmp_path):
    return dict(fault_registry_module='faults_fix.py',
                repo_root=str(tmp_path))

  def test_clean_inventory_passes(self, tmp_path):
    reg = self._registry(tmp_path, ['a.b', 'c.d'])
    self._doc(tmp_path, ['a.b', 'c.d'])
    sites = _write(tmp_path, 'sites.py', '''
        from graphlearn_tpu.utils.faults import fault_point

        def f():
            fault_point('a.b')

        def g():
            fault_point('c.d')
        ''')
    findings, _, _, _ = _lint([reg, sites], **self._cfg(tmp_path))
    assert findings == []

  def test_unregistered_and_undocumented_flagged(self, tmp_path):
    reg = self._registry(tmp_path, ['a.b'])
    self._doc(tmp_path, ['a.b'])
    sites = _write(tmp_path, 'sites.py', '''
        from graphlearn_tpu.utils.faults import fault_point

        def f():
            fault_point('a.b')

        def g():
            fault_point('rogue.site')
        ''')
    findings, _, _, _ = _lint([reg, sites], **self._cfg(tmp_path))
    msgs = [f.message for f in findings]
    assert _rules(findings) == ['fault-point-coverage'] * 2
    assert any('REGISTERED_SITES' in m for m in msgs)
    assert any('not documented' in m for m in msgs)

  def test_duplicate_site_flagged(self, tmp_path):
    reg = self._registry(tmp_path, ['a.b'])
    self._doc(tmp_path, ['a.b'])
    sites = _write(tmp_path, 'sites.py', '''
        from graphlearn_tpu.utils.faults import fault_point

        def f():
            fault_point('a.b')

        def g():
            fault_point('a.b')
        ''')
    findings, _, _, _ = _lint([reg, sites], **self._cfg(tmp_path))
    assert any('duplicate fault site' in f.message for f in findings)

  def test_stale_registration_flagged(self, tmp_path):
    reg = self._registry(tmp_path, ['a.b', 'ghost.site'])
    self._doc(tmp_path, ['a.b', 'ghost.site'])
    sites = _write(tmp_path, 'sites.py', '''
        from graphlearn_tpu.utils.faults import fault_point

        def f():
            fault_point('a.b')
        ''')
    findings, _, _, _ = _lint([reg, sites], **self._cfg(tmp_path))
    assert any('stale registration' in f.message for f in findings)

  def test_computed_name_flagged(self, tmp_path):
    reg = self._registry(tmp_path, ['a.b'])
    self._doc(tmp_path, ['a.b'])
    sites = _write(tmp_path, 'sites.py', '''
        from graphlearn_tpu.utils.faults import fault_point

        def f(which):
            fault_point('site.' + which)
        ''')
    findings, _, _, _ = _lint([reg, sites], **self._cfg(tmp_path))
    assert any('string literal' in f.message for f in findings)


# -------------------------------------------------------------- hetero-gate

class TestHeteroGate:

  def test_bare_raise_and_warn_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import warnings

        def check(self):
            if self.is_hetero:
                raise ValueError('homogeneous-only')

        def check_soft(ds):
            if getattr(ds, 'is_hetero', False):
                warnings.warn('hetero path unvalidated')
        ''')
    findings, _, _, _ = _lint(p)
    assert _rules(findings) == ['hetero-gate', 'hetero-gate']
    assert 'CapacityPlanError' in findings[0].message
    assert 'docs/capacity_plans.md' in findings[0].message

  def test_else_branch_raise_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        def check(ds):
            if not ds.is_hetero:
                pass
            else:
                raise NotImplementedError('typed stores unsupported')
        ''')
    findings, _, _, _ = _lint(p)
    assert _rules(findings) == ['hetero-gate']

  def test_capacity_plan_error_ok(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        from graphlearn_tpu.sampler import CapacityPlanError

        def check(self):
            if self.is_hetero:
                raise CapacityPlanError(
                    'Trainer', 'per-ntype feature stores')
        ''')
    findings, _, _, _ = _lint(p)
    assert findings == []

  def test_nested_raise_not_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        def deep(self, parts):
            if self.is_hetero:
                for part in parts:
                    if part is None:
                        raise ValueError('bad partition input')
        ''')
    findings, _, _, _ = _lint(p)
    assert findings == []

  def test_bare_reraise_not_flagged(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        def fwd(self, exc):
            try:
                self._run()
            except Exception:
                if self.is_hetero:
                    raise
        ''')
    findings, _, _, _ = _lint(p)
    assert findings == []

  def test_pragma_suppresses(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        def check(self):
            if self.is_hetero:
                # graftlint: allow[hetero-gate] homo accessor by contract
                raise ValueError('homo-only accessor')
        ''')
    findings, n_pragma, _, _ = _lint(p)
    assert findings == []
    assert n_pragma == 1


# ------------------------------------------------------------------ pragmas

class TestPragmas:

  SRC_VIOLATION = '''
      import jax

      @jax.jit
      def step(x):
          return x.item(){pragma}
      '''

  def test_same_line_pragma_suppresses(self, tmp_path):
    p = _write(tmp_path, 'fix.py', self.SRC_VIOLATION.format(
        pragma='  # graftlint: allow[host-sync] epoch-boundary fetch'))
    findings, n_pragma, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert findings == []
    assert n_pragma == 1

  def test_line_above_pragma_suppresses(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        @jax.jit
        def step(x):
            # graftlint: allow[host-sync] epoch-boundary fetch
            return x.item()
        ''')
    findings, n_pragma, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert findings == []
    assert n_pragma == 1

  def test_pragma_without_reason_is_a_finding(self, tmp_path):
    p = _write(tmp_path, 'fix.py', self.SRC_VIOLATION.format(
        pragma='  # graftlint: allow[host-sync]'))
    findings, _, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert 'pragma' in _rules(findings)
    assert any('needs a reason' in f.message for f in findings)

  def test_unknown_rule_pragma_is_a_finding(self, tmp_path):
    p = _write(tmp_path, 'fix.py', self.SRC_VIOLATION.format(
        pragma='  # graftlint: allow[no-such-rule] because'))
    findings, _, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert any('unknown rule' in f.message for f in findings)

  def test_pragma_only_suppresses_named_rule(self, tmp_path):
    p = _write(tmp_path, 'fix.py', self.SRC_VIOLATION.format(
        pragma='  # graftlint: allow[prng-discipline] wrong rule'))
    findings, _, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert 'host-sync' in _rules(findings)

  def test_docstring_lookalike_inert(self, tmp_path):
    # the pragma syntax mentioned in a docstring is not a pragma (and
    # not a malformed-pragma finding either): comments are tokenized
    p = _write(tmp_path, 'fix.py', '''
        def helper():
            """Suppress with '# graftlint: allow[host-sync] why'."""
            return 1
        ''')
    findings, n_pragma, _, _ = _lint(p, hot_sync_modules=('fix.py',))
    assert findings == []
    assert n_pragma == 0


# ------------------------------------------------------------------ baseline

class TestBaseline:

  def test_round_trip_suppresses_then_catches_new(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        @jax.jit
        def step(x):
            return x.item()
        ''')
    cfg = Config(hot_sync_modules=('fix.py',))
    findings, _, _, modules = run_lint([p], cfg)
    assert len(findings) == 1

    base_path = str(tmp_path / 'graftlint.baseline.json')
    core.write_baseline(base_path, findings, modules)
    baseline = core.load_baseline(base_path)
    assert len(baseline) == 1

    live, _, n_base, _ = run_lint([p], cfg, baseline)
    assert live == [] and n_base == 1

    # a NEW violation in the same file is not absorbed by the baseline
    with open(p, 'a') as fh:
      fh.write('\n\n@jax.jit\ndef step2(x):\n    return x.tolist()\n')
    live, _, n_base, _ = run_lint([p], cfg, baseline)
    assert len(live) == 1 and n_base == 1
    assert 'tolist' in live[0].message

  def test_fingerprints_survive_line_motion(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        @jax.jit
        def step(x):
            return x.item()
        ''')
    cfg = Config(hot_sync_modules=('fix.py',))
    findings, _, _, modules = run_lint([p], cfg)
    fps = core.fingerprints_for(findings, modules)

    # shift the whole file down: fingerprints hash line TEXT, not number
    src = open(p).read()
    open(p, 'w').write('# a new leading comment\n' + src)
    findings2, _, _, modules2 = run_lint([p], cfg)
    assert core.fingerprints_for(findings2, modules2) == fps

  def test_identical_violations_get_distinct_slots(self, tmp_path):
    p = _write(tmp_path, 'fix.py', '''
        import jax

        @jax.jit
        def a(x):
            return x.item()

        @jax.jit
        def b(x):
            return x.item()
        ''')
    cfg = Config(hot_sync_modules=('fix.py',))
    findings, _, _, modules = run_lint([p], cfg)
    assert len(findings) == 2
    fps = core.fingerprints_for(findings, modules)
    assert len(set(fps)) == 2

  def test_rejects_foreign_json(self, tmp_path):
    bad = tmp_path / 'graftlint.baseline.json'
    bad.write_text('{"some": "other file"}')
    with pytest.raises(ValueError):
      core.load_baseline(str(bad))


# ----------------------------------------------------------------------- CLI

class TestCli:

  def test_list_rules(self, capsys):
    assert lint_main(['--list-rules']) == 0
    out = capsys.readouterr().out
    for rule in core.PRAGMA_RULES:
      assert rule in out

  def test_no_paths_is_usage_error(self):
    assert lint_main([]) == 2

  def test_exit_one_on_findings_zero_when_clean(self, tmp_path, capsys):
    bad = _write(tmp_path, 'fix.py',
                 'from jax.experimental.shard_map import shard_map\n')
    assert lint_main([bad, '--no-baseline']) == 1
    assert 'compat-shard-map' in capsys.readouterr().out
    good = _write(tmp_path, 'ok.py', 'x = 1\n')
    assert lint_main([good, '--no-baseline']) == 0

  def test_write_baseline_flow(self, tmp_path, capsys):
    _write(tmp_path, 'fix.py',
           'from jax.experimental.shard_map import shard_map\n')
    base = str(tmp_path / 'graftlint.baseline.json')
    assert lint_main([str(tmp_path), '--baseline', base,
                      '--write-baseline']) == 0
    capsys.readouterr()
    assert lint_main([str(tmp_path), '--baseline', base]) == 0
    assert 'baselined' in capsys.readouterr().out


# --------------------------------------------------------- tier-1 gate

class TestPackageClean:
  """The acceptance gate: graftlint over the shipped package is clean,
  and the checked-in baseline is EMPTY (accepted debt is a decision,
  not a default — docs/static_analysis.md)."""

  def test_checked_in_baseline_is_empty(self):
    baseline = core.load_baseline(
        os.path.join(REPO, 'graftlint.baseline.json'))
    assert baseline == set()

  def test_graftlint_clean_over_package(self):
    findings, _, n_base, modules = run_lint([PKG], Config())
    assert n_base == 0
    assert findings == [], 'graftlint findings:\n' + '\n'.join(
        f.render() for f in findings)
    assert len(modules) > 50   # really walked the package

  @pytest.mark.slow  # tier-1 budget (PR 19): CLI-surface variant
  # of the same package walk — test_graftlint_clean_over_package
  # stays the tier-1 zero-findings rep
  def test_cli_entrypoint_clean(self):
    proc = subprocess.run(
        [sys.executable, '-m', 'graphlearn_tpu.analysis.lint',
         'graphlearn_tpu/'],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -------------------------------------------------------- strict guard rails

class TestStrictGuards:

  def test_disabled_by_default(self, monkeypatch):
    from graphlearn_tpu.utils.strict import strict_enabled, strict_guards
    monkeypatch.delenv('GLT_STRICT', raising=False)
    assert not strict_enabled()
    with strict_guards():      # no-op path
      pass
    monkeypatch.setenv('GLT_STRICT', '0')
    assert not strict_enabled()
    monkeypatch.setenv('GLT_STRICT', '1')
    assert strict_enabled()

  def test_guard_rejects_implicit_transfer(self, monkeypatch):
    import jax
    import jax.numpy as jnp
    from graphlearn_tpu.utils.strict import strict_guards
    monkeypatch.setenv('GLT_STRICT', '1')
    dev = jnp.arange(4.0)
    host = np.arange(4.0)
    with pytest.raises(Exception, match='[Tt]ransfer'):
      with strict_guards():
        _ = dev + host          # implicit host->device transfer
    # explicit device_put stays allowed inside the guard
    with strict_guards():
      ok = dev + jax.device_put(host)
    assert np.allclose(np.asarray(ok), np.arange(4.0) * 2)

  def test_guard_noop_when_disabled(self, monkeypatch):
    import jax.numpy as jnp
    from graphlearn_tpu.utils.strict import strict_guards
    monkeypatch.setenv('GLT_STRICT', '0')
    with strict_guards():
      out = jnp.arange(4.0) + np.arange(4.0)
    assert np.allclose(np.asarray(out), np.arange(4.0) * 2)

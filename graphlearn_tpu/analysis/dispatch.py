"""Rule dispatch-instrumentation: every jitted entrypoint dispatch in a
hot module must be counted.

The dispatch-budget asserts (tests/test_scan_epoch.py,
tests/test_dist_scan_epoch.py) and the benchmark's
``dispatches_per_step`` (perfbench/layer_metrics/) are only meaningful if EVERY hot-path program launch calls
``utils.trace.record_dispatch`` at its dispatch site (or is wrapped in
``wrap_dispatch``). An un-instrumented ``jax.jit`` entrypoint silently
deflates the counted budget — the budget test keeps passing while the
epoch quietly pays more dispatches than it asserts (exactly the
regression PERF.md's wall-clock-scales-with-dispatches finding makes
expensive).

Model (per module, name-based dataflow):

  * ``jax.jit(...)`` / ``shard_map(...)`` call results are HANDLES.
  * Handles propagate through local names, ``self.attr`` stores,
    container stores (``self._fns[k] = jfn``), returns (making the
    enclosing def a FACTORY), and calls of factories — plus the
    cross-module factories named in ``Config.known_jit_factories``.
  * A CALL of a handle is a dispatch site. It is fine when (a) the
    enclosing function is traced (jit-of-jit composes into the outer
    program — instrumenting there would count per trace, not per call),
    (b) ``record_dispatch``/``wrap_dispatch`` appears lexically before
    it in the same function, or (c) the enclosing function itself
    becomes a handle (a dispatch wrapper like DistFeature._build_fn's
    ``run``) whose OWN call sites are then checked — the fixpoint walks
    the wrapping chain up to wherever instrumentation must live.
  * Anything left is a finding at the original call site.
"""
import ast
from typing import Dict, List, Optional, Set

from . import astutil
from .core import Config, Finding, ParsedModule, in_scope

RULE = 'dispatch-instrumentation'

_INSTRUMENT_CALLS = ('record_dispatch', 'wrap_dispatch')


def check_package(modules: List[ParsedModule], config: Config):
  findings = []
  for mod in modules:
    if not in_scope(mod.relpath, config.dispatch_modules):
      continue
    findings.extend(_check_module(mod, config))
  return findings


class _ModuleState:
  def __init__(self, mod: ParsedModule, config: Config):
    self.mod = mod
    self.index = astutil.FuncIndex(mod.tree)
    self.aliases = astutil.import_aliases(mod.tree)
    self.traced = astutil.traced_functions(self.index, mod.tree,
                                           self.aliases)
    self.parents = astutil.parent_map(mod.tree)
    # handle identities: local names are scoped per function qualname
    self.attr_handles: Set[str] = set()        # self.<attr> is a handle
    self.container_attrs: Set[str] = set()     # self.<attr>[...] handles
    self.factories: Set[str] = set(config.known_jit_factories)
    self.local_handles: Dict[str, Set[str]] = {}  # fn qual -> names
    self.wrapped: Set[str] = set()             # wrap_dispatch products

  def scope_of(self, node) -> str:
    fi = astutil.enclosing_function(self.index, node, self.parents)
    return fi.qualname if fi else '<module>'


def _check_module(mod: ParsedModule, config: Config) -> List[Finding]:
  st = _ModuleState(mod, config)
  _seed_handles(st)
  sites = _propagate(st)
  out = []
  for call, qual in sites:
    out.append(Finding(
        RULE, mod.path, mod.relpath, call.lineno, call.col_offset + 1,
        'jitted program dispatched without instrumentation — call '
        'utils.trace.record_dispatch(<site>) immediately before the '
        'dispatch (or build the callable with wrap_dispatch) so the '
        'epoch dispatch budgets stay exact', symbol=qual))
  return out


def _is_handle_expr(st: _ModuleState, node: ast.AST, scope: str) -> bool:
  """Does this expression evaluate to a jitted callable?"""
  if isinstance(node, ast.Call):
    name = astutil.call_name(node)
    seg = astutil.last_segment(name)
    if seg == 'jit' or seg == 'shard_map':
      return True
    if seg == 'wrap_dispatch':
      return False    # instrumented at build — never a violation
    if seg in st.factories:
      return True
    return False
  if isinstance(node, ast.Name):
    return node.id in st.local_handles.get(scope, set()) or \
        node.id in st.local_handles.get('<module>', set())
  if isinstance(node, ast.Attribute):
    return node.attr in st.attr_handles
  if isinstance(node, ast.Subscript):
    base = node.value
    if isinstance(base, ast.Attribute) and base.attr in st.container_attrs:
      return True
    if isinstance(base, ast.Name):
      return base.id in st.local_handles.get(scope, set())
    return False
  if isinstance(node, ast.Tuple):
    return any(_is_handle_expr(st, e, scope) for e in node.elts)
  return False


def _seed_handles(st: _ModuleState):
  """First pass: direct jit/shard_map/factory results into names."""
  changed = True
  while changed:
    changed = False
    for node in ast.walk(st.mod.tree):
      if isinstance(node, ast.Assign):
        scope = st.scope_of(node)
        if _is_handle_expr(st, node.value, scope):
          for t in node.targets:
            changed |= _bind_target(st, t, scope)
      elif isinstance(node, ast.Return) and node.value is not None:
        scope = st.scope_of(node)
        if scope != '<module>' and \
            _is_handle_expr(st, node.value, scope):
          fn_name = scope.rsplit('.', 1)[-1]
          if fn_name not in st.factories:
            st.factories.add(fn_name)
            changed = True


def _bind_target(st: _ModuleState, t: ast.AST, scope: str) -> bool:
  if isinstance(t, ast.Name):
    s = st.local_handles.setdefault(scope, set())
    if t.id not in s:
      s.add(t.id)
      return True
  elif isinstance(t, ast.Attribute):
    if t.attr not in st.attr_handles:
      st.attr_handles.add(t.attr)
      return True
  elif isinstance(t, ast.Subscript):
    base = t.value
    if isinstance(base, ast.Attribute) and \
        base.attr not in st.container_attrs:
      st.container_attrs.add(base.attr)
      return True
  elif isinstance(t, ast.Tuple):
    return any(_bind_target(st, e, scope) for e in t.elts)
  return False


def _propagate(st: _ModuleState):
  """Fixpoint: find uninstrumented handle-call sites; a plain function
  containing one becomes a handle itself (its callers must instrument),
  until no new handles appear. Returns surviving violation sites."""
  for _round in range(20):
    sites = _dispatch_sites(st)
    new_handle = False
    for call, qual in sites:
      if qual == '<module>':
        continue
      fn_name = qual.rsplit('.', 1)[-1]
      fi = st.index.by_qual.get(qual)
      referenced = fi is not None and _is_referenced(st, fi)
      if referenced and fn_name not in st.factories and \
          not _name_is_handle(st, fn_name):
        # the wrapper itself dispatches: its call sites take over
        if fi.is_nested or fi.parent is not None:
          st.local_handles.setdefault(
              _parent_scope(fi), set()).add(fn_name)
        else:
          st.attr_handles.add(fn_name)
        new_handle = True
    if not new_handle:
      return [s for s in sites if not _excused(st, s)]
    _seed_handles(st)   # re-run: new handles may flow into factories
  return [s for s in _dispatch_sites(st) if not _excused(st, s)]


def _parent_scope(fi: astutil.FuncInfo) -> str:
  return fi.parent.qualname if fi.parent is not None else '<module>'


def _name_is_handle(st: _ModuleState, name: str) -> bool:
  if name in st.attr_handles:
    return True
  return any(name in s for s in st.local_handles.values())


def _is_referenced(st: _ModuleState, fi: astutil.FuncInfo) -> bool:
  """Is this def stored/returned/called anywhere else in the module?"""
  name = fi.node.name
  for node in ast.walk(st.mod.tree):
    if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) and \
        node.id == name:
      f = astutil.enclosing_function(st.index, node, st.parents)
      if f is None or f.qualname != fi.qualname:
        return True
    if isinstance(node, ast.Attribute) and node.attr == name and \
        not isinstance(node.ctx, ast.Store):
      return True
  return False


def _excused(st: _ModuleState, site) -> bool:
  call, qual = site
  fn_name = qual.rsplit('.', 1)[-1] if qual != '<module>' else ''
  # the enclosing fn became a handle/factory: checking moved to callers
  if fn_name and (fn_name in st.factories or _name_is_handle(st, fn_name)):
    fi = st.index.by_qual.get(qual)
    return fi is not None and _is_referenced(st, fi)
  return False


def _dispatch_sites(st: _ModuleState):
  """(call, enclosing-qualname) of uninstrumented handle calls."""
  sites = []
  for node in ast.walk(st.mod.tree):
    if not isinstance(node, ast.Call):
      continue
    if not _is_handle_expr(st, node.func, st.scope_of(node)):
      continue
    # a handle mentioned as a factory call's FUNC of form
    # self._chunk_fn_for(k)(...): func is a Call -> dispatch of its result
    fi = astutil.enclosing_function(st.index, node, st.parents)
    qual = fi.qualname if fi else '<module>'
    if fi is not None and fi.qualname in st.traced:
      continue                      # jit-of-jit: composes, not dispatches
    if _instrumented_before(st, fi, node):
      continue
    sites.append((node, qual))
  return sites


def _instrumented_before(st: _ModuleState, fi: Optional[astutil.FuncInfo],
                         call: ast.Call) -> bool:
  if fi is None:
    return False
  for node in st.index.own_nodes(fi):
    if isinstance(node, ast.Call) and \
        astutil.last_segment(astutil.call_name(node)) in \
        _INSTRUMENT_CALLS and node.lineno <= call.lineno:
      return True
  return False

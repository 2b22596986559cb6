"""Trace-hygiene checker: host-sync and recompile hazards in the hot loop.

The engine's throughput story rests on ONE compiled (chunk, decode) step
pair serving every request mix — no per-tick recompiles, no hidden
device->host syncs beyond the explicit ``jax.device_get`` at each step's
single read-back point.  Four static rules plus a runtime harness:

* ``host-sync`` — two scopes.  (a) In ``ServingEngine`` methods reachable
  from the ``run()``/``tick()`` hot loop (computed from the intra-class
  call graph), any ``.item()`` call, or ``float()``/``int()``/
  ``np.asarray()`` applied to a step-function result that was not first
  materialized through ``jax.device_get`` — each is an implicit blocking
  sync the profiler won't attribute.  (b) In ``core/steps.py``'s *traced*
  bodies (functions nested inside the ``make_*_step`` builders), any
  ``.item()``/``float()``/``int()``/``np.asarray()``/``np.array()`` on a
  non-``.shape`` value — on a tracer these either crash or silently
  constant-fold at trace time.
* ``missing-donation`` — every ``jax.jit`` call site in ``serving/`` and
  ``launch/serve.py`` must pass ``donate_argnums``/``donate_argnames``:
  these jits wrap step functions that thread the multi-MB cache through
  every tick, and the seed's train/dryrun paths set the donation
  precedent (launch/train.py, launch/dryrun.py).  Without donation the
  pool is double-buffered across every step call.
* ``traced-shape`` — a call to a jitted step attribute (``self.*_fn``)
  whose argument contains a slice with a non-constant Python bound: the
  bound becomes part of the traced shape, so every distinct value
  recompiles (the paged engine exists to avoid exactly this).
* ``async-barrier`` — the pipelined engine's overlap contract: in
  ``ServingEngine`` methods reachable from the plan/dispatch phases
  (``_plan_phase``/``_dispatch_phase``, via the intra-class call graph),
  any ``jax.device_get``, ``.block_until_ready()`` or ``.item()`` — a
  host barrier there serializes the host against the device mid-pipeline,
  silently destroying the one-tick-ahead overlap.  Barriers belong only
  at collect points (``_collect_phase``, which the rule does not scan).
  Scope note: the rule names the three explicit barrier forms;
  ``np.asarray`` on a device value also syncs but is covered by the
  ``host-sync`` taint rule where it matters (step-function results).

Runtime harness (``run_recompile_harness``): builds a tiny paged engine on
the paper's TinyLlama config, drives a mixed-length request batch to
completion tick by tick, and asserts every jitted step function gains
ZERO new jit cache entries after the first tick that used it.  (The
first use itself may insert two entries — the initial call sees
uncommitted host arrays while every later call sees the step's own
committed output — so the contract is no *growth* after first use, which
is exactly what a per-length retrace would violate.)
"""
from __future__ import annotations

import ast

from repro.analysis.core import iter_sources, scope_name

TARGETS = ["src/repro/serving", "src/repro/launch/serve.py",
           "src/repro/core/steps.py"]
ENGINE_PATH = "src/repro/serving/engine.py"
DONATION_PATHS = ("src/repro/serving/", "src/repro/launch/serve.py")
HOT_ROOTS = {"run", "tick"}
ASYNC_ROOTS = {"_plan_phase", "_dispatch_phase"}
HOST_CONVERTERS = {"float", "int"}
NP_CONVERTERS = {"asarray", "array"}


def _attr_chain(node) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_step_fn_attr(func) -> bool:
    return isinstance(func, ast.Attribute) and func.attr.endswith("_fn")


def _contains_device_get(node) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call) and \
                _attr_chain(n.func).endswith("device_get"):
            return True
    return False


# ---------------------------------------------------------------------------
# engine hot loop
# ---------------------------------------------------------------------------

def _reachable_methods(cls: ast.ClassDef, roots) -> dict:
    """Methods transitively reachable from ``roots`` via self.X() calls.
    -> {name: FunctionDef}."""
    methods = {n.name: n for n in cls.body
               if isinstance(n, ast.FunctionDef)}
    edges = {}
    for name, fn in methods.items():
        out = set()
        for n in ast.walk(fn):
            if isinstance(n, ast.Call) and \
                    isinstance(n.func, ast.Attribute) and \
                    isinstance(n.func.value, ast.Name) and \
                    n.func.value.id == "self" and \
                    n.func.attr in methods:
                out.add(n.func.attr)
        edges[name] = out
    seen = set()
    frontier = [r for r in roots if r in methods]
    while frontier:
        m = frontier.pop()
        if m in seen:
            continue
        seen.add(m)
        frontier.extend(edges[m] - seen)
    return {m: methods[m] for m in seen}


def _engine_hot_methods(cls: ast.ClassDef) -> dict:
    """Methods transitively reachable from run()/tick() via self.X() calls.
    -> {name: FunctionDef}."""
    return _reachable_methods(cls, HOT_ROOTS)


def _scan_hot_method(src, cls_name, fn, findings):
    scope = f"{cls_name}.{fn.name}"
    # names bound from step-function calls: logits, self.cache = self.X_fn()
    tainted = set()
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call) \
                and _is_step_fn_attr(n.value.func):
            for t in n.targets:
                elts = t.elts if isinstance(t, ast.Tuple) else [t]
                for e in elts:
                    if isinstance(e, ast.Name):
                        tainted.add(e.id)
    for n in ast.walk(fn):
        if not isinstance(n, ast.Call):
            continue
        if isinstance(n.func, ast.Attribute) and n.func.attr == "item":
            findings.append(src.finding(
                "host-sync", n,
                ".item() in the engine hot loop blocks on the device "
                "per element — batch through one jax.device_get instead",
                scope))
            continue
        chain = _attr_chain(n.func)
        is_conv = (isinstance(n.func, ast.Name)
                   and n.func.id in HOST_CONVERTERS) or \
            (chain.startswith("np.") and chain.split(".")[-1]
             in NP_CONVERTERS)
        if not (is_conv and n.args):
            continue
        arg = n.args[0]
        arg_names = {x.id for x in ast.walk(arg) if isinstance(x, ast.Name)}
        if arg_names & tainted and not _contains_device_get(arg):
            findings.append(src.finding(
                "host-sync", n,
                f"{chain or n.func.id}(...) on a step-function result "
                f"without jax.device_get — an implicit blocking sync in "
                f"the per-tick path", scope))
        # traced-shape: self.*_fn(... x[:, :S] ...) with a variable bound
    for n in ast.walk(fn):
        if not (isinstance(n, ast.Call) and _is_step_fn_attr(n.func)):
            continue
        for a in n.args:
            for s in ast.walk(a):
                if not isinstance(s, ast.Subscript):
                    continue
                slices = s.slice.elts if isinstance(s.slice, ast.Tuple) \
                    else [s.slice]
                for sl in slices:
                    if isinstance(sl, ast.Slice) and any(
                            b is not None and not isinstance(b, ast.Constant)
                            for b in (sl.lower, sl.upper)):
                        findings.append(src.finding(
                            "traced-shape", s,
                            f"argument of {_attr_chain(n.func)}(...) is "
                            f"sliced by a per-request Python value — the "
                            f"bound becomes a traced shape and every "
                            f"distinct value recompiles the step", scope))


def _scan_async_method(src, cls_name, fn, findings):
    """Flag host barriers inside the plan/dispatch closure — between
    dispatching a tick's steps and the next plan phase, the host must
    never block on the device (barriers belong at collect points)."""
    scope = f"{cls_name}.{fn.name}"
    for n in ast.walk(fn):
        if isinstance(n, ast.Call):
            chain = _attr_chain(n.func)
            if chain.endswith("device_get"):
                findings.append(src.finding(
                    "async-barrier", n,
                    "jax.device_get in the plan/dispatch path blocks the "
                    "host on in-flight device work — read results at the "
                    "collect point instead", scope))
                continue
            if isinstance(n.func, ast.Attribute) and \
                    n.func.attr in ("block_until_ready", "item"):
                findings.append(src.finding(
                    "async-barrier", n,
                    f".{n.func.attr}() in the plan/dispatch path is a "
                    f"host barrier mid-pipeline — it serializes planning "
                    f"against the dispatched step and destroys the "
                    f"one-tick-ahead overlap", scope))


def _scan_engine(src, findings):
    for node in src.tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "ServingEngine":
            for _, fn in sorted(_engine_hot_methods(node).items()):
                _scan_hot_method(src, node.name, fn, findings)
            for _, fn in sorted(_reachable_methods(node,
                                                   ASYNC_ROOTS).items()):
                _scan_async_method(src, node.name, fn, findings)


# ---------------------------------------------------------------------------
# traced bodies in core/steps.py
# ---------------------------------------------------------------------------

def _scan_traced_bodies(src, findings):
    for node in src.tree.body:
        if not (isinstance(node, ast.FunctionDef)
                and node.name.startswith("make_")):
            continue
        for inner in ast.walk(node):
            if not (isinstance(inner, ast.FunctionDef)
                    and inner is not node):
                continue
            scope = f"{node.name}.{inner.name}"
            for n in ast.walk(inner):
                if not isinstance(n, ast.Call):
                    continue
                chain = _attr_chain(n.func)
                bad = (isinstance(n.func, ast.Attribute)
                       and n.func.attr == "item") or \
                    (chain.startswith("np.")
                     and chain.split(".")[-1] in NP_CONVERTERS)
                if not bad or not (n.args or isinstance(n.func,
                                                        ast.Attribute)):
                    continue
                probe = n.args[0] if n.args else n.func.value
                txt = ast.unparse(probe)
                if txt.endswith((".shape", ".size", ".ndim", ".dtype")):
                    continue   # static metadata, not a tracer read
                findings.append(src.finding(
                    "host-sync", n,
                    f"{chain}(...) inside a traced step body — on a "
                    f"tracer this crashes or constant-folds at trace "
                    f"time", scope))


# ---------------------------------------------------------------------------
# donation at jit call sites
# ---------------------------------------------------------------------------

def _scan_donation(src, findings):
    if not any(src.path == p or src.path.startswith(p)
               for p in DONATION_PATHS):
        return

    class V(ast.NodeVisitor):
        def __init__(self):
            self.stack = []

        def visit_FunctionDef(self, node):
            self.stack.append(node)
            self.generic_visit(node)
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_ClassDef(self, node):
            self.stack.append(node)
            self.generic_visit(node)
            self.stack.pop()

        def visit_Call(self, node):
            if _attr_chain(node.func) == "jax.jit":
                kw = {k.arg for k in node.keywords}
                if not kw & {"donate_argnums", "donate_argnames"}:
                    findings.append(src.finding(
                        "missing-donation", node,
                        "jax.jit without donate_argnums: the cache "
                        "argument is threaded through every tick and gets "
                        "double-buffered without donation (precedent: "
                        "launch/train.py, launch/dryrun.py)",
                        scope_name(self.stack)))
            self.generic_visit(node)

    V().visit(src.tree)


def scan_source(src) -> list:
    findings = []
    if src.path == ENGINE_PATH:
        _scan_engine(src, findings)
    if src.path.endswith("core/steps.py"):
        _scan_traced_bodies(src, findings)
    _scan_donation(src, findings)
    return findings


def run(sources=None):
    sources = sources if sources is not None else iter_sources(TARGETS)
    findings = []
    for src in sources:
        findings.extend(scan_source(src))
    return findings, None


# ---------------------------------------------------------------------------
# runtime harness: zero recompiles across a mixed-length serving run
# ---------------------------------------------------------------------------

def run_recompile_harness(max_ticks: int = 200, verbose=print) -> list:
    """Drive a tiny paged engine (paper TinyLlama config, reduced dims)
    over mixed prompt lengths tick by tick and assert no jitted step
    function gains jit cache entries after the tick that first used it.
    -> list of Finding (empty = clean)."""
    import numpy as np

    from repro.analysis.core import Finding
    from repro.configs import get_config, reduced
    from repro.core import model
    from repro.core.partition import ShardingPlan
    from repro.launch.mesh import make_mesh
    from repro.serving import Request, ServingEngine

    import jax

    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    plan = ShardingPlan(tp=1, kv_cache_dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params = model.init_params(cfg, plan)
    eng = ServingEngine.build_paged(cfg, plan, mesh, 2, 32, params,
                                    page_size=8, prefill_chunk=8)
    rng = np.random.RandomState(0)
    for rid, L in enumerate([3, 7, 12, 5, 17, 9]):   # mixed lengths
        eng.submit(Request(
            rid=rid, prompt=rng.randint(2, cfg.vocab_size, L)
            .astype(np.int32), max_new_tokens=4))

    fns = {"prefill_fn (chunk)": eng.prefill_fn,
           "decode_fn": eng.decode_fn}
    if eng.copy_fn is not None:
        fns["copy_fn"] = eng.copy_fn
    if eng.verify_fn is not None:
        fns["verify_fn"] = eng.verify_fn

    def sizes():
        return {name: getattr(fn, "_cache_size", lambda: -1)()
                for name, fn in fns.items()}

    first_use = {}          # name -> (tick, entries when first used)
    grew = {}               # name -> (tick, from, to)
    for t in range(max_ticks):
        if not (eng.has_pending()
                or any(a is not None for a in eng.admissions)):
            break
        eng.tick()
        for name, size in sizes().items():
            if size <= 0:
                continue
            if name not in first_use:
                first_use[name] = (t, size)
            elif size > first_use[name][1] and name not in grew:
                grew[name] = (t, first_use[name][1], size)

    findings = []
    for name, (t0, base) in sorted(first_use.items()):
        cur = sizes()[name]
        verbose(f"  {name}: first used tick {t0} ({base} jit cache "
                f"entr{'y' if base == 1 else 'ies'}), final {cur}")
        if name in grew:
            t, frm, to = grew[name]
            findings.append(Finding(
                rule="jit-stability", path=ENGINE_PATH, line=0,
                message=f"{name} retraced mid-run: {frm} jit cache "
                        f"entries after first use (tick {t0}) grew to "
                        f"{to} at tick {t} — the one-compiled-step-per-"
                        f"tick contract is broken", scope="harness",
                snippet=name))
    return findings

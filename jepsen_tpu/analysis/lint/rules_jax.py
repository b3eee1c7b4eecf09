"""JAX invariant rules.

* ``no-host-effects-in-jit`` (JTJ001) — a traced function runs its
  Python body ONCE at trace time; ``time.time()``, ``random.*``, I/O,
  and ``print`` inside ``@jax.jit`` / pallas kernels silently freeze
  into the compiled program (or fire only on retrace) — the classic
  "my timestamp never changes" bug.
* ``donation-reuse`` (JTJ002) — a buffer passed at a
  ``donate_argnums`` position is dead after dispatch; reading it again
  is use-after-free that XLA may or may not catch (the jitlin pallas
  fallback retry is the in-repo incident: the non-donating wrapper
  exists precisely because the donated carry was about to be reused).
* ``recompile-hazard`` (JTJ003) — ``jax.jit(...)`` constructed inside a
  loop retraces every iteration, and a ``static_argnums`` position fed
  the loop variable recompiles per call: both turn a compile-once hot
  path into a compile-always cold one.
* ``no-host-roundtrip`` (JTJ004) — arrays obtained from the history
  IR's device placement (``device_columns`` / ``shard_leading`` /
  ``shard_chunked``) are device-resident by contract; pulling them
  back to host with ``np.asarray``/``np.array``/``jax.device_get`` or
  ``.tolist()`` inside checker-path code silently re-pays the H2D/D2H
  transfer the IR exists to avoid. Waivable per line with
  ``# lint: ignore[no-host-roundtrip]`` when a host read is the point
  (e.g. a final verdict gather).
* ``threshold-dtype`` (JTJ005) — ``jnp.dot(...,
  preferred_element_type=jnp.float32)`` whose result feeds a ``> 0``
  threshold, in kernel scope. The threshold is the proof the operands
  live in the boolean 0/1 semiring (the product is consumed as
  reachability, not magnitude), and an f32 matmul then computes
  AND/OR at 1/4 the MXU's int8 operand density — the pattern the
  packed-boolean kernel rework removed (ops/pallas_matrix.py,
  doc/performance.md "Packed boolean kernels"). Kernel scope =
  proven-jitted functions, plus every function of a module that
  imports pallas (kernel bodies there are reached through
  ``pallas_call`` indirections the jit index can't always prove).
  Waivable per line where f32 is load-bearing (e.g. the probe-verified
  terminal fallback variant every backend can lower).

The jit rules only scan modules that import ``jax`` (or pallas), and
only the bodies of functions proven jitted: decorated with ``jit`` /
``partial(jax.jit, ...)``, wrapped via ``name = jax.jit(fn, ...)``, or
passed to ``pallas_call``. The host-roundtrip rule scans every module
(device-placement results can flow anywhere).
"""
from __future__ import annotations

import ast

from jepsen_tpu.analysis.diagnostics import Finding
from jepsen_tpu.analysis.lint.astcache import ModuleInfo
from jepsen_tpu.analysis.lint.callgraph import body_calls


def _imports_jax(mod: ModuleInfo) -> bool:
    if any(v == "jax" or v.startswith("jax.") for v in mod.imports.values()):
        return True
    return any(m == "jax" or m.startswith("jax.")
               for m, _ in mod.import_names.values())


def _is_jax_jit(node, mod: ModuleInfo) -> bool:
    """node is the callable expression ``jax.jit`` / imported ``jit``."""
    if isinstance(node, ast.Attribute) and node.attr == "jit" \
            and isinstance(node.value, ast.Name):
        target = (mod.imports.get(node.value.id)
                  or ".".join(mod.import_names.get(node.value.id, ())))
        return target == "jax" or node.value.id == "jax"
    if isinstance(node, ast.Name):
        imp = mod.import_names.get(node.id)
        return imp is not None and imp[0] == "jax" and imp[1] == "jit"
    return False


def _jit_call_kwargs(call: ast.Call) -> dict:
    out = {}
    for k in call.keywords:
        if k.arg in ("donate_argnums", "donate_argnames",
                     "static_argnums", "static_argnames"):
            out[k.arg] = k.value
    return out


def _literal_ints(node) -> tuple:
    """Positions from a literal int / tuple-of-ints node; () = unknown."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                vals.append(el.value)
        return tuple(vals)
    return ()


class _JitIndex:
    """Per-module index of jit-traced functions and jitted callables."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.traced: dict[str, dict] = {}     # func qualname -> jit kwargs
        self.wrappers: dict[str, dict] = {}   # bound name -> jit kwargs
        self._build()

    def _func_by_simple_name(self, name: str):
        hits = [q for q, fi in self.mod.functions.items()
                if fi.node.name == name]
        return hits[0] if len(hits) == 1 else None

    def _mark(self, qualname: str, kwargs: dict):
        self.traced.setdefault(qualname, {}).update(kwargs)

    def _build(self):
        mod = self.mod
        # decorators
        for q, fi in mod.functions.items():
            for dec in fi.node.decorator_list:
                if _is_jax_jit(dec, mod):
                    self._mark(q, {})
                elif isinstance(dec, ast.Call):
                    if _is_jax_jit(dec.func, mod):
                        self._mark(q, _jit_call_kwargs(dec))
                    elif self._is_partial_jit(dec):
                        self._mark(q, _jit_call_kwargs(dec))
        # jax.jit(fn, ...) calls + pallas_call(kernel, ...) anywhere
        for n in ast.walk(mod.tree):
            if not isinstance(n, ast.Call):
                continue
            if _is_jax_jit(n.func, mod) and n.args:
                kwargs = _jit_call_kwargs(n)
                inner = n.args[0]
                if isinstance(inner, ast.Name):
                    q = self._func_by_simple_name(inner.id)
                    if q is not None:
                        self._mark(q, kwargs)
            f = n.func
            if isinstance(f, ast.Attribute) and f.attr == "pallas_call" \
                    and n.args and isinstance(n.args[0], ast.Name):
                q = self._func_by_simple_name(n.args[0].id)
                if q is not None:
                    self._mark(q, {"pallas": True})
            elif isinstance(f, ast.Name) and f.id == "pallas_call" \
                    and n.args and isinstance(n.args[0], ast.Name):
                q = self._func_by_simple_name(n.args[0].id)
                if q is not None:
                    self._mark(q, {"pallas": True})
        # name = jax.jit(fn, ...): the bound name is a jitted callable
        for n in ast.walk(mod.tree):
            if not isinstance(n, ast.Assign):
                continue
            for call in ast.walk(n.value):
                if isinstance(call, ast.Call) and _is_jax_jit(call.func,
                                                              self.mod):
                    kwargs = _jit_call_kwargs(call)
                    if not kwargs:
                        continue
                    for t in n.targets:
                        if isinstance(t, ast.Name):
                            self.wrappers.setdefault(t.id, {}).update(kwargs)

    def _is_partial_jit(self, call: ast.Call) -> bool:
        f = call.func
        is_partial = (isinstance(f, ast.Name) and f.id == "partial") or (
            isinstance(f, ast.Attribute) and f.attr == "partial")
        return (is_partial and call.args
                and _is_jax_jit(call.args[0], self.mod))


# ---------------------------------------------------------------------------
# JTJ001 — host effects under jit
# ---------------------------------------------------------------------------

_BANNED_BUILTINS = {"open", "print", "input"}
_EFFECT_MODULES = {"time", "random", "os"}


def _host_effect(call: ast.Call, mod: ModuleInfo) -> str | None:
    f = call.func
    if isinstance(f, ast.Name):
        if f.id in _BANNED_BUILTINS and f.id not in mod.import_names:
            return f"{f.id}()"
        imp = mod.import_names.get(f.id)
        if imp is not None and imp[0] in ("time", "random"):
            return f"{imp[0]}.{imp[1]}()"
        return None
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        recv = f.value.id
        # an alias bound from jax (e.g. `from jax import random`) is fine
        imp = mod.import_names.get(recv)
        if imp is not None and imp[0].startswith("jax"):
            return None
        if recv in _EFFECT_MODULES:
            return f"{recv}.{f.attr}()"
        if recv in ("np", "numpy") and f.attr == "random":
            return f"{recv}.random()"
    # np.random.<x>() / numpy.random.<x>()
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Attribute) \
            and isinstance(f.value.value, ast.Name) \
            and f.value.value.id in ("np", "numpy") \
            and f.value.attr == "random":
        return f"{f.value.value.id}.random.{f.attr}()"
    return None


def _walk_with_nested(func_node):
    """Calls inside the function INCLUDING nested defs — a nested helper
    defined and called inside a traced body inlines into the trace."""
    out = []
    for n in ast.walk(func_node):
        if isinstance(n, ast.Call):
            out.append(n)
    return out


def no_host_effects_in_jit(mod: ModuleInfo) -> list[Finding]:
    if not _imports_jax(mod):
        return []
    idx = _JitIndex(mod)
    out: list[Finding] = []
    for q, meta in sorted(idx.traced.items()):
        fi = mod.functions.get(q)
        if fi is None or "no-host-effects-in-jit" in fi.ignores:
            continue
        kind = "pallas kernel" if meta.get("pallas") else "jitted function"
        for call in _walk_with_nested(fi.node):
            effect = _host_effect(call, mod)
            if effect is None:
                continue
            if "no-host-effects-in-jit" in mod.line_ignores(call.lineno):
                continue
            out.append(Finding(
                rule="no-host-effects-in-jit", code="JTJ001",
                path=mod.relpath, line=call.lineno,
                col=call.col_offset + 1, qualname=q,
                message=(f"{effect} inside {kind} {fi.node.name!r} runs "
                         "once at trace time and freezes into the "
                         "compiled program"),
                hint="compute host values outside the traced function "
                     "and pass them in as arguments (use jax.random "
                     "with explicit keys for randomness)"))
    return out


# ---------------------------------------------------------------------------
# JTJ002 — donated buffer read after dispatch
# ---------------------------------------------------------------------------

def donation_reuse(mod: ModuleInfo) -> list[Finding]:
    if not _imports_jax(mod):
        return []
    idx = _JitIndex(mod)
    donated = {name: _literal_ints(kw["donate_argnums"])
               for name, kw in idx.wrappers.items()
               if "donate_argnums" in kw}
    donated = {n: pos for n, pos in donated.items() if pos}
    if not donated:
        return []
    out: list[Finding] = []
    for q, fi in mod.functions.items():
        if "donation-reuse" in fi.ignores:
            continue
        calls = [c for c in body_calls(fi.node)
                 if isinstance(c.func, ast.Name) and c.func.id in donated]
        if not calls:
            continue
        names = [n for n in ast.walk(fi.node) if isinstance(n, ast.Name)]
        for call in calls:
            for pos in donated[call.func.id]:
                if pos >= len(call.args):
                    continue
                arg = call.args[pos]
                if not isinstance(arg, ast.Name):
                    continue
                stores = sorted(n.lineno for n in names
                                if n.id == arg.id
                                and isinstance(n.ctx, ast.Store))
                for n in names:
                    if n.id != arg.id or not isinstance(n.ctx, ast.Load) \
                            or n.lineno <= call.lineno:
                        continue
                    # a store on the call line itself (x = fast(x)) is
                    # the canonical rebind-from-result pattern
                    rebound = any(call.lineno <= s <= n.lineno
                                  for s in stores)
                    if rebound:
                        continue
                    if "donation-reuse" in mod.line_ignores(n.lineno):
                        continue
                    out.append(Finding(
                        rule="donation-reuse", code="JTJ002",
                        path=mod.relpath, line=n.lineno,
                        col=n.col_offset + 1, qualname=q,
                        message=(f"{arg.id!r} was donated to "
                                 f"{call.func.id}() at line "
                                 f"{call.lineno} (donate_argnums="
                                 f"{pos}) and is read again here — "
                                 "its buffer may already be reused"),
                        hint="keep a non-donating wrapper for retry "
                             "paths, or rebind the variable from the "
                             "dispatch result"))
                    break  # one finding per donated arg per call
    return out


# ---------------------------------------------------------------------------
# JTJ003 — recompile hazards
# ---------------------------------------------------------------------------

def _loop_bodies(func_node):
    """(loop_node, loop_target_names) for every for/while lexically in
    the function (nested defs excluded)."""
    out = []
    stack = list(ast.iter_child_nodes(func_node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            continue
        if isinstance(n, (ast.For, ast.While)):
            targets: set = set()
            if isinstance(n, ast.For):
                for t in ast.walk(n.target):
                    if isinstance(t, ast.Name):
                        targets.add(t.id)
            out.append((n, targets))
        stack.extend(ast.iter_child_nodes(n))
    return out


def _in_loop_walk(loop_node):
    """Nodes lexically inside a loop body, skipping nested defs."""
    stack = list(loop_node.body) + list(getattr(loop_node, "orelse", []))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            # a def in a loop still re-decorates per iteration; surface
            # its decorators but not its body
            for dec in getattr(n, "decorator_list", []):
                yield dec
                for sub in ast.walk(dec):
                    yield sub
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def recompile_hazard(mod: ModuleInfo) -> list[Finding]:
    if not _imports_jax(mod):
        return []
    idx = _JitIndex(mod)
    statics = {name: kw for name, kw in idx.wrappers.items()
               if "static_argnums" in kw or "static_argnames" in kw}
    out: list[Finding] = []
    for q, fi in mod.functions.items():
        if "recompile-hazard" in fi.ignores:
            continue
        for loop, targets in _loop_bodies(fi.node):
            for n in _in_loop_walk(loop):
                if not isinstance(n, ast.Call):
                    continue
                if "recompile-hazard" in mod.line_ignores(n.lineno):
                    continue
                if _is_jax_jit(n.func, mod):
                    out.append(Finding(
                        rule="recompile-hazard", code="JTJ003",
                        path=mod.relpath, line=n.lineno,
                        col=n.col_offset + 1, qualname=q,
                        message="jax.jit(...) constructed inside a loop "
                                "— every iteration builds a fresh "
                                "wrapper and retraces",
                        hint="hoist the jitted callable out of the loop "
                             "(cache it, as ops.jitlin's kernel cache "
                             "does)"))
                    continue
                f = n.func
                if isinstance(f, ast.Name) and f.id in statics and targets:
                    kw = statics[f.id]
                    pos = _literal_ints(kw.get("static_argnums",
                                                ast.Constant(value=None)))
                    hazard = None
                    for p in pos:
                        if p < len(n.args):
                            used = {x.id for x in ast.walk(n.args[p])
                                    if isinstance(x, ast.Name)}
                            if used & targets:
                                hazard = p
                                break
                    if hazard is None and "static_argnames" in kw:
                        want = set()
                        sn = kw["static_argnames"]
                        if isinstance(sn, ast.Constant):
                            want = {sn.value}
                        elif isinstance(sn, (ast.Tuple, ast.List)):
                            want = {e.value for e in sn.elts
                                    if isinstance(e, ast.Constant)}
                        for k in n.keywords:
                            if k.arg in want:
                                used = {x.id for x in ast.walk(k.value)
                                        if isinstance(x, ast.Name)}
                                if used & targets:
                                    hazard = k.arg
                                    break
                    if hazard is not None:
                        out.append(Finding(
                            rule="recompile-hazard", code="JTJ003",
                            path=mod.relpath, line=n.lineno,
                            col=n.col_offset + 1, qualname=q,
                            message=(f"{f.id}() takes the loop variable "
                                     f"at static position {hazard!r} — "
                                     "every distinct value recompiles"),
                            hint="make the argument dynamic (traced), "
                                 "or bucket it so the static set stays "
                                 "small"))
    return out


# ---------------------------------------------------------------------------
# JTJ004 — device-resident IR arrays round-tripped to host
# ---------------------------------------------------------------------------

#: calls whose result is device-resident by contract (the history IR's
#: placement surface and the parallel staging helpers)
_DEVICE_SOURCES = {"device_columns", "shard_leading", "shard_chunked"}

#: receiver method that materializes on host
_ROUNDTRIP_METHODS = {"tolist"}

#: np./jax. level functions that materialize on host
_ROUNDTRIP_FUNCS = {("np", "asarray"), ("np", "array"),
                    ("numpy", "asarray"), ("numpy", "array"),
                    ("jax", "device_get")}


def _taint_events(func_node) -> list:
    """(lineno, name, source) for every Assign target in the function,
    line-ordered. ``source`` is True (bound from a device-source call),
    ("alias", base_name) (bound from a subscript of another name), or
    False (any other binding — CLEARS taint: a name rebound to host
    data must not stay flagged)."""
    events = []
    for n in ast.walk(func_node):
        if not isinstance(n, ast.Assign):
            continue
        val = n.value
        if isinstance(val, ast.Call) \
                and isinstance(val.func, ast.Attribute) \
                and val.func.attr in _DEVICE_SOURCES:
            src = True
        elif isinstance(val, ast.Call) and isinstance(val.func, ast.Name) \
                and val.func.id in _DEVICE_SOURCES:
            src = True
        elif isinstance(val, ast.Subscript) \
                and isinstance(val.value, ast.Name):
            src = ("alias", val.value.id)
        else:
            src = False
        for t in n.targets:
            for sub in ast.walk(t):
                if isinstance(sub, ast.Name):
                    events.append((n.lineno, sub.id, src))
    events.sort(key=lambda e: e[0])
    return events


def _tainted_at(events, line) -> dict[str, int]:
    """name -> taint lineno for names device-tainted at ``line``,
    replaying bindings in line order (last binding wins)."""
    cur: dict[str, int] = {}
    for ln, nm, src in events:
        if ln >= line:
            break
        if src is True:
            cur[nm] = ln
        elif src is False:
            cur.pop(nm, None)
        else:  # subscript alias: tainted iff its base currently is
            if src[1] in cur:
                cur[nm] = ln
            else:
                cur.pop(nm, None)
    return cur


def _mentions(node, names) -> str | None:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            return sub.id
    return None


def _imports_pallas(mod: ModuleInfo) -> bool:
    if any("pallas" in v for v in mod.imports.values()):
        return True
    return any("pallas" in m or n == "pallas"
               for m, n in mod.import_names.values())


def _is_jnp_dot_f32(call: ast.Call, mod: ModuleInfo) -> bool:
    """``jnp.dot(..., preferred_element_type=jnp.float32)`` (any alias
    of jax.numpy as the receiver)."""
    f = call.func
    if not (isinstance(f, ast.Attribute) and f.attr == "dot"
            and isinstance(f.value, ast.Name)):
        return False
    target = mod.imports.get(f.value.id)
    if not (f.value.id == "jnp" or target == "jax.numpy"):
        return False
    for k in call.keywords:
        if k.arg == "preferred_element_type":
            v = k.value
            return isinstance(v, ast.Attribute) and v.attr == "float32"
    return False


def _threshold_dot(node, mod: ModuleInfo):
    """The ``dot > 0`` / ``0 < dot`` threshold Compare; returns the dot
    Call or None."""
    if not isinstance(node, ast.Compare) or len(node.ops) != 1:
        return None
    op, left, right = node.ops[0], node.left, node.comparators[0]
    if isinstance(op, ast.Gt) and isinstance(left, ast.Call) \
            and isinstance(right, ast.Constant) and right.value == 0 \
            and _is_jnp_dot_f32(left, mod):
        return left
    if isinstance(op, ast.Lt) and isinstance(right, ast.Call) \
            and isinstance(left, ast.Constant) and left.value == 0 \
            and _is_jnp_dot_f32(right, mod):
        return right
    return None


def threshold_dtype(mod: ModuleInfo) -> list[Finding]:
    pallas_mod = _imports_pallas(mod)
    if not pallas_mod and not _imports_jax(mod):
        return []
    # kernel scope: proven-jitted/pallas bodies; in a pallas-importing
    # module, every function (kernel defs there reach pallas_call
    # through closures and name indirections the index can't prove)
    if pallas_mod:
        spans = list(mod.functions.values())
    else:
        idx = _JitIndex(mod)
        spans = [mod.functions[q] for q in idx.traced
                 if q in mod.functions]
    if not spans:
        return []

    def innermost(lineno):
        best = None
        for fi in spans:
            if fi.lineno <= lineno <= fi.end_lineno:
                if best is None or fi.lineno > best.lineno:
                    best = fi
        return best

    out: list[Finding] = []
    seen: set = set()
    for node in ast.walk(mod.tree):
        dot = _threshold_dot(node, mod)
        if dot is None:
            continue
        key = (node.lineno, node.col_offset)
        if key in seen:
            continue
        seen.add(key)
        fi = innermost(node.lineno)
        if fi is None or "threshold-dtype" in fi.ignores:
            continue
        if "threshold-dtype" in (mod.line_ignores(node.lineno)
                                 | mod.line_ignores(dot.lineno)):
            continue
        out.append(Finding(
            rule="threshold-dtype", code="JTJ005",
            path=mod.relpath, line=dot.lineno,
            col=dot.col_offset + 1, qualname=fi.qualname,
            message="thresholded f32 dot: the > 0 test proves the "
                    "operands live in the 0/1 boolean semiring, and an "
                    "f32 matmul computes that AND/OR at 1/4 the MXU's "
                    "int8 operand density",
            hint="feed int8 0/1 operands with preferred_element_type="
                 "jnp.int32 (or the bit-packed uint32 path) and keep "
                 "the > 0 threshold; waive with # lint: "
                 "ignore[threshold-dtype] where f32 is load-bearing"))
    return out


def no_host_roundtrip(mod: ModuleInfo) -> list[Finding]:
    out: list[Finding] = []
    for q, fi in mod.functions.items():
        if "no-host-roundtrip" in fi.ignores:
            continue
        events = _taint_events(fi.node)
        if not any(src is True for _, _, src in events):
            continue
        for call in ast.walk(fi.node):
            if not isinstance(call, ast.Call):
                continue
            tainted = _tainted_at(events, call.lineno)
            if not tainted:
                continue
            f = call.func
            hit = what = None
            if isinstance(f, ast.Attribute) \
                    and f.attr in _ROUNDTRIP_METHODS:
                hit = _mentions(f.value, tainted)
                what = f".{f.attr}()"
            elif isinstance(f, ast.Attribute) \
                    and isinstance(f.value, ast.Name) \
                    and (f.value.id, f.attr) in _ROUNDTRIP_FUNCS \
                    and call.args:
                hit = _mentions(call.args[0], tainted)
                what = f"{f.value.id}.{f.attr}()"
            if hit is None:
                continue
            if "no-host-roundtrip" in mod.line_ignores(call.lineno):
                continue
            out.append(Finding(
                rule="no-host-roundtrip", code="JTJ004",
                path=mod.relpath, line=call.lineno,
                col=call.col_offset + 1, qualname=q,
                message=(f"{what} on {hit!r} (device-resident: bound "
                         f"from a device-placement call at line "
                         f"{tainted[hit]}) round-trips IR arrays back "
                         "to host inside a checker path"),
                hint="consume the device arrays in-kernel (shard_map/"
                     "jit) or keep a host-side copy from before "
                     "placement; waive with # lint: "
                     "ignore[no-host-roundtrip] when a host gather is "
                     "the point"))
    return out

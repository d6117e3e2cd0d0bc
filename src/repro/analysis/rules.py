"""The RPR0xx rule set: JAX/Pallas hazards distilled from this repo's bug
history.

Each rule is a :class:`Rule` subclass with a ``check_file`` hook (one file's
AST) and/or a ``check_project`` hook (whole-tree context, e.g. config-flag
liveness).  Rules are deliberately repo-specific: they encode the exact
failure shapes we have shipped and hot-fixed —

- RPR001: a ``cached_property``/``lru_cache`` member producing ``jnp``
  values was first touched under ``jax.eval_shape`` and permanently cached
  tracers (the PR 3 sparse-decode dry-run crash).
- RPR002: a buffer donated through ``donate_argnums`` was read after the
  donating call (donated buffers are invalidated; every new jit step has
  had to be hand-audited for this).
- RPR003: plan/layout descriptor builders must stay host numpy — a ``jnp``
  constant built at plan time rides the lru-cached plan into every later
  trace.
- RPR004: blocking calls inside ``async def`` stall the continuous-batching
  serve loop for every stream it multiplexes.
- RPR005: fault-injection sites must fire BEFORE jit dispatch, or an
  injected error lands after the donated cache is already invalidated.
- RPR006: every ``SparseConfig``/``ServeConfig`` field must be read
  somewhere — a dead flag silently green-lights configs that do nothing.
- RPR007: module-import must not touch device state (configs are plain
  data; import-time ``jnp`` constants break that contract and pay a device
  sync per import).

RPR008 (unused ``# noqa: RPR0xx`` suppression) lives in the engine, not
here: it falls out of pragma accounting after all rules have run.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple


@dataclass(frozen=True)
class Finding:
    code: str
    message: str
    path: str
    line: int
    col: int = 0

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col + 1}: {self.code} {self.message}"

    def as_dict(self) -> dict:
        return {
            "code": self.code,
            "message": self.message,
            "path": self.path,
            "line": self.line,
            "col": self.col,
        }


class FileContext:
    """One parsed file plus the alias facts rules keep re-deriving."""

    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path
        self.source = source
        self.tree = tree
        self.jnp_aliases: Set[str] = set()   # names bound to jax.numpy
        self.jax_aliases: Set[str] = set()   # names bound to the jax module
        self.np_aliases: Set[str] = set()    # names bound to numpy
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    bound = a.asname or a.name.split(".")[0]
                    if a.name == "jax.numpy":
                        self.jnp_aliases.add(a.asname or "jax.numpy")
                    elif a.name == "jax" or a.name.startswith("jax."):
                        self.jax_aliases.add(bound)
                    elif a.name == "numpy":
                        self.np_aliases.add(bound)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "jax":
                    for a in node.names:
                        if a.name == "numpy":
                            self.jnp_aliases.add(a.asname or "numpy")

    def is_jnp(self, node: ast.expr) -> bool:
        """True when ``node`` is (rooted at) the jax.numpy module alias."""
        root = _attr_root(node)
        return root in self.jnp_aliases or _attr_path(node).startswith(
            "jax.numpy."
        )


def _attr_root(node: ast.expr) -> Optional[str]:
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _attr_path(node: ast.expr) -> str:
    """Dotted source path of a Name/Attribute chain ('' when dynamic)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _decorator_name(dec: ast.expr) -> str:
    if isinstance(dec, ast.Call):
        dec = dec.func
    return _attr_path(dec).rsplit(".", 1)[-1] if _attr_path(dec) else ""


def _functions(tree: ast.AST) -> Iterator[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class Rule:
    code: str = "RPR000"
    name: str = "?"
    description: str = ""

    def check_file(self, ctx: FileContext) -> Iterator[Finding]:
        return iter(())

    def check_project(
        self, contexts: Sequence[FileContext]
    ) -> Iterator[Finding]:
        return iter(())


# ---------------------------------------------------------------------------
# RPR001 — cached members must not capture device values (tracer capture)
# ---------------------------------------------------------------------------

_CACHING_DECORATORS = {"cached_property", "lru_cache", "cache"}


class TracerCaptureRule(Rule):
    """``cached_property`` / ``lru_cache`` members whose body builds ``jnp``
    values: the first touch may happen under ``jit``/``jax.eval_shape``
    (lru-cached plans are shared across trace boundaries), permanently
    caching tracers.  The PR 3 regression shape: ``AttentionPlan.stacked``
    first accessed inside ``eval_shape(init_cache)`` poisoned every sparse
    decode dry-run with ``TracerArrayConversionError``.  Cached members must
    return host numpy; convert to device values at the use site."""

    code = "RPR001"
    name = "cached-tracer-capture"
    description = (
        "cached_property/lru_cache member builds jnp values; a first touch "
        "under jit/eval_shape caches tracers permanently"
    )

    def check_file(self, ctx: FileContext) -> Iterator[Finding]:
        for fn in _functions(ctx.tree):
            if not any(
                _decorator_name(d) in _CACHING_DECORATORS
                for d in fn.decorator_list
            ):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and ctx.is_jnp(node.func):
                    yield Finding(
                        self.code,
                        f"cached member {fn.name!r} builds a jax.numpy value "
                        f"({_attr_path(node.func)}); a first access under "
                        "jit/eval_shape caches a tracer — return host numpy "
                        "and convert at the device use site",
                        ctx.path,
                        node.lineno,
                        node.col_offset,
                    )


# ---------------------------------------------------------------------------
# RPR002 — donated buffers must not be referenced after the donating call
# ---------------------------------------------------------------------------


def _donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """donate_argnums value of a ``jax.jit`` call, when statically known."""
    if _attr_path(call.func) not in ("jax.jit", "jit"):
        return None
    for kw in call.keywords:
        if kw.arg not in ("donate_argnums", "donate_argnames"):
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.append(e.value)
                else:
                    return None
            return tuple(out)
        return None
    return None


def _target_paths(target: ast.expr) -> Set[str]:
    """Dotted paths (re)bound by an assignment target."""
    out: Set[str] = set()
    for node in ast.walk(target):
        if isinstance(node, (ast.Name, ast.Attribute)):
            p = _attr_path(node)
            if p:
                out.add(p)
    return out


class DonationSafetyRule(Rule):
    """A buffer passed into a ``donate_argnums`` position is invalidated by
    the call; reading it afterwards returns garbage (or errors on TPU).
    Tracks, within one function scope, locals bound to
    ``jax.jit(fn, donate_argnums=...)`` plus immediately-invoked jitted
    calls, and flags donated arguments referenced after the call site
    without being rebound by the call's own assignment."""

    code = "RPR002"
    name = "use-after-donation"
    description = (
        "buffer passed through donate_argnums is referenced after the "
        "donating call site"
    )

    def check_file(self, ctx: FileContext) -> Iterator[Finding]:
        for fn in _functions(ctx.tree):
            yield from self._check_scope(ctx, fn)

    def _check_scope(self, ctx, fn) -> Iterator[Finding]:
        # local name (dotted path) -> donated positions
        jitted: Dict[str, Tuple[int, ...]] = {}
        statements = list(ast.walk(fn))
        for node in statements:
            if isinstance(node, ast.Assign):
                don = (
                    _donated_positions(node.value)
                    if isinstance(node.value, ast.Call)
                    else None
                )
                if don is not None:
                    for t in node.targets:
                        for p in _target_paths(t):
                            jitted[p] = don

        for node in statements:
            call, rebound = None, set()
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                call = node.value
                for t in node.targets:
                    rebound |= _target_paths(t)
            elif isinstance(node, ast.Expr) and isinstance(
                node.value, ast.Call
            ):
                call = node.value
            if call is None:
                continue
            don = None
            if isinstance(call.func, ast.Call):
                don = _donated_positions(call.func)  # jax.jit(f, ...)(args)
            if don is None:
                don = jitted.get(_attr_path(call.func))
            if don is None:
                continue
            for pos in don:
                if pos >= len(call.args):
                    continue
                path = _attr_path(call.args[pos])
                if not path or path in rebound:
                    continue
                # the donating statement's own nodes (a multiline call puts
                # its args on later lines) are not reads-after-donation.
                own = set(ast.walk(node))
                for later in statements:
                    if (
                        isinstance(later, (ast.Name, ast.Attribute))
                        and later not in own
                        and isinstance(getattr(later, "ctx", None), ast.Load)
                        and later.lineno > node.lineno
                        and _attr_path(later) == path
                    ):
                        yield Finding(
                            self.code,
                            f"{path!r} is donated (donate_argnums includes "
                            f"position {pos}) at line {node.lineno} but read "
                            f"again at line {later.lineno}; donation "
                            "invalidates the buffer — rebind the result or "
                            "drop the donation",
                            ctx.path,
                            later.lineno,
                            later.col_offset,
                        )
                        break


# ---------------------------------------------------------------------------
# RPR003 — plan/layout descriptor builders stay host numpy
# ---------------------------------------------------------------------------

#: host-only zones: classes whose bodies build plan-time descriptors, and
#: module-level builder functions.  ``as_arrays`` is the sanctioned
#: host->device conversion point and is exempt by design.
_HOST_ZONE_CLASSES = {"RaggedLayout", "AttentionPlan"}
_HOST_ZONE_FUNCTIONS = {
    "stack_layouts",
    "layout_for",
    "uniform_layout",
    "build_plan",
}


class HostDeviceBoundaryRule(Rule):
    """Plan descriptors (``RaggedLayout`` constants, ``AttentionPlan``
    members, ``stack_layouts`` stacks) are built once, lru-cached and shared
    across jit boundaries — they must be host numpy.  A ``jnp`` value built
    here is a device constant at best and a captured tracer at worst
    (see RPR001); device conversion belongs at the use site (the cache
    allocator's ``jax.tree.map(jnp.array, ...)``)."""

    code = "RPR003"
    name = "device-array-in-plan-builder"
    description = (
        "jnp used inside a host-only plan/layout descriptor builder "
        "(AttentionPlan/RaggedLayout construction must be host numpy)"
    )

    def check_file(self, ctx: FileContext) -> Iterator[Finding]:
        zones: List[ast.AST] = []
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.ClassDef)
                and node.name in _HOST_ZONE_CLASSES
            ):
                zones.append(node)
            elif (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in _HOST_ZONE_FUNCTIONS
            ):
                zones.append(node)
        for zone in zones:
            for node in ast.walk(zone):
                if isinstance(node, ast.Call) and ctx.is_jnp(node.func):
                    zname = getattr(zone, "name", "?")
                    yield Finding(
                        self.code,
                        f"{_attr_path(node.func)} inside host-only "
                        f"plan/layout builder {zname!r}: descriptors are "
                        "cached and shared across traces — build with "
                        "numpy, convert at the device use site",
                        ctx.path,
                        node.lineno,
                        node.col_offset,
                    )


# ---------------------------------------------------------------------------
# RPR004 — no blocking calls inside async def
# ---------------------------------------------------------------------------

#: dotted-path prefixes that block the event loop.
_BLOCKING_PREFIXES = (
    "time.sleep",
    "os.system",
    "subprocess.",
    "socket.",
    "requests.",
    "urllib.request.",
    "shutil.",
)
#: attribute calls that block regardless of receiver.
_BLOCKING_ATTRS = {"run_until_done", "block_until_ready", "join"}
#: builtins that block on I/O or a human.
_BLOCKING_BUILTINS = {"open", "input"}


class AsyncBlockingRule(Rule):
    """A blocking call inside ``async def`` wedges the event loop — every
    multiplexed token stream stalls behind it.  Flags known-blocking
    library calls, blocking builtins, and this repo's engine drains
    (``run_until_done`` / ``engine.step``).  Wrap genuinely-blocking work
    in ``asyncio.to_thread`` or justify with a pragma (the deterministic
    virtual-tick serve loop does the latter, by design)."""

    code = "RPR004"
    name = "blocking-call-in-async"
    description = "blocking call inside async def stalls the serve loop"

    def check_file(self, ctx: FileContext) -> Iterator[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            for node in self._async_body_calls(fn):
                why = self._blocking(node)
                if why:
                    yield Finding(
                        self.code,
                        f"blocking call {why!r} inside async def "
                        f"{fn.name!r}; the event loop (and every stream it "
                        "serves) stalls until it returns — use "
                        "asyncio.to_thread or move it off the loop",
                        ctx.path,
                        node.lineno,
                        node.col_offset,
                    )

    def _async_body_calls(self, fn: ast.AsyncFunctionDef):
        """Calls lexically inside ``fn`` but not inside a nested sync def
        (a nested def runs on its caller's schedule, not the loop's)."""
        skip: Set[ast.AST] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.FunctionDef):
                skip.update(ast.walk(node))
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and node not in skip:
                yield node

    def _blocking(self, call: ast.Call) -> Optional[str]:
        path = _attr_path(call.func)
        if not path:
            return None
        for prefix in _BLOCKING_PREFIXES:
            if path == prefix or path.startswith(prefix):
                return path
        if path in _BLOCKING_BUILTINS:
            return path
        leaf = path.rsplit(".", 1)[-1]
        if leaf in _BLOCKING_ATTRS and "." in path:
            return path
        # this repo's engine tick: a jit dispatch + host sync per call.
        if leaf == "step" and "engine" in path.lower():
            return path
        return None


# ---------------------------------------------------------------------------
# RPR005 — fault-injection sites fire before jit dispatch
# ---------------------------------------------------------------------------

#: a call whose dotted path ends in one of these dispatches a jit'd step
#: (donating the cache): repo idiom for engine step functions.
_DISPATCH_SUFFIXES = ("_step_fn", "_step_fns", "step_fn")
_INJECT_ATTR = "check_raise"


class FaultHookPlacementRule(Rule):
    """Within a function that both consults the fault injector
    (``*.check_raise``) and dispatches a jit'd step (``*_step_fn[s]``),
    the injection site must come FIRST: an injected fault raised after
    dispatch lands on a donated (already invalidated) cache, which is
    exactly the corruption the harness exists to simulate safely."""

    code = "RPR005"
    name = "fault-hook-after-dispatch"
    description = (
        "fault-injection check_raise placed after the jit step dispatch "
        "(must fire before dispatch so the donated cache stays valid)"
    )

    def check_file(self, ctx: FileContext) -> Iterator[Finding]:
        for fn in _functions(ctx.tree):
            inject_lines: List[int] = []
            dispatch: List[ast.Call] = []
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                path = _attr_path(node.func)
                leaf = path.rsplit(".", 1)[-1] if path else ""
                if leaf == _INJECT_ATTR:
                    inject_lines.append(node.lineno)
                elif leaf.endswith(_DISPATCH_SUFFIXES):
                    dispatch.append(node)
                elif isinstance(node.func, ast.Subscript) and isinstance(
                    node.func.value, ast.Call
                ):
                    # self._rung_step_fns(rung)[i](...) — subscripted
                    # dispatch-table call.
                    inner = _attr_path(node.func.value.func)
                    if inner.rsplit(".", 1)[-1].endswith(_DISPATCH_SUFFIXES):
                        dispatch.append(node)
            if not inject_lines or not dispatch:
                continue
            first_inject = min(inject_lines)
            for d in dispatch:
                if d.lineno < first_inject:
                    yield Finding(
                        self.code,
                        f"jit step dispatched at line {d.lineno} before the "
                        f"fault-injection site at line {first_inject}; "
                        "check_raise must fire pre-dispatch so an injected "
                        "fault never invalidates the donated cache",
                        ctx.path,
                        d.lineno,
                        d.col_offset,
                    )


# ---------------------------------------------------------------------------
# RPR006 — config-flag liveness (project-wide)
# ---------------------------------------------------------------------------

#: config dataclasses whose every field must be consumed somewhere.
_LIVENESS_CLASSES = ("SparseConfig", "ServeConfig", "ResilienceConfig")


class ConfigLivenessRule(Rule):
    """Every ``SparseConfig`` / ``ServeConfig`` / ``ResilienceConfig`` field
    must be READ somewhere in the tree.  A field nobody consumes is a knob
    wired to nothing: configs built against it silently change nothing
    (the serving engine has shipped exactly such flags).  A read is any
    attribute load of the field name anywhere — including the config
    class's own methods (``budget_for`` consuming ``budget_frac`` is
    legitimate liveness) — deliberately lenient (name collisions count as
    reads) so the rule never cries wolf."""

    code = "RPR006"
    name = "dead-config-field"
    description = (
        "config dataclass field is never read anywhere in the linted tree"
    )

    def check_project(
        self, contexts: Sequence[FileContext]
    ) -> Iterator[Finding]:
        # field name -> (ctx, class name, line)
        fields: Dict[str, Tuple[FileContext, str, int]] = {}
        for ctx in contexts:
            for node in ast.walk(ctx.tree):
                if (
                    not isinstance(node, ast.ClassDef)
                    or node.name not in _LIVENESS_CLASSES
                ):
                    continue
                for stmt in node.body:
                    if isinstance(stmt, ast.AnnAssign) and isinstance(
                        stmt.target, ast.Name
                    ):
                        fields.setdefault(
                            stmt.target.id, (ctx, node.name, stmt.lineno)
                        )
        if not fields:
            return

        read: Set[str] = set()
        for ctx in contexts:
            for node in ast.walk(ctx.tree):
                if (
                    isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)
                    and node.attr in fields
                ):
                    read.add(node.attr)
        for name, (ctx, cls, line) in sorted(
            fields.items(), key=lambda kv: (kv[1][0].path, kv[1][2])
        ):
            if name not in read:
                yield Finding(
                    self.code,
                    f"{cls}.{name} is never read anywhere in the linted "
                    "tree — wire it up or remove it (a dead flag silently "
                    "accepts configs that change nothing)",
                    ctx.path,
                    line,
                )


# ---------------------------------------------------------------------------
# RPR007 — no device state at import time
# ---------------------------------------------------------------------------

#: jax attribute chains that are pure metadata / registration — safe at
#: module import, never touch a device.
_IMPORT_SAFE_JAX = (
    "jax.tree_util.",
    "jax.custom_vjp",
    "jax.custom_jvp",
    "jax.ShapeDtypeStruct",
    "jax.named_scope",
)
_DEVICE_TOUCHING_JNP_EXEMPT = {"dtype"}


class ImportTimeDeviceRule(Rule):
    """Importing a module must not touch jax device state (the config
    contract: configs are plain data).  A module-level ``jnp`` constant
    initializes the backend at import, breaks ``XLA_FLAGS`` device forcing
    done after import, and pays a device transfer for every importer.
    Registration-only jax calls (pytree registration, ShapeDtypeStruct)
    are exempt."""

    code = "RPR007"
    name = "import-time-device-state"
    description = (
        "module-level jax.numpy call touches device state at import time"
    )

    def check_file(self, ctx: FileContext) -> Iterator[Finding]:
        for node in self._module_level_calls(ctx.tree):
            path = _attr_path(node.func)
            leaf = path.rsplit(".", 1)[-1] if path else ""
            if ctx.is_jnp(node.func):
                if leaf in _DEVICE_TOUCHING_JNP_EXEMPT:
                    continue
                yield Finding(
                    self.code,
                    f"module-level {path} builds a device value at import "
                    "time; importing must stay device-free — build lazily "
                    "or keep the constant as numpy",
                    ctx.path,
                    node.lineno,
                    node.col_offset,
                )
            elif path.startswith(("jax.random.", "jax.device_put")):
                yield Finding(
                    self.code,
                    f"module-level {path} touches the device at import "
                    "time; move it inside a function",
                    ctx.path,
                    node.lineno,
                    node.col_offset,
                )

    def _module_level_calls(self, tree: ast.Module):
        """Calls executed at import: module body + class bodies, but not
        function bodies (decorators ARE import-time and are included)."""
        skip: Set[ast.AST] = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for child in ast.walk(node):
                    if child is not node:
                        skip.add(child)
                skip.update(
                    c for d in node.decorator_list for c in ast.walk(d)
                )
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and node not in skip:
                yield node


ALL_RULES: Tuple[Rule, ...] = (
    TracerCaptureRule(),
    DonationSafetyRule(),
    HostDeviceBoundaryRule(),
    AsyncBlockingRule(),
    FaultHookPlacementRule(),
    ConfigLivenessRule(),
    ImportTimeDeviceRule(),
)

#: RPR008 is emitted by the engine from pragma accounting.
UNUSED_PRAGMA_CODE = "RPR008"

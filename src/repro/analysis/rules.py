"""Rule registry for the invariant linter.

Each rule is a function ``check(ctx) -> list[Violation]`` over one parsed
file, registered in :data:`RULES` with an id, a one-line title, and the
regression class it guards against. Rules are pure AST + config — no
imports of the code under analysis, no third-party deps — so the pass runs
identically on a tree that does not even import (a syntax error is itself
reported, not crashed on).

Scoping and allowlists live in :data:`CONFIG`; :func:`config_fingerprint`
hashes the whole configuration (rule ids included) into the baseline file so
CI fails on silent config drift — loosening a scope is a reviewed change,
exactly like raising the tier-1 failure budget would be.
"""
from __future__ import annotations

import ast
import hashlib
import json
from dataclasses import dataclass, field
from typing import Callable

from repro.analysis.engine import FileContext, Violation

# ---------------------------------------------------------------------------
# Configuration (hashed into the baseline; edits are config drift)
# ---------------------------------------------------------------------------

CONFIG: dict = {
    # RA01: files under these prefixes must never read a wall clock. obs/ is
    # in scope because the tracer (obs/trace.py) must stay on the gateway's
    # VIRTUAL clock for byte-identical trace JSON; hooks.py is the one
    # sanctioned wall-clock sink (stage timers, never trace/telemetry input).
    "virtual_clock_scope": [
        "src/repro/serve/", "src/repro/session/", "src/repro/codec/",
        "src/repro/pipeline/", "src/repro/obs/", "src/repro/tasks/",
    ],
    "virtual_clock_allow_files": {
        "src/repro/obs/hooks.py":
            "the sanctioned wall-clock measurement sink: stage timers feed "
            "metrics histograms only, never the trace or replay state",
    },
    "wall_clock_calls": [
        "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
        "time.perf_counter", "time.perf_counter_ns",
        "time.process_time", "time.process_time_ns",
        "time.localtime", "time.gmtime", "time.ctime", "time.strftime",
        "datetime.datetime.now", "datetime.datetime.utcnow",
        "datetime.datetime.today", "datetime.date.today",
    ],
    # RA02: legacy global-state RNG entry points (numpy legacy API + stdlib
    # random module). jax.random / np.random.Generator are the sanctioned
    # explicit-state APIs and are never flagged.
    "legacy_np_random": [
        "rand", "randn", "randint", "random", "random_sample", "ranf",
        "sample", "choice", "shuffle", "permutation", "uniform", "normal",
        "standard_normal", "beta", "binomial", "poisson", "exponential",
        "seed", "get_state", "set_state", "RandomState",
    ],
    "legacy_py_random": [
        "random", "randint", "randrange", "choice", "choices", "shuffle",
        "sample", "uniform", "gauss", "normalvariate", "betavariate",
        "expovariate", "seed", "getrandbits",
    ],
    # RA02b: set-iteration order must not reach wire bytes / schedules /
    # serialized output; scoped to the modules that produce them.
    "set_iteration_scope": [
        "src/repro/serve/", "src/repro/session/", "src/repro/codec/",
        "src/repro/core/", "src/repro/pipeline/", "src/repro/obs/",
        "src/repro/tasks/",
    ],
    # RA03: the only files allowed to touch the version-skewed jax surface.
    "compat_shims": ["src/repro/kernels/compat.py", "src/repro/compat.py"],
    # RA05: host-sync calls inside traced (jit / shard_map / pallas) bodies.
    "host_sync_scope": ["src/repro/"],
    # RA06: best-effort sites where a silent catch-all is the contract.
    # obs/bench.py is the canonical example: git_sha() falls back to
    # $GITHUB_SHA — but even there the except is narrowed to the concrete
    # (SubprocessError, OSError) pair, so the allowlist entry documents the
    # contract rather than hiding a blanket handler.
    "silent_except_allow_files": {
        "src/repro/obs/bench.py":
            "best-effort git metadata: every failure path falls back to "
            "$GITHUB_SHA / 'unknown'; handlers stay typed regardless",
    },
}


def config_fingerprint() -> str:
    """Hash of everything that changes what the pass flags."""
    payload = {"config": CONFIG, "rules": sorted(RULES)}
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------

def build_alias_map(tree: ast.AST) -> dict[str, str]:
    """Local name -> dotted origin, from every import in the file.

    ``import numpy as np`` -> {"np": "numpy"}; ``from time import
    perf_counter`` -> {"perf_counter": "time.perf_counter"}; ``from datetime
    import datetime`` -> {"datetime": "datetime.datetime"}. Function-level
    imports are folded in too — resolution is per-file, not per-scope, which
    is the right bias for a linter (a shadowed import is its own smell).
    """
    alias: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    alias[a.asname] = a.name
                else:
                    alias[a.name.split(".")[0]] = a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            for a in node.names:
                if a.name == "*":
                    continue
                alias[a.asname or a.name] = f"{node.module}.{a.name}"
    return alias


def dotted_parts(node: ast.AST) -> list[str] | None:
    """['np', 'random', 'rand'] for the expression ``np.random.rand``."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return None


def resolve(alias: dict[str, str], node: ast.AST) -> str | None:
    """Fully-qualified dotted name of an expression, through the imports."""
    parts = dotted_parts(node)
    if not parts:
        return None
    head = alias.get(parts[0], parts[0])
    return ".".join([head] + parts[1:])


def _in_scope(path: str, prefixes: list[str]) -> bool:
    return any(path.startswith(p) for p in prefixes)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Rule:
    id: str
    title: str
    guards: str                          # the regression class this catches
    check: Callable[[FileContext], list]
    fixable: bool = False


RULES: dict[str, Rule] = {}


def _register(rule: Rule) -> Rule:
    RULES[rule.id] = rule
    return rule


def _v(rule_id: str, ctx: FileContext, node: ast.AST, message: str) -> Violation:
    return Violation(rule=rule_id, path=ctx.path,
                     line=getattr(node, "lineno", 1),
                     col=getattr(node, "col_offset", 0), message=message)


# ---------------------------------------------------------------------------
# RA01 — virtual-clock purity
# ---------------------------------------------------------------------------

def _check_ra01(ctx: FileContext) -> list:
    if not _in_scope(ctx.path, CONFIG["virtual_clock_scope"]):
        return []
    if ctx.path in CONFIG["virtual_clock_allow_files"]:
        return []
    wall = set(CONFIG["wall_clock_calls"])
    out = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            name = resolve(ctx.alias, node.func)
            if name in wall:
                out.append(_v("RA01", ctx, node,
                              f"wall-clock call {name}() on a virtual-clock "
                              f"path; replay gates require the event-loop "
                              f"clock (or an allowlisted measurement site)"))
    return out


_register(Rule(
    id="RA01", title="virtual-clock purity", check=_check_ra01,
    guards="one time.time() in serve/session/codec/pipeline/obs breaks "
           "bit-identical replay, byte-identical traces, and session "
           "signatures all at once"))


# ---------------------------------------------------------------------------
# RA02 — determinism: legacy RNG + set-iteration order
# ---------------------------------------------------------------------------

def _is_setish(node: ast.AST, alias: dict[str, str]) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        name = resolve(alias, node.func)
        if name in ("set", "frozenset"):
            return True
    if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.BitOr, ast.BitAnd, ast.Sub, ast.BitXor)):
        return (_is_setish(node.left, alias)
                or _is_setish(node.right, alias))
    return False


def _check_ra02(ctx: FileContext) -> list:
    out = []
    np_legacy = set(CONFIG["legacy_np_random"])
    py_legacy = set(CONFIG["legacy_py_random"])
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            name = resolve(ctx.alias, node.func)
            if not name:
                continue
            parts = name.split(".")
            if (len(parts) == 3 and parts[0] == "numpy"
                    and parts[1] == "random" and parts[2] in np_legacy):
                out.append(_v("RA02", ctx, node,
                              f"legacy global-state RNG {name}(); thread an "
                              f"explicit np.random.Generator "
                              f"(np.random.default_rng(seed)) instead"))
            elif (len(parts) == 2 and parts[0] == "random"
                    and parts[1] in py_legacy):
                out.append(_v("RA02", ctx, node,
                              f"stdlib global-state RNG {name}(); use an "
                              f"explicit random.Random(seed) or "
                              f"np.random.default_rng(seed)"))
    if _in_scope(ctx.path, CONFIG["set_iteration_scope"]):
        # results consumed by an order-insensitive reducer are fine:
        # sorted(x for x in set(...)) is the *fix*, not a violation, and a
        # SetComp built from a set stays unordered by construction.
        unordered_ok: set[int] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                name = resolve(ctx.alias, node.func)
                if name in ("sorted", "min", "max", "sum", "any", "all",
                            "len", "set", "frozenset"):
                    for a in node.args:
                        unordered_ok.add(id(a))

        def flag_iter(it: ast.AST) -> None:
            if _is_setish(it, ctx.alias):
                out.append(_v("RA02", ctx, it,
                              "iteration over a set: ordering is "
                              "hash-randomized and must never reach wire "
                              "bytes, schedules, or serialized output — "
                              "wrap in sorted(...)"))
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.For, ast.AsyncFor)):
                flag_iter(node.iter)
            elif isinstance(node, (ast.ListComp, ast.GeneratorExp,
                                   ast.DictComp)):
                if id(node) in unordered_ok:
                    continue
                for gen in node.generators:
                    flag_iter(gen.iter)
            elif isinstance(node, ast.Call):
                name = resolve(ctx.alias, node.func)
                if name in ("list", "tuple", "enumerate") and node.args:
                    flag_iter(node.args[0])
    return out


_register(Rule(
    id="RA02", title="determinism: no unseeded/global RNG, no set-order "
                     "into wire bytes or schedules",
    check=_check_ra02, fixable=True,
    guards="hash-randomized or process-global entropy feeding wire bytes, "
           "scheduler order, or serialized output silently breaks replay "
           "signatures and RD caches"))


# ---------------------------------------------------------------------------
# RA03 — compat discipline (version-skewed jax surface only via shims)
# ---------------------------------------------------------------------------

def _check_ra03(ctx: FileContext) -> list:
    if ctx.path in CONFIG["compat_shims"]:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax.experimental" or a.name.startswith(
                        "jax.experimental."):
                    out.append(_v("RA03", ctx, node,
                                  f"raw import of {a.name}: the "
                                  f"jax.experimental surface renames across "
                                  f"releases; route through "
                                  f"kernels/compat.py or repro/compat.py"))
        elif isinstance(node, ast.ImportFrom) and node.module:
            if (node.module == "jax.experimental"
                    or node.module.startswith("jax.experimental.")):
                out.append(_v("RA03", ctx, node,
                              f"raw 'from {node.module} import ...': route "
                              f"through kernels/compat.py or "
                              f"repro/compat.py (the PR-2 API-skew class)"))
            elif node.module == "jax" and any(
                    a.name == "shard_map" for a in node.names):
                out.append(_v("RA03", ctx, node,
                              "'from jax import shard_map' skews across "
                              "releases (axis_names/auto, check_vma/"
                              "check_rep); use repro.compat.shard_map"))
        elif isinstance(node, ast.Attribute):
            name = resolve(ctx.alias, node)
            if not name:
                continue
            if name.startswith("jax.experimental."):
                out.append(_v("RA03", ctx, node,
                              f"raw use of {name}: route through the compat "
                              f"shims"))
            elif name == "jax.shard_map":
                out.append(_v("RA03", ctx, node,
                              "jax.shard_map called directly; "
                              "repro.compat.shard_map translates the "
                              "axis_names/check_vma spelling across jax "
                              "versions"))
            elif node.attr in ("CompilerParams", "TPUCompilerParams") and (
                    "pltpu" in name.split(".") or "pallas" in name):
                out.append(_v("RA03", ctx, node,
                              f"{name} is the renamed-across-releases "
                              f"compiler-params class; use "
                              f"kernels.compat.CompilerParams"))
    return out


_register(Rule(
    id="RA03", title="compat discipline: version-skewed jax APIs only via "
                     "the compat shims",
    check=_check_ra03,
    guards="the exact API-skew class that caused the 40 seed failures PR 2 "
           "burned down (CompilerParams/TPUCompilerParams, shard_map "
           "spellings, pallas module moves)"))


# ---------------------------------------------------------------------------
# RA05 — host-sync inside traced code
# ---------------------------------------------------------------------------

_TRACED_ENTRY_TAILS = ("jit", "shard_map", "pallas_call")


def _traced_function_defs(ctx: FileContext) -> list[ast.FunctionDef]:
    defs: dict[str, list[ast.FunctionDef]] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    traced: list[ast.FunctionDef] = []
    traced_names: set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = resolve(ctx.alias, target) or ""
                if name.split(".")[-1] in ("jit",):
                    traced.append(node)
                elif name.split(".")[-1] == "partial" and isinstance(
                        dec, ast.Call):
                    for a in dec.args:
                        an = resolve(ctx.alias, a) or ""
                        if an.split(".")[-1] == "jit":
                            traced.append(node)
                            break
        elif isinstance(node, ast.Call):
            name = resolve(ctx.alias, node.func) or ""
            if name.split(".")[-1] in _TRACED_ENTRY_TAILS:
                for a in node.args[:1]:
                    if isinstance(a, ast.Name):
                        traced_names.add(a.id)
    for name in traced_names:
        traced.extend(defs.get(name, []))
    return traced


def _check_ra05(ctx: FileContext) -> list:
    if not _in_scope(ctx.path, CONFIG["host_sync_scope"]):
        return []
    out = []
    seen: set[int] = set()
    for fn in _traced_function_defs(ctx):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            seen.add(id(node))
            where = f"traced body {fn.name}()"
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                out.append(_v("RA05", ctx, node,
                              f".item() inside {where}: host sync on a "
                              f"traced value (ConcretizationTypeError on "
                              f"jit, a stall at best)"))
                continue
            name = resolve(ctx.alias, node.func)
            if name in ("numpy.asarray", "numpy.array"):
                out.append(_v("RA05", ctx, node,
                              f"{name}() inside {where}: forces a device "
                              f"sync / fails under tracing; use jnp or move "
                              f"to the host side"))
            elif (isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int", "bool")
                    and node.func.id not in ctx.alias
                    and len(node.args) == 1
                    and not isinstance(node.args[0], ast.Constant)):
                out.append(_v("RA05", ctx, node,
                              f"builtin {node.func.id}() on a non-literal "
                              f"inside {where}: concretizes a traced value"))
    return out


_register(Rule(
    id="RA05", title="no host-sync (.item()/float()/np.asarray) in traced "
                     "bodies",
    check=_check_ra05,
    guards="host syncs inside jit/shard_map/Pallas bodies crash under "
           "tracing or silently serialize the device pipeline"))


# ---------------------------------------------------------------------------
# RA06 — silent failure
# ---------------------------------------------------------------------------

def _silent_body(body: list[ast.stmt]) -> bool:
    for stmt in body:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(
                stmt.value, ast.Constant):
            continue                      # docstring / Ellipsis
        return False
    return True


def _check_ra06(ctx: FileContext) -> list:
    if ctx.path in CONFIG["silent_except_allow_files"]:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            out.append(_v("RA06", ctx, node,
                          "bare 'except:' swallows KeyboardInterrupt and "
                          "SystemExit too; name the concrete exception "
                          "types"))
            continue
        name = resolve(ctx.alias, node.type)
        if name in ("Exception", "BaseException") and _silent_body(node.body):
            out.append(_v("RA06", ctx, node,
                          f"'except {name}: pass' silently discards every "
                          f"failure; narrow to the concrete types or "
                          f"handle/log the error"))
    return out


_register(Rule(
    id="RA06", title="no silent catch-alls", check=_check_ra06, fixable=True,
    guards="a swallowed exception on a serving or codec path turns a loud "
           "failure into a wrong-bytes one"))


# RA04 lives in repro.analysis.wire (it is cross-file: formats + revision
# constants + the committed fingerprint file); importing it here would cycle.
RA04_ID = "RA04"
RA04_TITLE = ("wire-format hygiene: pack/unpack symmetry, CRC coverage, and "
              "fingerprinted layouts that fail the build when edited without "
              "a codec_revision() bump")


# RA00 is the meta-rule for pragma hygiene (reason mandatory, no unused or
# unknown suppressions). It is emitted by the engine, never baselined.
RA00_ID = "RA00"

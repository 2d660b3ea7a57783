"""Repo-contract linter: one AST rule engine for the serving stack's invariants.

Every rule here encodes a contract an earlier PR established and some test
used to guard with ad-hoc `inspect.getsource` + substring checks. The engine
replaces those greps with AST facts (identifiers, call sites, assignment
targets — never comments or docstrings), so a docstring *mentioning* FLIT
sizes doesn't trip the gate but code *re-deriving* them does.

Rules (id — invariant — origin):

  R1  ucie-cost-isolation      serve/* and benchmarks/* own NO link math:
                               no hard-coded bandwidth/FLIT/latency
                               constants, no direct `ucie.transfer` calls
                               outside the one sanctioned accounting wrapper
                               (`serve/migration.migration_cost`).      PR 9
  R2  attn-core-unification    `_project_qkv` / `apply_rope` call sites live
                               only in the attention core (`attn_block`),
                               the MLA plug-in, and the recurrent family's
                               local-attention block.                    PR 7
  R3  replay-determinism       fault/health/sampling/migration/scheduler
                               code is replay-deterministic: no wall clocks,
                               no stdlib `random`, no unseeded np RNG.   PR 6
  R4  host-authority           scheduler/planner code is numpy-only (tables
                               are host-authoritative); no serve module
                               blocks the tick loop on `jax.device_get` /
                               `.item()`.                                PR 5
  R5  donation-safety          a buffer passed to a `donate_argnums` jit is
                               dead — never read again in the same scope.
                                                                         PR 1
  R6  pool-key-genericity      the ("k", "v") pool-key tuple is spelled out
                               only where the pool layout is DEFINED
                               (`transformer._pools_of`/`cache_shape`/...)
                               — everything else iterates the cache's own
                               keys so MLA's ("k",) pool keeps working. PR 7
  R7  pallas-hygiene           Pallas kernel bodies and BlockSpec index maps
                               are pure: no prints, no host numpy, no
                               clocks, no global state.                  PR 1

Escape hatch: a finding is suppressed by `# contract: allow(R3)` on the
offending line or the line directly above — every use must carry a comment
justifying it (the CLI prints suppressed counts so silent rot is visible).
Per-rule structural allowlists (the sanctioned definition sites above) live
on the Rule itself.

Pure stdlib on purpose — the CI lint job needs no jax install.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import pathlib
import re
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

# --------------------------------------------------------------------------
# findings / rules


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative, forward slashes
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.rule} {self.path}:{self.line} — {self.message}"

    def as_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Rule:
    """One contract. `check(module)` yields (node, message) pairs; the
    engine resolves lines, applies the structural `allow` list (path glob +
    enclosing-qualname glob) and the `# contract: allow(ID)` escape hatch."""
    id: str
    title: str
    rationale: str
    paths: Tuple[str, ...]                       # fnmatch globs the rule scans
    check: Callable[["Module"], Iterator[Tuple[ast.AST, str]]]
    allow: Tuple[Tuple[str, str], ...] = ()      # (path glob, qualname glob)


_ALLOW_RE = re.compile(r"#\s*contract:\s*allow\(([A-Za-z0-9_,\s]+)\)")


class Module:
    """One parsed file: AST + parent links + qualnames + allow-comments."""

    def __init__(self, root: pathlib.Path, path: pathlib.Path):
        self.rel = path.relative_to(root).as_posix()
        self.source = path.read_text()
        self.tree = ast.parse(self.source, filename=self.rel)
        self._parents: Dict[int, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parents[id(child)] = node
        self.allow_lines: Dict[int, Set[str]] = {}
        for lineno, text in enumerate(self.source.splitlines(), start=1):
            m = _ALLOW_RE.search(text)
            if m:
                ids = {s.strip() for s in m.group(1).split(",") if s.strip()}
                self.allow_lines[lineno] = ids

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(id(node))

    def qualname(self, node: ast.AST) -> str:
        """Dotted chain of enclosing function/class defs ('' at module
        scope). A def's own name is included for its body AND signature."""
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = self.parent(cur)
        return ".".join(reversed(parts))

    def line_allowed(self, line: int, rule_id: str) -> bool:
        for ln in (line, line - 1):
            if rule_id in self.allow_lines.get(ln, ()):
                return True
        return False


# --------------------------------------------------------------------------
# small AST helpers


def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(node: ast.Call) -> Optional[str]:
    """Last path segment of the callee ('f' for both f(...) and m.f(...))."""
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def _const_str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, (ast.Tuple, ast.List)) and all(
            isinstance(e, ast.Constant) and isinstance(e.value, str)
            for e in node.elts):
        return tuple(e.value for e in node.elts)
    return None


def _has_numeric_literal(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Constant) and isinstance(n.value, (int, float))
               and not isinstance(n.value, bool) for n in ast.walk(node))


# --------------------------------------------------------------------------
# R1 — UCIe cost isolation


_LINK_FIELDS = {"bandwidth_gbps", "latency_us", "pj_per_bit"}
_LINK_CONSTS = {"FLIT_BYTES", "HEADER_BYTES", "STREAM_BURST_FLITS"}
_LINK_NAME_TOKENS = ("gbps", "flit", "pj_per_bit")


def _check_ucie_isolation(mod: Module) -> Iterator[Tuple[ast.AST, str]]:
    # nodes inside a UCIeConfig(...) construction are sanctioned: building
    # the config that core/ucie prices with IS the one legitimate way to
    # name link parameters outside core/ucie
    sanctioned: Set[int] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and (call_name(node) or "").endswith(
                "UCIeConfig"):
            for sub in ast.walk(node):
                sanctioned.add(id(sub))
    for node in ast.walk(mod.tree):
        if id(node) in sanctioned:
            continue
        if isinstance(node, ast.Attribute) and node.attr in _LINK_FIELDS:
            yield node, (f"link parameter `.{node.attr}` read outside "
                         "core/ucie — price the transfer through "
                         "`ucie.transfer` / `ucie.migration_ticks` instead")
        elif isinstance(node, ast.Name) and node.id in _LINK_CONSTS:
            yield node, (f"UCIe wire constant `{node.id}` used outside "
                         "core/ucie — the FLIT framing belongs to the one "
                         "quantitative link model")
        elif isinstance(node, ast.Call):
            d = dotted(node.func) or ""
            if d == "ucie.transfer" or d.endswith(".ucie.transfer"):
                yield node, ("direct `ucie.transfer` call — serving code "
                             "prices link cost through "
                             "`ucie.migration_ticks` (or the sanctioned "
                             "`migration_cost` wrapper)")
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            value = node.value
            if value is None or not _has_numeric_literal(value):
                continue
            for t in targets:
                name = (t.id if isinstance(t, ast.Name) else
                        t.attr if isinstance(t, ast.Attribute) else "")
                low = name.lower()
                if any(tok in low for tok in _LINK_NAME_TOKENS) or \
                        low.endswith("bandwidth") or "latency_us" in low:
                    yield node, (f"hard-coded link constant `{name}` — "
                                 "Chiplet-Actuary lesson: ONE quantitative "
                                 "cost model (core/ucie), not scattered "
                                 "constants")


# --------------------------------------------------------------------------
# R2 — attention-core unification


_ATTN_PRIMITIVES = {"_project_qkv", "apply_rope"}


def _check_attn_core(mod: Module) -> Iterator[Tuple[ast.AST, str]]:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and call_name(node) in _ATTN_PRIMITIVES:
            yield node, (f"`{call_name(node)}` call outside the attention "
                         "core — schedule wrappers reach projections only "
                         "through `attn_block(mode=...)` (PR 7 deleted the "
                         "mirrored QKV/rope bodies; don't grow them back)")
        elif isinstance(node, ast.ImportFrom):
            hit = [a.name for a in node.names if a.name in _ATTN_PRIMITIVES]
            if hit:
                yield node, (f"import of {', '.join(hit)} outside the "
                             "attention core / its plug-ins")


# --------------------------------------------------------------------------
# R3 — replay determinism


_SEEDED_NP_RANDOM = {"default_rng", "Generator", "SeedSequence"}


def _check_deterministic_replay(mod: Module) -> Iterator[Tuple[ast.AST, str]]:
    why = ("fault/sampling/migration paths replay bit-for-bit from a seed — "
           "a wall clock or ambient RNG breaks `chaos_token_divergence == 0`")
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name in ("time", "random", "datetime"):
                    yield node, f"`import {a.name}` in a replay-deterministic module — {why}"
        elif isinstance(node, ast.ImportFrom):
            if node.module in ("time", "random", "datetime"):
                yield node, f"`from {node.module} import ...` in a replay-deterministic module — {why}"
        elif isinstance(node, ast.Attribute):
            d = dotted(node) or ""
            if d.startswith("time.") or d.startswith("random."):
                yield node, f"`{d}` — {why}"
            elif d in ("datetime.now", "datetime.utcnow", "datetime.today") \
                    or d.startswith("datetime.datetime."):
                yield node, f"`{d}` — {why}"
            elif d.startswith("np.random.") or d.startswith("numpy.random."):
                leaf = d.rsplit(".", 1)[1]
                if leaf not in _SEEDED_NP_RANDOM:
                    yield node, (f"`{d}` draws from numpy's AMBIENT global "
                                 f"stream — {why}; use a seeded "
                                 "`np.random.default_rng(seed)`")
        elif isinstance(node, ast.Call):
            d = dotted(node.func) or ""
            if d.endswith("random.default_rng") and not node.args \
                    and not node.keywords:
                yield node, (f"`{d}()` without a seed is entropy-seeded — "
                             f"{why}")


# --------------------------------------------------------------------------
# R4 — host authority


_NUMPY_ONLY_FILES = {
    "src/repro/serve/scheduler.py",   # host-authoritative tables/free lists
    "src/repro/serve/migration.py",   # pure planner over scheduler views
}


def _check_host_authority(mod: Module) -> Iterator[Tuple[ast.AST, str]]:
    numpy_only = mod.rel in _NUMPY_ONLY_FILES
    for node in ast.walk(mod.tree):
        if numpy_only:
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax" or a.name.startswith("jax."):
                        yield node, ("scheduler/planner code is HOST-"
                                     "authoritative: page tables and free "
                                     "lists are np arrays fed per tick — "
                                     "importing jax here invites per-tick "
                                     "device sync and retraces")
            elif isinstance(node, ast.ImportFrom):
                if node.module and (node.module == "jax"
                                    or node.module.startswith("jax.")):
                    yield node, ("scheduler/planner code is host-"
                                 "authoritative (numpy-only) — no jax "
                                 "imports")
            elif isinstance(node, ast.Name) and node.id == "jnp":
                yield node, ("`jnp` in host-authoritative planner code — "
                             "use `np`; device math belongs in the jitted "
                             "engine step")
        if isinstance(node, ast.Call):
            d = dotted(node.func) or ""
            if d == "jax.device_get":
                yield node, ("`jax.device_get` in the serving stack — the "
                             "tick loop keeps ONE host sync per step (the "
                             "emitted tokens); ad-hoc gets serialize the "
                             "pipeline")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                yield node, (".item() forces a device->host sync — pull "
                             "values through the step's one batched token "
                             "sync instead")


# --------------------------------------------------------------------------
# R5 — donation safety


def _donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """(positions,) if `call` is jax.jit(..., donate_argnums=<literal>)."""
    d = dotted(call.func) or ""
    if not (d == "jax.jit" or d.endswith(".jit") or d == "jit"):
        return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)) and all(
                    isinstance(e, ast.Constant) and isinstance(e.value, int)
                    for e in v.elts):
                return tuple(e.value for e in v.elts)
    return None


def _bound_name(target: ast.AST) -> Optional[str]:
    if isinstance(target, ast.Name):
        return target.id
    if isinstance(target, ast.Attribute) and \
            isinstance(target.value, ast.Name) and target.value.id == "self":
        return target.attr
    return None


def _stmt_reads(stmt: ast.stmt, skip: Set[int]) -> Iterator[ast.Name]:
    for n in ast.walk(stmt):
        if id(n) in skip:
            continue
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
            yield n


def _stmt_stores(stmt: ast.stmt) -> Set[str]:
    out: Set[str] = set()
    for n in ast.walk(stmt):
        if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store,
                                                          ast.Del)):
            out.add(n.id)
    return out


def _flat_stmts(body: Sequence[ast.stmt]) -> Iterator[ast.stmt]:
    """Statements in source order, descending into compound bodies but NOT
    into nested function defs (their scope is analyzed separately)."""
    for stmt in body:
        yield stmt
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        for field in ("body", "orelse", "finalbody"):
            yield from _flat_stmts(getattr(stmt, field, []) or [])
        for handler in getattr(stmt, "handlers", []) or []:
            yield from _flat_stmts(handler.body)


def _check_donation_safety(mod: Module) -> Iterator[Tuple[ast.AST, str]]:
    # pass 1: names bound to jax.jit(..., donate_argnums=<literal>)
    donated: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            pos = _donated_positions(node.value)
            if pos is None:
                continue
            for t in node.targets:
                name = _bound_name(t)
                if name:
                    donated[name] = pos
    if not donated:
        return
    # pass 2: per function scope, flag reads of a donated buffer after the
    # donating call (a donated buffer's storage is re-used by the output —
    # reading it afterwards returns garbage or raises on device)
    scopes = [n for n in ast.walk(mod.tree)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    scopes.append(mod.tree)  # module scope
    for scope in scopes:
        body = scope.body
        live: Dict[str, str] = {}      # donated var -> donating jit name
        for stmt in _flat_stmts(body):
            # donating calls in this statement
            marks: Dict[str, str] = {}
            call_arg_ids: Set[int] = set()
            for n in ast.walk(stmt):
                if not isinstance(n, ast.Call):
                    continue
                cname = _bound_name(n.func) if isinstance(
                    n.func, ast.Attribute) else (
                    n.func.id if isinstance(n.func, ast.Name) else None)
                if cname not in donated:
                    continue
                for i in donated[cname]:
                    if i < len(n.args) and isinstance(n.args[i], ast.Name):
                        marks[n.args[i].id] = cname
                        call_arg_ids.add(id(n.args[i]))
            # reads of already-donated buffers (the donating call's own
            # argument doesn't count)
            for name_node in _stmt_reads(stmt, call_arg_ids):
                if name_node.id in live:
                    yield name_node, (
                        f"`{name_node.id}` read after being donated to "
                        f"`{live[name_node.id]}` — donate_argnums hands the "
                        "buffer to XLA; rebind the result instead of "
                        "touching the dead operand")
            # stores kill both existing marks and this statement's own
            # (x = f(x) rebinds x to the result — safe)
            for stored in _stmt_stores(stmt):
                live.pop(stored, None)
                marks.pop(stored, None)
            live.update(marks)


# --------------------------------------------------------------------------
# R6 — pool-key genericity


def _check_pool_keys(mod: Module) -> Iterator[Tuple[ast.AST, str]]:
    for node in ast.walk(mod.tree):
        if _const_str_tuple(node) == ("k", "v"):
            yield node, ('literal ("k", "v") pool-key tuple — iterate the '
                         "cache's own pools (`transformer.pool_data_keys`) "
                         "so MLA's single ('k',) latent pool keeps working")


# --------------------------------------------------------------------------
# R7 — Pallas hygiene


_HOST_CALL_PREFIXES = ("np.", "numpy.", "time.", "random.", "jax.debug.")
_HOST_CALLS = {"print", "open", "input", "breakpoint", "device_get"}


def _kernel_bodies(mod: Module) -> Iterator[Tuple[ast.AST, str]]:
    """(function node, why-it's-a-kernel) for kernel bodies + index maps."""
    named: Dict[str, ast.FunctionDef] = {
        n.name: n for n in ast.walk(mod.tree)
        if isinstance(n, ast.FunctionDef)}
    seen: Set[int] = set()

    def emit(fn: ast.AST, kind: str):
        if id(fn) not in seen:
            seen.add(id(fn))
            yield fn, kind

    for name, fn in named.items():
        if name.endswith("_kernel"):
            yield from emit(fn, "kernel body")
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        cn = call_name(node) or ""
        if cn == "pallas_call" and node.args:
            a = node.args[0]
            if isinstance(a, ast.Name) and a.id in named:
                yield from emit(named[a.id], "kernel body")
            elif isinstance(a, ast.Lambda):
                yield from emit(a, "kernel body")
        elif cn == "BlockSpec":
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(a, ast.Lambda):
                    yield from emit(a, "BlockSpec index map")
                elif isinstance(a, ast.Name) and a.id in named:
                    yield from emit(named[a.id], "BlockSpec index map")


def _check_pallas_hygiene(mod: Module) -> Iterator[Tuple[ast.AST, str]]:
    for fn, kind in _kernel_bodies(mod):
        body = fn.body if isinstance(fn, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)) else [fn]
        for node in (n for stmt in body for n in ast.walk(stmt)):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                yield node, (f"{kind} mutates enclosing scope — kernels and "
                             "index maps must be pure (they trace once and "
                             "replay on device)")
            elif isinstance(node, ast.Call):
                d = dotted(node.func) or ""
                leaf = call_name(node) or ""
                if leaf in _HOST_CALLS or any(
                        d.startswith(p) for p in _HOST_CALL_PREFIXES):
                    yield node, (f"host call `{d or leaf}` inside a {kind} "
                                 "— Python side effects don't exist on the "
                                 "device; they fire at trace time only and "
                                 "silently desync from execution")


# --------------------------------------------------------------------------
# the rule table


RULES: Tuple[Rule, ...] = (
    Rule(
        id="R1",
        title="UCIe cost isolation",
        rationale="ONE quantitative interconnect model (core/ucie.transfer) "
                  "prices every cross-chiplet byte — serving and benches "
                  "never re-derive link math (PR 9).",
        paths=("src/repro/serve/*.py", "benchmarks/*.py"),
        check=_check_ucie_isolation,
        allow=(
            # THE sanctioned accounting wrapper, numerically pinned by
            # tests/test_migration.py::test_ucie_single_call_path
            ("src/repro/serve/migration.py", "migration_cost"),
        ),
    ),
    Rule(
        id="R2",
        title="attention-core unification",
        rationale="QKV projection + rope run in exactly one place per "
                  "family; schedule wrappers call attn_block(mode=...) "
                  "(PR 7).",
        paths=("src/**/*.py",),
        check=_check_attn_core,
        allow=(
            # the definitions themselves
            ("src/repro/models/common.py", "*"),
            # THE core: attn_block owns all four execution modes (the
            # module-scope entry is its import of the primitives)
            ("src/repro/models/transformer.py", "attn_block"),
            ("src/repro/models/transformer.py", ""),
            # the MLA plug-in family (absorbed attention, own rope layout)
            ("src/repro/models/mla.py", "*"),
            # the recurrent family's windowed local attention — a different
            # primitive, not a decoder-core mirror
            ("src/repro/models/rglru.py", "*"),
        ),
    ),
    Rule(
        id="R3",
        title="replay determinism",
        rationale="chaos/migration parity gates replay a seeded plan "
                  "bit-for-bit; a clock or ambient RNG anywhere in these "
                  "modules breaks divergence==0 (PR 6).",
        paths=(
            "src/repro/serve/faults.py",
            "src/repro/serve/health.py",
            "src/repro/serve/sampling.py",
            "src/repro/serve/migration.py",
            "src/repro/serve/scheduler.py",
        ),
        check=_check_deterministic_replay,
        allow=(),
    ),
    Rule(
        id="R4",
        title="host authority",
        rationale="page tables / free lists are host np state fed per tick; "
                  "planners stay numpy-only and the tick loop holds ONE "
                  "device sync per step (PR 5).",
        paths=("src/repro/serve/*.py",),
        check=_check_host_authority,
        allow=(),
    ),
    Rule(
        id="R5",
        title="donation safety",
        rationale="donate_argnums re-uses the operand's storage for the "
                  "output; reading a donated buffer afterwards is garbage "
                  "on TPU and only *happens* to work on CPU (PR 1).",
        paths=("src/**/*.py",),
        check=_check_donation_safety,
        allow=(),
    ),
    Rule(
        id="R6",
        title="pool-key genericity",
        rationale="cache pools are keyed per family — GQA ('k','v'), MLA "
                  "('k',); spelled-out key tuples outside the layout "
                  "definition silently skip MLA pools (PR 7).",
        paths=("src/**/*.py",),
        check=_check_pool_keys,
        allow=(
            # the layout-definition sites: the one place the key set is law
            ("src/repro/models/transformer.py", "_pools_of"),
            ("src/repro/models/transformer.py", "pool_data_keys"),
            ("src/repro/models/transformer.py", "cache_shape"),
            ("src/repro/models/transformer.py", "paged_kv_shapes"),
            # the checker that defines the forbidden pattern may spell it
            ("src/repro/analysis/contracts.py", "_check_pool_keys"),
        ),
    ),
    Rule(
        id="R7",
        title="Pallas hygiene",
        rationale="kernel bodies and BlockSpec index maps trace once and "
                  "replay on device — host calls/side effects silently "
                  "desync from execution (PR 1).",
        paths=("src/repro/kernels/*.py",),
        check=_check_pallas_hygiene,
        allow=(),
    ),
)


def rules_by_id(ids: Optional[Iterable[str]]) -> Tuple[Rule, ...]:
    if ids is None:
        return RULES
    ids = list(ids)
    by_id = {r.id: r for r in RULES}
    unknown = [i for i in ids if i not in by_id]
    if unknown:
        raise ValueError(f"unknown rule id(s) {unknown}; have "
                         f"{sorted(by_id)}")
    return tuple(by_id[i] for i in ids)


# --------------------------------------------------------------------------
# the engine


DEFAULT_SCAN = ("src/**/*.py", "benchmarks/*.py")


def _scan_files(root: pathlib.Path) -> List[pathlib.Path]:
    out: List[pathlib.Path] = []
    for glob in DEFAULT_SCAN:
        out.extend(p for p in sorted(root.glob(glob))
                   if "__pycache__" not in p.parts)
    return out


def _allowed_context(rule: Rule, rel: str, qual: str) -> bool:
    for path_glob, qual_glob in rule.allow:
        if not fnmatch.fnmatch(rel, path_glob):
            continue
        if qual_glob == "*" or fnmatch.fnmatch(qual, qual_glob) \
                or qual.startswith(qual_glob + "."):
            return True
    return False


def run_rules(root, rules: Optional[Sequence] = None,
              files: Optional[Sequence[pathlib.Path]] = None,
              collect_suppressed: Optional[List[Finding]] = None,
              ) -> List[Finding]:
    """Run the contract rules over the tree at `root`.

    `rules` — Rule objects or rule-id strings (default: all of RULES).
    `files` — explicit file list (default: DEFAULT_SCAN globs under root).
    `collect_suppressed` — optional sink for findings silenced by
    `# contract: allow(...)` comments, so callers can surface the count.
    Returns findings sorted by (path, line, rule).
    """
    root = pathlib.Path(root)
    if rules is not None and any(isinstance(r, str) for r in rules):
        rules = rules_by_id([r if isinstance(r, str) else r.id
                             for r in rules])
    rule_set: Sequence[Rule] = tuple(rules) if rules is not None else RULES
    findings: List[Finding] = []
    for path in (files if files is not None else _scan_files(root)):
        path = pathlib.Path(path)
        rel = path.relative_to(root).as_posix()
        applicable = [r for r in rule_set
                      if any(fnmatch.fnmatch(rel, g) for g in r.paths)]
        if not applicable:
            continue
        mod = Module(root, path)
        for rule in applicable:
            for node, message in rule.check(mod):
                line = getattr(node, "lineno", 1)
                if _allowed_context(rule, rel, mod.qualname(node)):
                    continue
                f = Finding(rule=rule.id, path=rel, line=line,
                            message=message)
                if mod.line_allowed(line, rule.id):
                    if collect_suppressed is not None:
                        collect_suppressed.append(f)
                    continue
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings

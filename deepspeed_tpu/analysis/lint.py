"""Repo-invariant linter: AST-level rules the test suite enforces.

Three invariants this tree has paid for learning, now encoded so CI fails
the moment a patch re-violates one (``tests/unit/test_lint.py``):

R1 **raw shard_map** — ``jax.shard_map`` / ``jax.experimental.shard_map``
   moved twice across jax releases (``check_rep`` -> ``check_vma``,
   ``auto`` -> ``axis_names``); every module must go through
   ``utils/shard_map_compat`` so the next move is a one-file edit.
R2 **host syncs in default-on paths** — ``block_until_ready`` /
   ``jax.device_get`` in ``runtime/engine.py`` or ``telemetry/`` serialize
   the async dispatch pipeline for every user.  Deliberate sites (the
   telemetry drain span, offload transfers) carry a ``# sync-ok:`` comment
   naming why; anything unannotated fails.
R3 **mutable default args in public APIs** — a ``def f(x, acc=[])`` in a
   public function is shared state across calls; forbidden outside
   underscore-private functions.
R4 **silent error swallows in failure-handling code** — a bare
   ``except Exception: pass`` inside ``runtime/resilience/``, ``serving/``
   or ``control/`` hides exactly the errors that subsystem exists to
   surface (a swallowed transport error is an invisible dead host).
   Deliberate sites carry a ``# swallow-ok: <reason>`` comment naming why;
   anything unannotated fails.
R5 **raw PartitionSpec literals outside the sharding subsystem** — every
   inline ``P(...)`` is a sharding decision hidden from the declarative
   rules layer (``deepspeed_tpu/sharding/``): it cannot be audited,
   renamed with the mesh, or overridden by a rule pack.  Construct specs
   through ``sharding.sites`` / ``sharding.rules`` instead.  The few
   mechanical survivors (per-leaf spec *surgery* like ZeRO free-dim
   claiming, not layout *choices*) carry a ``# spec-ok: <reason>``
   comment; anything unannotated fails.

Stdlib-only (ast + tokenize); no jax import, so the lint test runs even
where jax is broken.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import tokenize
from typing import Dict, Iterable, List, Optional, Set, Tuple

#: modules allowed to touch raw shard_map (the shared wrapper itself)
SHARD_MAP_EXEMPT = ("utils/shard_map_compat.py",)
#: path prefixes where host syncs are forbidden unless annotated: the
#: engine hot path, the (default-off but attach-everywhere) telemetry,
#: and the integrity tier — whose whole design contract is "no hot-path
#: host sync" (digests are fetched one step delayed; only the harvest
#: and the off-path shadow replay may sync, each with a sync-ok blessing)
HOST_SYNC_SCOPED = ("runtime/engine.py", "telemetry/",
                    "runtime/resilience/integrity.py")
#: the annotation that blesses one host-sync line: `# sync-ok: <why>`
SYNC_OK_MARKER = "sync-ok:"
#: path prefixes where silent `except Exception: pass` is forbidden: the
#: failure-handling tiers, where a swallowed error IS the failure
SWALLOW_SCOPED = ("runtime/resilience/", "serving/", "control/")
#: the annotation that blesses one deliberate swallow: `# swallow-ok: <why>`
SWALLOW_OK_MARKER = "swallow-ok:"
#: the one package allowed to construct PartitionSpec directly: the
#: declarative sharding subsystem, the single source of layout truth
SPEC_EXEMPT = ("sharding/",)
#: the annotation that blesses one deliberate raw-spec line: `# spec-ok: <why>`
SPEC_OK_MARKER = "spec-ok:"

_HOST_SYNC_NAMES = ("block_until_ready", "device_get")
_MUTABLE_DEFAULTS = (ast.List, ast.Dict, ast.Set)
_BROAD_EXC_NAMES = ("Exception", "BaseException")


@dataclasses.dataclass(frozen=True)
class LintFinding:
    rule: str        # 'raw-shard-map' | 'host-sync' | 'mutable-default'
                     # | 'swallow' | 'raw-partition-spec'
    path: str        # repo-relative
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _annotated_lines(source: str, marker: str = SYNC_OK_MARKER) -> Set[int]:
    """Line numbers carrying the given blessing marker comment."""
    out: Set[int] = set()
    try:
        import io

        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT and marker in tok.string:
                out.add(tok.start[0])
    except (tokenize.TokenError, IndentationError):
        pass
    return out


def _call_name_chain(node: ast.AST) -> List[str]:
    """['jax', 'device_get'] for ``jax.device_get`` etc."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return parts[::-1]


def _lint_shard_map(tree: ast.AST, rel: str,
                    findings: List[LintFinding]) -> None:
    if any(rel.endswith(x) for x in SHARD_MAP_EXEMPT):
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            names = {a.name for a in node.names}
            if mod == "jax.experimental.shard_map" or (
                    mod == "jax" and "shard_map" in names) or (
                    mod == "jax.experimental" and "shard_map" in names):
                findings.append(LintFinding(
                    "raw-shard-map", rel, node.lineno,
                    "import shard_map via utils/shard_map_compat (the one "
                    "module that spells the jax API)"))
        elif isinstance(node, ast.Attribute):
            chain = _call_name_chain(node)
            if chain[-1:] == ["shard_map"] and chain[:1] == ["jax"]:
                findings.append(LintFinding(
                    "raw-shard-map", rel, node.lineno,
                    "jax.shard_map used directly; go through "
                    "utils/shard_map_compat"))


def _lint_host_sync(tree: ast.AST, rel: str, source: str,
                    findings: List[LintFinding]) -> None:
    if not any(rel.startswith(p) or f"/{p}" in rel
               for p in HOST_SYNC_SCOPED):
        return
    blessed = _annotated_lines(source)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _call_name_chain(node.func)
        if not chain:
            continue
        leaf = chain[-1]
        if leaf in _HOST_SYNC_NAMES:
            # the marker blesses its own line, the statement's last line,
            # or the line directly above (long statements annotate above)
            if (node.lineno in blessed or (node.end_lineno or 0) in blessed
                    or node.lineno - 1 in blessed):
                continue
            findings.append(LintFinding(
                "host-sync", rel, node.lineno,
                f"{'.'.join(chain)} in a default-on path forces a device "
                f"sync; annotate the line '# {SYNC_OK_MARKER} <why>' if "
                f"deliberate"))


def _lint_swallows(tree: ast.AST, rel: str, source: str,
                   findings: List[LintFinding]) -> None:
    if not any(rel.startswith(p) or f"/{p}" in rel for p in SWALLOW_SCOPED):
        return
    blessed = _annotated_lines(source, SWALLOW_OK_MARKER)
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        # broad handler: bare `except:` or `except (Base)Exception:`
        t = node.type
        names = []
        for n in ([t] if not isinstance(t, ast.Tuple) else t.elts) \
                if t is not None else []:
            if isinstance(n, ast.Name):
                names.append(n.id)
            elif isinstance(n, ast.Attribute):
                names.append(n.attr)
        broad = t is None or any(n in _BROAD_EXC_NAMES for n in names)
        if not broad:
            continue
        # a silent swallow: the handler body is a single `pass`
        if not (len(node.body) == 1 and isinstance(node.body[0], ast.Pass)):
            continue
        pass_line = node.body[0].lineno
        # the marker blesses the except line, the line above it, or the
        # pass line itself — NOT the line after the pass, where a comment
        # documenting the NEXT statement would silently bless an
        # unannotated swallow above it
        if any(ln in blessed for ln in (node.lineno, node.lineno - 1,
                                        pass_line)):
            continue
        findings.append(LintFinding(
            "swallow", rel, node.lineno,
            "bare `except Exception: pass` in failure-handling code hides "
            "the errors this tier exists to surface; handle it, or "
            f"annotate '# {SWALLOW_OK_MARKER} <why>' if deliberate"))


def _lint_partition_specs(tree: ast.AST, rel: str, source: str,
                          findings: List[LintFinding]) -> None:
    if any(rel.startswith(p) or f"/{p}" in rel for p in SPEC_EXEMPT):
        return
    # local names bound to PartitionSpec by imports (P, PSpec, ...)
    aliases: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name == "PartitionSpec":
                    aliases.add(a.asname or a.name)
    blessed = _annotated_lines(source, SPEC_OK_MARKER)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        raw = (isinstance(f, ast.Name) and f.id in aliases) or (
            isinstance(f, ast.Attribute)
            and _call_name_chain(f)[-1:] == ["PartitionSpec"])
        if not raw:
            continue
        if (node.lineno in blessed or (node.end_lineno or 0) in blessed
                or node.lineno - 1 in blessed):
            continue
        findings.append(LintFinding(
            "raw-partition-spec", rel, node.lineno,
            "raw PartitionSpec literal outside deepspeed_tpu/sharding/ "
            "hides a layout decision from the rules layer; use "
            "sharding.sites / a RuleSet, or annotate "
            f"'# {SPEC_OK_MARKER} <why>' if it is mechanical spec surgery"))


def _lint_mutable_defaults(tree: ast.AST, rel: str,
                           findings: List[LintFinding]) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name.startswith("_"):
            continue  # private API: caller beware
        args = node.args
        for arg, default in zip(
                (args.posonlyargs + args.args)[-len(args.defaults):]
                if args.defaults else [],
                args.defaults):
            if isinstance(default, _MUTABLE_DEFAULTS):
                findings.append(LintFinding(
                    "mutable-default", rel, default.lineno,
                    f"public def {node.name}(... {arg.arg}="
                    f"{type(default).__name__.lower()}()): mutable default "
                    f"is shared across calls; use None + init inside"))
        for arg, default in zip(args.kwonlyargs, args.kw_defaults):
            if isinstance(default, _MUTABLE_DEFAULTS):
                findings.append(LintFinding(
                    "mutable-default", rel, default.lineno,
                    f"public def {node.name}(..., *, {arg.arg}=...): "
                    f"mutable default is shared across calls"))


def lint_source(source: str, rel_path: str) -> List[LintFinding]:
    """All rule violations in one module's source."""
    findings: List[LintFinding] = []
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [LintFinding("raw-shard-map", rel_path, e.lineno or 0,
                            f"unparseable: {e.msg}")]
    _lint_shard_map(tree, rel_path, findings)
    _lint_host_sync(tree, rel_path, source, findings)
    _lint_swallows(tree, rel_path, source, findings)
    _lint_partition_specs(tree, rel_path, source, findings)
    _lint_mutable_defaults(tree, rel_path, findings)
    return findings


def lint_paths(root: str,
               rel_paths: Optional[Iterable[str]] = None
               ) -> List[LintFinding]:
    """Lint every ``.py`` under ``root`` (or just ``rel_paths``), skipping
    caches.  ``root`` should be the package dir (``deepspeed_tpu/``)."""
    findings: List[LintFinding] = []
    if rel_paths is None:
        rel_paths = []
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".py"):
                    rel_paths.append(os.path.relpath(
                        os.path.join(dirpath, fn), root))
    for rel in sorted(rel_paths):
        path = os.path.join(root, rel)
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        except OSError:
            continue
        findings.extend(lint_source(source, rel.replace(os.sep, "/")))
    return findings

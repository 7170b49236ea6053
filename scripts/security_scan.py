#!/usr/bin/env python
"""Security scan analogue of the reference's security workflow.

The reference runs gosec + Trivy + nancy + CodeQL weekly
(/root/reference/.github/workflows/security.yml:28-105). This image is
hermetic (no pip installs, zero egress), so the equivalent is built
natively:

* static scan (gosec/bandit analogue): an AST walk over all first-party
  Python flagging the classic dangerous-call patterns — exec/eval,
  subprocess with shell=True, pickle deserialization, weak hashes used
  outside tests, yaml.load without a safe loader, hardcoded secrets,
  binding 0.0.0.0 by default, tempfile.mktemp, and SQL string
  interpolation.
* dependency audit (nancy/pip-audit analogue): inventories every
  installed distribution with importlib.metadata and cross-checks the
  pins in requirements.txt against what is actually installed. The
  advisory-DB lookup (the online half of pip-audit) is explicitly
  gated: with no egress there is nothing to fetch, so the inventory is
  recorded as the auditable artifact instead, and the gate is printed
  so the transcript can't be mistaken for a vulnerability clearance.

Exit code: nonzero on any HIGH finding. MEDIUM/LOW are reported but do
not gate (matching the reference's gosec severity threshold usage).
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys
from dataclasses import dataclass

ROOT = pathlib.Path(__file__).resolve().parent.parent

SCAN_DIRS = ["ggrmcp_tpu", "scripts", "examples", "tests"]
SCAN_FILES = ["__graft_entry__.py"]

# Names whose string-literal assignment looks like an embedded secret.
SECRET_NAME = re.compile(
    r"(password|passwd|secret|api_key|apikey|auth_token|private_key)",
    re.IGNORECASE,
)
# Values that are clearly placeholders, not credentials.
PLACEHOLDER = re.compile(
    r"^$|^(x+|\*+|<[^>]*>|\{[^}]*\}|dummy|test|example|changeme|redacted)$",
    re.IGNORECASE,
)
SQL_VERB = re.compile(
    r"^\s*(select\s.+\sfrom|insert\s+into|update\s.+\sset|delete\s+from)\s",
    re.IGNORECASE,
)


@dataclass
class Finding:
    severity: str  # HIGH / MEDIUM / LOW
    rule: str
    path: str
    line: int
    detail: str

    def fmt(self) -> str:
        return (
            f"[{self.severity:^6}] {self.rule:22} "
            f"{self.path}:{self.line}  {self.detail}"
        )


def _call_name(node: ast.Call) -> str:
    """Dotted name of the called object, best-effort ('' if dynamic)."""
    parts: list[str] = []
    f = node.func
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
        return ".".join(reversed(parts))
    return ""


def _kw(node: ast.Call, name: str):
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


class Scanner(ast.NodeVisitor):
    def __init__(self, rel: str, is_test: bool):
        self.rel = rel
        self.is_test = is_test
        self.findings: list[Finding] = []

    def add(self, sev: str, rule: str, node: ast.AST, detail: str) -> None:
        self.findings.append(
            Finding(sev, rule, self.rel, getattr(node, "lineno", 0), detail)
        )

    def visit_Call(self, node: ast.Call) -> None:
        name = _call_name(node)
        base = name.split(".")[-1]

        if base in ("eval", "exec") and "." not in name:
            # ast.literal_eval etc. keep their prefix and fall through.
            self.add(
                "HIGH", "exec-eval", node,
                f"{base}() executes dynamic code",
            )
        if name.startswith("subprocess.") or base in (
            "Popen", "call", "check_call", "check_output", "run",
        ):
            shell = _kw(node, "shell")
            if isinstance(shell, ast.Constant) and shell.value is True:
                sev = "MEDIUM" if self.is_test else "HIGH"
                self.add(
                    sev, "subprocess-shell", node,
                    "shell=True invites injection; pass an argv list",
                )
        if name in ("os.system", "os.popen"):
            self.add(
                "HIGH", "os-system", node,
                f"{name}() runs through the shell; use subprocess with argv",
            )
        if name in ("pickle.load", "pickle.loads", "pickle.Unpickler",
                    "cPickle.load", "cPickle.loads", "dill.load",
                    "dill.loads", "shelve.open", "marshal.load",
                    "marshal.loads", "torch.load"):
            sev = "LOW" if self.is_test else "MEDIUM"
            self.add(
                sev, "unsafe-deserialize", node,
                f"{name}() deserializes arbitrary objects",
            )
        if name in ("yaml.load", "yaml.full_load", "yaml.unsafe_load"):
            loader = _kw(node, "Loader")
            safe = isinstance(loader, ast.Attribute) and loader.attr in (
                "SafeLoader", "CSafeLoader", "BaseLoader",
            )
            if name != "yaml.load" or not safe:
                self.add(
                    "HIGH", "yaml-unsafe-load", node,
                    "yaml.load without SafeLoader constructs objects",
                )
        if name in ("hashlib.md5", "hashlib.sha1"):
            # Weak for signatures/passwords; fine for cache keys — the
            # call sites here must carry usedforsecurity=False to state
            # that, else flag for review.
            ufs = _kw(node, "usedforsecurity")
            if not (isinstance(ufs, ast.Constant) and ufs.value is False):
                self.add(
                    "MEDIUM", "weak-hash", node,
                    f"{name} without usedforsecurity=False",
                )
        if name == "tempfile.mktemp":
            self.add(
                "HIGH", "insecure-tempfile", node,
                "mktemp() is race-prone; use NamedTemporaryFile/mkstemp",
            )
        if name in ("random.random", "random.randint", "random.choice",
                    "random.randbytes", "random.getrandbits"):
            # Only a problem when feeding identifiers/secrets; the model
            # plane's use of `random` is seeded reproducibility, so LOW.
            self.add(
                "LOW", "non-crypto-random", node,
                f"{name}: not for security tokens (sessions use secrets)",
            )
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        if isinstance(node.value, ast.Constant) and isinstance(
            node.value.value, str
        ):
            val = node.value.value
            for tgt in node.targets:
                tname = tgt.id if isinstance(tgt, ast.Name) else (
                    tgt.attr if isinstance(tgt, ast.Attribute) else ""
                )
                if (
                    tname
                    and SECRET_NAME.search(tname)
                    and val
                    and not PLACEHOLDER.match(val)
                    and len(val) >= 8
                ):
                    sev = "LOW" if self.is_test else "HIGH"
                    self.add(
                        sev, "hardcoded-secret", node,
                        f"string literal assigned to '{tname}'",
                    )
                if SQL_VERB.match(val) and "%s" in val:
                    self.add(
                        "MEDIUM", "sql-format", node,
                        "SQL with %-interpolation; parameterize",
                    )
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant) -> None:
        if node.value == "0.0.0.0":
            sev = "LOW" if self.is_test else "MEDIUM"
            self.add(
                sev, "bind-all-interfaces", node,
                "literal 0.0.0.0 bind; ensure it is config-overridable",
            )
        self.generic_visit(node)


def scan_tree(root: pathlib.Path = ROOT) -> list[Finding]:
    """Static-scan every first-party source under `root`. Parameterized
    so the tier-1 smoke test (tests/test_graftlint.py) can run the real
    scanner over a fixture tree with a planted HIGH finding and assert
    the gate actually trips — the scanner itself must not silently rot."""
    findings: list[Finding] = []
    files: list[pathlib.Path] = []
    for d in SCAN_DIRS:
        if (root / d).is_dir():
            files.extend(sorted((root / d).rglob("*.py")))
    files.extend(root / f for f in SCAN_FILES)
    self_path = pathlib.Path(__file__).resolve()
    for path in files:
        if not path.exists() or path.resolve() == self_path:
            continue  # the rule literals would flag themselves
        rel = str(path.relative_to(root))
        is_test = rel.startswith("tests/")
        try:
            tree = ast.parse(path.read_text(), filename=rel)
        except SyntaxError as exc:
            findings.append(
                Finding("HIGH", "syntax-error", rel, exc.lineno or 0,
                        "unparseable source")
            )
            continue
        sc = Scanner(rel, is_test)
        sc.visit(tree)
        findings.extend(sc.findings)
    return findings


def dependency_audit() -> tuple[list[str], list[str]]:
    """Installed-distribution inventory + requirements.txt pin check.
    Returns (report_lines, problems)."""
    import importlib.metadata as md

    lines: list[str] = []
    problems: list[str] = []
    installed = {
        dist.metadata["Name"].lower(): dist.version
        for dist in md.distributions()
        if dist.metadata["Name"]
    }
    lines.append(
        f"installed distributions: {len(installed)} "
        "(full inventory below is the offline audit artifact)"
    )
    req_path = ROOT / "requirements.txt"
    pin = re.compile(r"^([A-Za-z0-9._-]+)\s*([=<>!~]+)\s*([^#\s]+)")
    if req_path.exists():
        for raw in req_path.read_text().splitlines():
            raw = raw.strip()
            if not raw or raw.startswith("#"):
                continue
            m = pin.match(raw)
            if not m:
                continue
            name, op, want = m.group(1).lower(), m.group(2), m.group(3)
            have = installed.get(name)
            if have is None:
                problems.append(f"requirement '{raw}' is NOT installed")
            elif op == "==" and have != want:
                problems.append(
                    f"pin mismatch: {name}=={want} pinned, {have} installed"
                )
            else:
                lines.append(f"  ok: {name} {op}{want} (installed {have})")
    lines.append("")
    lines.append(
        "advisory-DB lookup: GATED (zero-egress image — no vulnerability "
        "feed to query; this inventory is the auditable input for "
        "pip-audit/nancy on a connected host)"
    )
    for name in sorted(installed):
        lines.append(f"  {name}=={installed[name]}")
    return lines, problems


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--static-only", action="store_true",
        help="skip the dependency audit (for CI jobs that don't "
        "install the project deps, where it would be all noise); "
        "the full run is scripts/ci_local.py's",
    )
    parser.add_argument(
        "--root", default=None,
        help="scan an alternate tree (fixture smoke tests); the "
        "dependency audit only makes sense on the real checkout, so "
        "--root implies --static-only",
    )
    args = parser.parse_args()
    static_only = args.static_only
    root = ROOT
    if args.root is not None:
        root = pathlib.Path(args.root).resolve()
        static_only = True
    findings = scan_tree(root)
    order = {"HIGH": 0, "MEDIUM": 1, "LOW": 2}
    findings.sort(key=lambda f: (order[f.severity], f.path, f.line))
    high = [f for f in findings if f.severity == "HIGH"]
    med = [f for f in findings if f.severity == "MEDIUM"]
    low = [f for f in findings if f.severity == "LOW"]

    print("== static scan (gosec/bandit analogue) ==")
    for f in findings:
        print(f.fmt())
    print(
        f"static scan: {len(high)} high, {len(med)} medium, "
        f"{len(low)} low across first-party sources"
    )
    if not static_only:
        print()
        print("== dependency audit (nancy/pip-audit analogue) ==")
        dep_lines, dep_problems = dependency_audit()
        for ln in dep_lines:
            print(ln)
        for p in dep_problems:
            print(f"[MEDIUM] dependency: {p}")

    if high:
        print(f"security-scan: FAIL ({len(high)} high-severity findings)")
        return 1
    print("security-scan: PASS (no high-severity findings)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

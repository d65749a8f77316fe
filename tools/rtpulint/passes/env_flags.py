"""env-flag: the RTPU_* operator-flag surface must stay registered.

``RTPU_*`` env vars are the operator escape hatches
(RTPU_STREAMING_SHUFFLE, RTPU_COLUMNAR_EXCHANGE, RTPU_INLINE_MAX_BYTES).
Each one must be:

- read ONLY through ``ray_tpu/core/config.py`` (a module-level helper next
  to the matching config entry), never ad hoc at a call site — scattered
  reads drift from the config default and are invisible to
  ``config.snapshot()`` distribution;
- named in ``core/config.py`` (the registry) and mentioned in README.md
  (operators discover flags there, not by grepping).

Findings: any ``os.environ.get("RTPU_...")`` / ``os.environ[...]`` /
``os.getenv`` outside config.py; any flag read that config.py never names;
any flag README.md never names.
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional, Set, Tuple

from tools.rtpulint.core import Finding, LintContext, ParsedFile, const_str, \
    dotted_name

_FLAG_RE = re.compile(r"RTPU_[A-Z0-9_]+")


def _env_read(node: ast.Call) -> Optional[str]:
    name = dotted_name(node.func)
    if name in ("os.environ.get", "os.getenv", "environ.get") and node.args:
        return const_str(node.args[0])
    return None


def _collect_reads(pf: ParsedFile) -> List[Tuple[str, int]]:
    reads: List[Tuple[str, int]] = []
    for node in ast.walk(pf.tree):
        flag: Optional[str] = None
        if isinstance(node, ast.Call):
            flag = _env_read(node)
        elif isinstance(node, ast.Subscript) and dotted_name(node.value) in (
                "os.environ", "environ"):
            flag = const_str(node.slice)
        if flag and flag.startswith("RTPU_"):
            reads.append((flag, node.lineno))
    return reads


def run(files: List[ParsedFile], ctx: LintContext) -> List[Finding]:
    declared: Set[str] = set(_FLAG_RE.findall(ctx.config_source))
    documented: Set[str] = set(_FLAG_RE.findall(ctx.readme_source))
    findings: List[Finding] = []
    for pf in files:
        is_config = pf.relpath.endswith("core/config.py")
        for flag, line in _collect_reads(pf):
            if not is_config:
                findings.append(Finding(
                    path=pf.relpath, line=line, pass_name="env-flag",
                    message=f"{flag} read outside core/config.py — add a "
                            f"config field + helper there and call it",
                    key_token=f"outside:{flag}"))
            if flag not in declared:
                findings.append(Finding(
                    path=pf.relpath, line=line, pass_name="env-flag",
                    message=f"{flag} is not named anywhere in "
                            f"core/config.py — declare the flag in the "
                            f"registry",
                    key_token=f"undeclared:{flag}"))
            if flag not in documented:
                findings.append(Finding(
                    path=pf.relpath, line=line, pass_name="env-flag",
                    message=f"{flag} is not mentioned in README.md — "
                            f"document the operator flag",
                    key_token=f"undocumented:{flag}"))
    return findings

"""Finding model for the repro static checkers (DESIGN.md §12).

Every check in the analysis subsystem — kernel contracts, trace audit, AST
lint — reports through one shape: a :class:`Finding` carrying a rule id, a
``path:line`` anchor, a message, and a fix hint. Suppression is two-tier:

* inline ``# repro: ignore[RULE]`` on the flagged line (or the line above)
  silences one occurrence at the source — use for accepted false positives
  that live next to the code they describe;
* an allowlist JSON file (``tools/check_allowlist.json``) for findings that
  have no source line to annotate (trace-audit findings anchor to a traced
  entry point, not a file) — every entry must carry a ``reason``, and stale
  entries that no longer match anything are reported so the burn-down list
  can only shrink.

``tools/check.py`` renders unsuppressed findings and exits non-zero when
any remain, which is the CI gate contract.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: rule id -> one-line description. The single registry: every Finding's
#: rule must be here, and DESIGN.md §12 catalogues the same ids.
RULES: Dict[str, str] = {
    # kernel contract checker (contracts.py / kernel_pass.py)
    "KC-VMEM": "kernel launch VMEM footprint exceeds the backend budget",
    "KC-LOC": "tile height overflows the 16-bit in-tile row field",
    "KC-GRID": "grid/index-map divisibility broken for the launch shape",
    "KC-SPLIT": "split_k outside [1, Kt] wastes or breaks the partials grid",
    "KC-NTB": "N tile not lane-aligned (multiple of 8, cap 128)",
    "KC-ACC": "kernel accumulator/scratch is not float32",
    "KC-OUT": "sparse_linear call site missing declared_out",
    # trace auditor (trace_audit.py)
    "TA-UPCAST": "large bf16->f32 convert_element_type in a traced step",
    "TA-CALLBACK": "host callback/sync primitive inside a step-path trace",
    "TA-RETRACE": "entry point compiled more shapes than its budget",
    # AST lint (lint.py)
    "PK-FRESH": "PRNG key created inside a serving loop body",
    "PK-SPLIT": "jax.random.split in a serving loop (use fold_in discipline)",
    "PK-REUSE": "same PRNG key consumed by more than one random draw",
    "PY-TRACED-BRANCH": "Python if/while branches on a traced value",
    "PY-MUT-DEFAULT": "mutable default argument",
    "PY-DICT-MUT": "dict/list mutated while being iterated",
    "PY-SWALLOW": "bare/over-broad except in serving/ drops the exception",
    # observability plane (lint.py OB-SYNC; tools/check.py --obs OB-EVENT)
    "OB-SYNC": "host sync (block_until_ready/.item/asarray) in the step "
               "hot path without a profiling-fence annotation",
    "OB-EVENT": "metrics counters and the trace event stream disagree",
}

_IGNORE_RE = re.compile(r"#\s*repro:\s*ignore\[([A-Z0-9,\- ]+)\]")


@dataclasses.dataclass
class Finding:
    """One rule violation.

    ``path`` is a repo-relative file path for source-anchored rules, or a
    pseudo-path like ``trace:engine_decode_step`` for trace-audit findings.
    ``line`` is 0 when no source line applies.
    """

    rule: str
    path: str
    line: int
    message: str
    hint: str = ""
    suppressed: bool = False
    justification: str = ""

    def __post_init__(self) -> None:
        assert self.rule in RULES, f"unregistered rule id {self.rule!r}"

    def anchor(self) -> str:
        return f"{self.path}:{self.line}" if self.line else self.path

    def render(self) -> str:
        tail = f"\n      hint: {self.hint}" if self.hint else ""
        if self.suppressed:
            tail += f"\n      suppressed: {self.justification}"
        return f"{self.anchor()}: {self.rule}: {self.message}{tail}"


def parse_inline_ignores(source: str) -> Dict[int, Tuple[str, ...]]:
    """Map 1-based line number -> rule ids ignored on that line.

    A ``# repro: ignore[RULE]`` comment applies to its own line and to the
    line below it, so a comment-only line can annotate the statement it
    precedes (long statements whose flagged expression is mid-statement).
    """
    out: Dict[int, Tuple[str, ...]] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _IGNORE_RE.search(text)
        if not m:
            continue
        rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
        out[i] = out.get(i, ()) + rules
        out[i + 1] = out.get(i + 1, ()) + rules
    return out


def apply_inline_ignores(findings: Iterable[Finding],
                         source_by_path: Dict[str, str]) -> List[Finding]:
    """Mark findings whose line carries a matching inline ignore."""
    cache: Dict[str, Dict[int, Tuple[str, ...]]] = {}
    out = []
    for f in findings:
        src = source_by_path.get(f.path)
        if src is not None and f.line:
            if f.path not in cache:
                cache[f.path] = parse_inline_ignores(src)
            if f.rule in cache[f.path].get(f.line, ()):
                f.suppressed = True
                f.justification = f.justification or "inline ignore"
        out.append(f)
    return out


class Allowlist:
    """Burn-down allowlist: JSON entries suppressing known findings.

    Format::

        {"entries": [{"rule": "TA-UPCAST",
                      "path": "trace:engine_decode_step",
                      "match": "softmax",              # optional substring
                      "reason": "f32 softmax is intentional"}]}

    ``path`` is matched with fnmatch (globs allowed); ``match`` is a
    substring of the finding message; ``reason`` is mandatory — an entry
    without one is invalid and ignored (reported via :meth:`problems`).
    """

    def __init__(self, entries: Sequence[dict]):
        self.entries = list(entries)
        self._used = [False] * len(self.entries)
        self._invalid = [not (e.get("rule") and e.get("path")
                              and e.get("reason"))
                         for e in self.entries]

    @classmethod
    def load(cls, path: Optional[str]) -> "Allowlist":
        if not path or not os.path.exists(path):
            return cls([])
        with open(path) as f:
            data = json.load(f)
        return cls(data.get("entries", []))

    def suppress(self, findings: Iterable[Finding]) -> List[Finding]:
        out = []
        for f in findings:
            for i, e in enumerate(self.entries):
                if self._invalid[i] or f.suppressed:
                    continue
                if e["rule"] != f.rule:
                    continue
                if not fnmatch.fnmatch(f.path, e["path"]):
                    continue
                if e.get("match") and e["match"] not in f.message:
                    continue
                f.suppressed = True
                f.justification = e["reason"]
                self._used[i] = True
            out.append(f)
        return out

    def problems(self) -> List[str]:
        """Stale or invalid entries — the burn-down file may only shrink."""
        out = []
        for i, e in enumerate(self.entries):
            label = f"{e.get('rule')}@{e.get('path')}"
            if self._invalid[i]:
                out.append(f"allowlist entry {label} missing "
                           f"rule/path/reason")
            elif not self._used[i]:
                out.append(f"allowlist entry {label} is stale "
                           f"(matched nothing); remove it")
        return out


def render_report(findings: Sequence[Finding], *,
                  show_suppressed: bool = False) -> str:
    live = [f for f in findings if not f.suppressed]
    sup = [f for f in findings if f.suppressed]
    lines = [f.render() for f in live]
    if show_suppressed and sup:
        lines.append(f"-- {len(sup)} suppressed --")
        lines.extend(f.render() for f in sup)
    lines.append(f"{len(live)} finding(s), {len(sup)} suppressed")
    return "\n".join(lines)

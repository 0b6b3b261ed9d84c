"""GL005 — literal drift (absorbs ``tools/check_perf_claims.py``).

Docs drift from code silently: a README that cites a renamed metric,
a chaos site that no longer exists, or a perf multiplier no bench
artifact ever measured is worse than no README. Three sub-checks,
unchanged in semantics from the standalone lint they generalize:

- **perf claims**: every ``N.Nx``/``N.N×`` multiplier in README.md /
  COMPONENTS.md must match an explicit ``*vs_*`` ratio key in
  BENCH_DETAIL.json or a ratio of two same-(unit, metric-family)
  config values, at the claim's own precision. Lines containing
  "target" are exempt (a goal is not a measurement). Where the
  artifact does not exist nothing was measured, and every multiplier
  is a finding.
- **metric names**: every backticked ``*_total``/``*_seconds``/
  ``*_bytes``/``*_depth``/``*_firing``/``*_state`` token in the docs
  must exist as a metric-name string literal under the package
  (f-string templates match as wildcards). Fleet-level metrics don't
  all carry a typed suffix (``fleet_targets_up``), so any backticked
  ``fleet_*`` token is held to the same must-exist bar.
- **chaos sites**: inside doc sections headed fault-injection/chaos,
  every backticked dotted token must exist as a string literal under
  the package.

The legacy functions (``check``, ``check_metric_names``,
``check_site_names``) are kept with their list-of-strings API —
``tools/check_perf_claims.py`` is now a shim over them.
"""

from __future__ import annotations

import itertools
import json
import os
import re
from typing import Iterable, List, Tuple

from tools.graftlint.core import (Finding, PACKAGE_DIR, ParsedModule,
                                  RepoContext)
from tools.graftlint.rules.base import Rule

DOC_FILES = ["README.md", "COMPONENTS.md"]
ARTIFACT = "BENCH_DETAIL.json"

# an N.Nx multiplier claim: requires a decimal point (plain "2x256"
# tensor shapes and "8x" core counts are not perf claims in this
# repo's docs; the measured-claim convention is one decimal or more)
CLAIM_RE = re.compile(r"(\d+\.\d+)\s*[x×]")

METRIC_SUFFIXES = ("_total", "_seconds", "_bytes", "_depth",
                   "_firing", "_state")
# the fleet collector's gauges don't all carry a typed suffix
# (fleet_targets_up), so the whole prefix family counts as metric
# citations too
METRIC_PREFIXES = ("fleet_",)
_SUFFIX_ALT = "|".join(METRIC_SUFFIXES)
_PREFIX_ALT = "|".join(METRIC_PREFIXES)
DOC_METRIC_RE = re.compile(
    r"`([a-z][a-z0-9_]*(?:%s)|(?:%s)[a-z0-9_]+)`"
    % (_SUFFIX_ALT, _PREFIX_ALT))
SRC_METRIC_RE = re.compile(
    r"""["']([A-Za-z0-9_{}]*(?:%s)|(?:%s)[A-Za-z0-9_{}]+)["']"""
    % (_SUFFIX_ALT, _PREFIX_ALT))

DOC_SITE_RE = re.compile(r"`([a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+)`")
SRC_SITE_RE = re.compile(
    r"""["']([a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+)["']""")
_SITE_EXT_SKIP = {"py", "json", "jsonl", "md", "zip", "npz", "npy",
                  "txt", "ini", "csv", "bin", "gz", "log", "html",
                  "h5", "yaml", "yml"}


# ---------------------------------------------------------------------------
# perf claims
# ---------------------------------------------------------------------------

def _collect_ratio_keys(obj, out: List[float]) -> None:
    if isinstance(obj, dict):
        for k, v in obj.items():
            if "vs_" in str(k) and isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                out.append(float(v))
            else:
                _collect_ratio_keys(v, out)
    elif isinstance(obj, list):
        for v in obj:
            _collect_ratio_keys(v, out)


def measured_numbers(detail: dict) -> List[float]:
    """Legitimate multiplier sources only: explicit ``*vs_*`` ratio
    keys anywhere in the artifact, plus cross-config ``value`` ratios
    within one (unit, metric-family) pair — NOT every raw number."""
    out: List[float] = []
    _collect_ratio_keys(detail, out)
    configs = detail.get("configs", [])
    by_family = {}
    for c in configs:
        if isinstance(c.get("value"), (int, float)) and c.get("unit"):
            family = (c["unit"],
                      str(c.get("metric", "")).split(" ")[0])
            by_family.setdefault(family, []).append(float(c["value"]))
    for vals in by_family.values():
        for a, b in itertools.permutations(vals, 2):
            if b:
                out.append(a / b)
    return out


def claim_matches(claim: float, ndecimals: int,
                  numbers: List[float]) -> bool:
    tol = 10.0 ** (-ndecimals)
    return any(abs(n - claim) <= tol for n in numbers)


def find_claims(path: str) -> List[Tuple[int, str, float, int]]:
    """(line_no, line, claim_value, n_decimals) for each N.Nx."""
    claims = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            if "target" in line.lower():
                continue
            for m in CLAIM_RE.finditer(line):
                txt = m.group(1)
                claims.append((i, line.rstrip(), float(txt),
                               len(txt.split(".")[1])))
    return claims


def check_perf_claims(repo: str) -> List[Tuple[str, int, str]]:
    """A missing artifact means NO measured numbers: every multiplier
    in the docs is then a finding, not a pass."""
    artifact_path = os.path.join(repo, ARTIFACT)
    numbers: List[float] = []
    if os.path.exists(artifact_path):
        with open(artifact_path) as f:
            numbers = measured_numbers(json.load(f))
    errors = []
    for doc in DOC_FILES:
        path = os.path.join(repo, doc)
        if not os.path.exists(path):
            continue
        for line_no, line, claim, nd in find_claims(path):
            if not claim_matches(claim, nd, numbers):
                errors.append((doc, line_no,
                               f"claim '{claim}x' has no measured "
                               f"counterpart in {ARTIFACT} "
                               f"(line: {line.strip()[:100]})"))
    return errors


# ---------------------------------------------------------------------------
# stale metric names
# ---------------------------------------------------------------------------

def _package_sources(repo: str) -> Iterable[str]:
    for root, dirs, files in os.walk(os.path.join(repo, PACKAGE_DIR)):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fname in files:
            if fname.endswith(".py"):
                with open(os.path.join(root, fname),
                          encoding="utf-8", errors="replace") as f:
                    yield f.read()


def registered_metric_patterns(repo: str, sources=None
                               ) -> List[re.Pattern]:
    """Compile every metric-name literal under the package into a
    matcher; ``{...}`` f-string holes become wildcards."""
    patterns = set()
    for src in (sources if sources is not None
                else _package_sources(repo)):
        for m in SRC_METRIC_RE.finditer(src):
            patterns.add(m.group(1))
    out = []
    for p in sorted(patterns):
        rx = re.escape(p).replace(r"\{", "{").replace(r"\}", "}")
        rx = re.sub(r"\{[^{}]*\}", r"[a-zA-Z0-9_/.-]+", rx)
        out.append(re.compile(rx + r"\Z"))
    return out


def find_doc_metric_names(path: str) -> List[Tuple[int, str]]:
    names = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            for m in DOC_METRIC_RE.finditer(line):
                names.append((i, m.group(1)))
    return names


def check_metric_names_raw(repo: str, sources=None
                           ) -> List[Tuple[str, int, str]]:
    patterns = registered_metric_patterns(repo, sources)
    errors = []
    for doc in DOC_FILES:
        path = os.path.join(repo, doc)
        if not os.path.exists(path):
            continue
        for line_no, name in find_doc_metric_names(path):
            if not any(p.match(name) for p in patterns):
                errors.append((doc, line_no,
                               f"metric `{name}` is cited in the "
                               f"docs but registered nowhere under "
                               f"{PACKAGE_DIR}/ — stale name?"))
    return errors


# ---------------------------------------------------------------------------
# stale chaos-site names
# ---------------------------------------------------------------------------

def find_doc_site_names(path: str) -> List[Tuple[int, str]]:
    """Backticked dotted tokens inside any section whose heading
    mentions fault injection / chaos (scoped: a dotted token
    elsewhere in the docs — `np.ndarray`, module paths — is not a
    site citation). Fenced code blocks are skipped entirely: a shell
    comment's leading '#' is not a markdown heading and must not
    toggle the section scope."""
    names = []
    in_section = False
    in_fence = False
    with open(path) as f:
        for i, line in enumerate(f, 1):
            if line.lstrip().startswith("```"):
                in_fence = not in_fence
                continue
            if in_fence:
                continue
            if re.match(r"#+\s", line):
                low = line.lower()
                in_section = ("fault injection" in low
                              or "chaos" in low)
                continue
            if not in_section:
                continue
            for m in DOC_SITE_RE.finditer(line):
                token = m.group(1)
                if token.rsplit(".", 1)[-1] in _SITE_EXT_SKIP:
                    continue
                names.append((i, token))
    return names


def registered_site_literals(repo: str, sources=None) -> set:
    literals = set()
    for src in (sources if sources is not None
                else _package_sources(repo)):
        for m in SRC_SITE_RE.finditer(src):
            literals.add(m.group(1))
    return literals


def check_site_names_raw(repo: str, sources=None
                         ) -> List[Tuple[str, int, str]]:
    literals = registered_site_literals(repo, sources)
    errors = []
    for doc in DOC_FILES:
        path = os.path.join(repo, doc)
        if not os.path.exists(path):
            continue
        for line_no, name in find_doc_site_names(path):
            if name not in literals:
                errors.append((doc, line_no,
                               f"chaos site `{name}` is cited in "
                               f"the docs but exists as a string "
                               f"literal nowhere under "
                               f"{PACKAGE_DIR}/ — stale site name?"))
    return errors


# ---------------------------------------------------------------------------
# legacy string API (the check_perf_claims.py shim contract)
# ---------------------------------------------------------------------------

def _fmt(errors: List[Tuple[str, int, str]]) -> List[str]:
    return [f"{doc}:{line}: {msg}" for doc, line, msg in errors]


def check(repo: str) -> List[str]:
    """All three sub-checks, as ``DOC:LINE: message`` strings."""
    errors = check_perf_claims(repo)
    errors.extend(check_metric_names_raw(repo))
    errors.extend(check_site_names_raw(repo))
    return _fmt(errors)


def check_metric_names(repo: str) -> List[str]:
    return _fmt(check_metric_names_raw(repo))


def check_site_names(repo: str) -> List[str]:
    return _fmt(check_site_names_raw(repo))


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

class LiteralDriftRule(Rule):
    id = "GL005"
    title = "literal-drift"
    rationale = ("doc perf claims, metric names and chaos sites "
                 "must keep matching code and bench artifacts")
    scope = "repo"

    def repo_triggered(self, relpath: str) -> bool:
        return (relpath in DOC_FILES or relpath == ARTIFACT
                or (relpath.startswith(PACKAGE_DIR + "/")
                    and relpath.endswith(".py")))

    def check_repo(self, ctx: RepoContext) -> Iterable[Finding]:
        errors = check_perf_claims(ctx.repo)
        # one package-source pass feeds both literal scans (the
        # legacy wrappers below still read independently)
        sources = list(_package_sources(ctx.repo))
        errors.extend(check_metric_names_raw(ctx.repo, sources))
        errors.extend(check_site_names_raw(ctx.repo, sources))
        return [Finding(rule=self.id, path=doc, line=line,
                        message=msg)
                for doc, line, msg in errors]

"""Finding model + rule catalog + suppression parsing for picolint.

Every analyzer emits ``Finding`` records tagged with a rule ID from
``RULES``.  IDs are stable API: they appear in baseline entries
(``analysis/baseline.json``), suppression comments
(``# picolint: disable=PICO-J001``), docs (docs/ANALYSIS.md), and in code
comments that cross-link a hazard to the rule enforcing it (e.g.
``ops/pallas/decode_attention.py`` ↔ PICO-J003).  Never renumber a rule;
retire IDs instead.

Baselines match findings by **fingerprint** — (rule, path, context,
snippet) — not by line number, so unrelated edits above a baselined
finding don't invalidate the baseline.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class Rule:
    id: str
    title: str
    rationale: str  # one line; the full story lives in docs/ANALYSIS.md


# The catalog. J = JAX hot-path rules, C = host-concurrency rules.
RULES = {
    r.id: r
    for r in [
        Rule(
            "PICO-J001",
            "host sync on a traced value",
            "float()/int()/bool()/.item()/np.asarray/jax.device_get on a "
            "traced value inside jit-reachable code forces a device->host "
            "transfer (or a ConcretizationTypeError) on the hot path",
        ),
        Rule(
            "PICO-J002",
            "host nondeterminism under trace",
            "time.*/random.*/np.random.*/uuid/datetime calls inside "
            "jit-reachable code are evaluated ONCE at trace time and baked "
            "into the compiled program — silently stale and nondeterministic "
            "across recompiles",
        ),
        Rule(
            "PICO-J003",
            "pl.program_id read inside a loop body",
            "the Pallas interpreter (jax 0.9.0 too) cannot lower pl.program_id "
            "inside a fori_loop/while_loop/scan body's sub-jaxpr; read grid "
            "ids once, outside the loop (the decode_attention.py incident)",
        ),
        Rule(
            "PICO-J004",
            "jit/pallas_call constructed inside a loop",
            "jax.jit/jax.pmap/pl.pallas_call evaluated in a loop body builds "
            "a fresh callable per iteration — every call recompiles unless "
            "the result is cached outside the loop",
        ),
        Rule(
            "PICO-J005",
            "make_async_copy started without a reachable wait",
            "a pltpu.make_async_copy whose .start() has no matching "
            ".wait() in scope — or whose per-iteration start inside a "
            "fori_loop body has its only wait outside that loop path — "
            "leaves DMAs in flight while compute reads the buffer (or "
            "imbalances the semaphore), the exact hazard double-buffered "
            "pipelining introduces",
        ),
        Rule(
            "PICO-J006",
            "model program dispatched outside _dispatch",
            "a compiled model program (a self._*_jit/_prog attribute "
            "called with params as its first operand) invoked outside "
            "self._dispatch(lambda: ...) skips the retry / flash-fallback "
            "fault wrapper every engine program family must inherit",
        ),
        Rule(
            "PICO-C001",
            "lock-order inversion",
            "two locks acquired in opposite orders on different code paths "
            "deadlock the first time the paths interleave (the PR 6 "
            "_next_uid-under-_mu incident class)",
        ),
        Rule(
            "PICO-C002",
            "blocking call while holding a lock",
            "sleep/join/subprocess/file-I/O/unbounded queue ops under a lock "
            "stall every thread contending for it — the serving admission "
            "path sheds on a 10s bound precisely because of this class",
        ),
        Rule(
            "PICO-C003",
            "guarded attribute mutated outside its lock",
            "an attribute mutated under a lock in one method and without it "
            "in another loses updates or tears reads the moment two threads "
            "interleave (the serve.py rejection-counter incident)",
        ),
        Rule(
            "PICO-C004",
            "cross-thread mutation with no lock",
            "an attribute mutated both by a background-thread method and by "
            "foreground methods with no lock anywhere has no ordering at "
            "all (the checkpoint.py mirror-error-list incident)",
        ),
    ]
}


@dataclass(frozen=True)
class Finding:
    """One analyzer hit, anchored to a source line.

    ``context`` is the enclosing qualname (``Class.method``, ``func``,
    ``func.<locals>.body``, or ``<module>``); ``snippet`` is the stripped
    source line.  Both feed the baseline fingerprint so line drift above
    the finding does not break the match.
    """

    rule: str
    path: str  # scan-root-relative, posix separators
    line: int
    context: str
    snippet: str
    message: str

    def fingerprint(self) -> tuple:
        return (self.rule, self.path, self.context, _norm(self.snippet))

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "title": RULES[self.rule].title if self.rule in RULES else "",
            "path": self.path,
            "line": self.line,
            "context": self.context,
            "snippet": self.snippet,
            "message": self.message,
        }

    def render(self) -> str:
        return (f"{self.path}:{self.line}: {self.rule} [{self.context}] "
                f"{self.message}")

    def sort_key(self) -> tuple:
        return (self.path, self.line, self.rule)


def _norm(s: str) -> str:
    return " ".join(s.split())


# --------------------------------------------------------------------------- #
# suppression comments
# --------------------------------------------------------------------------- #

# `# picolint: disable=PICO-J001[,PICO-C002|all]` on the flagged line
# silences those rules for that line; `disable-file=` anywhere silences
# them for the whole file.  The bare rule suffix ("J001") is accepted
# too.  The capture stops at the first token that isn't part of a
# comma-separated rule list, so trailing prose
# (`# picolint: disable=PICO-J002 — intended, see docs`) still suppresses.
_SUPPRESS_RE = re.compile(
    r"#\s*picolint:\s*(disable(?:-file)?)\s*=\s*"
    r"([A-Za-z0-9_\-*]+(?:\s*,\s*[A-Za-z0-9_\-*]+)*)")


def _canon(rule: str) -> str:
    rule = rule.strip().upper()
    if not rule:
        return ""
    if rule in ("ALL", "*"):
        return "*"
    if not rule.startswith("PICO-"):
        rule = "PICO-" + rule
    return rule


@dataclass
class Suppressions:
    """Per-file suppression table, parsed once from the raw source text."""

    by_line: dict = field(default_factory=dict)  # line -> set of rule ids/"*"
    whole_file: set = field(default_factory=set)

    @classmethod
    def parse(cls, text: str) -> "Suppressions":
        sup = cls()
        for lineno, line in enumerate(text.splitlines(), start=1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = {_canon(r) for r in m.group(2).split(",")} - {""}
            if m.group(1) == "disable-file":
                sup.whole_file |= rules
            else:
                sup.by_line.setdefault(lineno, set()).update(rules)
        return sup

    def silences(self, finding: Finding) -> bool:
        for scope in (self.whole_file, self.by_line.get(finding.line, ())):
            if "*" in scope or finding.rule in scope:
                return True
        return False


def validate_rule_ids(ids) -> Optional[str]:
    """The first unknown rule ID in ``ids``, or None when all are known."""
    for r in ids:
        if r != "*" and r not in RULES:
            return r
    return None

"""``analysis.toml`` — per-rule allowlists and options.

The config file at the repo root scopes *sanctioned* violations (the
retired-name mentions in CHANGES.md, the direct wall-clock reads in the
serving modules that own the clock seam) so the engines themselves stay
allowlist-free: a rule reports everything it sees, and the config is
the single audited place where exceptions live.
"""

from __future__ import annotations

import fnmatch
import tomllib
from dataclasses import dataclass, field
from pathlib import Path

CONFIG_NAME = "analysis.toml"


@dataclass
class AnalysisConfig:
    """Loaded view of ``analysis.toml``.

    ``allow`` maps rule id -> list of repo-relative path patterns
    (``fnmatch`` syntax, so both exact files and ``src/**`` globs work);
    ``options`` maps rule id -> its ``[rules.<id>]`` table minus the
    ``allow`` key, for rules that take parameters.
    """

    allow: dict[str, list[str]] = field(default_factory=dict)
    options: dict[str, dict] = field(default_factory=dict)

    @classmethod
    def load(cls, root: Path) -> "AnalysisConfig":
        path = Path(root) / CONFIG_NAME
        if not path.is_file():
            return cls()
        data = tomllib.loads(path.read_text(encoding="utf-8"))
        allow: dict[str, list[str]] = {}
        options: dict[str, dict] = {}
        for rule, table in data.get("rules", {}).items():
            if not isinstance(table, dict):
                raise ValueError(
                    f"{CONFIG_NAME}: [rules.{rule}] must be a table")
            entries = table.get("allow", [])
            if not isinstance(entries, list):
                raise ValueError(
                    f"{CONFIG_NAME}: rules.{rule}.allow must be a list")
            allow[rule] = [str(e) for e in entries]
            opts = {k: v for k, v in table.items() if k != "allow"}
            if opts:
                options[rule] = opts
        return cls(allow=allow, options=options)

    def allowed(self, rule: str, location: str) -> bool:
        """True when ``location`` is sanctioned for ``rule``."""
        loc = location.replace("\\", "/")
        for pattern in self.allow.get(rule, ()):
            if loc == pattern or fnmatch.fnmatch(loc, pattern):
                return True
        return False

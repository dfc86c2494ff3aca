"""Configuration loader: YAML-subset files with env-var expansion.

Role parity with the reference config system
(/root/reference/src/x/config/config.go:73-93 — YAML + ${ENV:default}
expansion + validation). To stay dependency-free this parses the YAML
subset real deployments use (nested mappings, lists of scalars/mappings,
scalars with comments); anchors/multiline scalars are out of scope.
"""

from __future__ import annotations

import os
import re
from typing import Any

_ENV_RE = re.compile(r"\$\{(\w+)(?::([^}]*))?\}")


def expand_env(text: str, env: dict | None = None) -> str:
    env = env if env is not None else os.environ

    def sub(m: re.Match) -> str:
        name, default = m.group(1), m.group(2)
        val = env.get(name)
        if val is None:
            if default is None:
                raise KeyError(f"environment variable {name} not set and no default")
            return default
        return val

    return _ENV_RE.sub(sub, text)


def _parse_scalar(s: str) -> Any:
    s = s.strip()
    if s in ("null", "~", ""):
        return None
    if s in ("true", "True"):
        return True
    if s in ("false", "False"):
        return False
    if s == "[]":  # the empty flow collections, the only flow syntax
        return []  # the subset reads (config/coordinator.yml: `rollup: []`)
    if s == "{}":
        return {}
    if s.startswith('"') and s.endswith('"') or s.startswith("'") and s.endswith("'"):
        return s[1:-1]
    try:
        return int(s)
    except ValueError:
        pass
    try:
        return float(s)
    except ValueError:
        pass
    return s


def _strip_comment(line: str) -> str:
    out = []
    in_s = in_d = False
    for ch in line:
        if ch == "'" and not in_d:
            in_s = not in_s
        elif ch == '"' and not in_s:
            in_d = not in_d
        elif ch == "#" and not in_s and not in_d:
            break
        out.append(ch)
    return "".join(out).rstrip()


def parse_yaml(text: str) -> Any:
    """Parse the YAML subset (nested maps, lists, scalars)."""
    lines = []
    for raw in text.splitlines():
        line = _strip_comment(raw)
        if line.strip():
            lines.append(line)
    value, rest = _parse_block(lines, 0, _indent(lines[0]) if lines else 0)
    if rest:
        raise ValueError(f"trailing unparsed config lines: {rest[:2]}")
    return value


def _indent(line: str) -> int:
    return len(line) - len(line.lstrip())


def _parse_block(lines: list[str], pos: int, indent: int):
    if pos >= len(lines):
        return None, []
    if lines[pos].lstrip().startswith("- "):
        return _parse_list(lines, pos, indent)
    return _parse_map(lines, pos, indent)


def _parse_map(lines: list[str], pos: int, indent: int):
    out: dict[str, Any] = {}
    while pos < len(lines):
        line = lines[pos]
        ind = _indent(line)
        if ind < indent:
            break
        if ind > indent:
            raise ValueError(f"bad indent at: {line!r}")
        stripped = line.strip()
        if ":" not in stripped:
            raise ValueError(f"expected key: value, got {stripped!r}")
        key, _, rest = stripped.partition(":")
        key = _parse_scalar(key)
        rest = rest.strip()
        pos += 1
        if rest:
            out[key] = _parse_scalar(rest)
        else:
            deeper = pos < len(lines) and _indent(lines[pos]) > indent
            # standard YAML also allows the list at the SAME indent as its key
            same_list = (
                pos < len(lines)
                and _indent(lines[pos]) == indent
                and lines[pos].lstrip().startswith("- ")
            )
            if deeper or same_list:
                child_indent = _indent(lines[pos])
                child, remaining = _parse_block(lines[pos:], 0, child_indent)
                consumed = len(lines[pos:]) - len(remaining)
                pos += consumed
                out[key] = child
            else:
                out[key] = None
    return out, lines[pos:]


def _parse_list(lines: list[str], pos: int, indent: int):
    out: list[Any] = []
    while pos < len(lines):
        line = lines[pos]
        ind = _indent(line)
        if ind < indent or not line.lstrip().startswith("- "):
            break
        item = line.strip()[2:]
        pos += 1
        # YAML: '- key: value' (space after colon, or trailing colon) starts
        # a mapping; '- 10s:2d' (no space) is a scalar
        if re.match(r"^[^:\s]+:(\s|$)", item):
            sub_lines = [" " * (ind + 2) + item]
            while pos < len(lines) and _indent(lines[pos]) > ind:
                sub_lines.append(lines[pos])
                pos += 1
            child, _ = _parse_map(sub_lines, 0, ind + 2)
            out.append(child)
        else:
            out.append(_parse_scalar(item))
    return out, lines[pos:]


def load_config(path: str, env: dict | None = None) -> Any:
    with open(path) as f:
        raw = f.read()
    # strip comments BEFORE env expansion so a commented-out ${VAR} with no
    # default can't fail the load
    stripped = "\n".join(_strip_comment(line) for line in raw.splitlines())
    return parse_yaml(expand_env(stripped, env))

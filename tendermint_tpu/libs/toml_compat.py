"""TOML reading that works on Python 3.10 containers.

Stdlib ``tomllib`` exists only from 3.11; this repo's TOML consumers
(node config, e2e manifests) mostly read files the repo ITSELF wrote
(``Config.to_toml``, ``e2e/generate.doc_to_toml``) — a flat subset:
``key = value`` lines, ``[section]`` / ``[dotted.section]`` headers,
full-line or trailing comments, and values that are quoted strings,
booleans, integers, floats, or one-line lists thereof. When ``tomllib``
is available it is used verbatim; otherwise :func:`loads` parses exactly
that subset, so subprocess localnets (the e2e runner, ``cmd testnet``) run on 3.10 images instead of dying at import.
"""

from __future__ import annotations

from typing import Any, Dict

try:
    import tomllib as _tomllib
except ModuleNotFoundError:  # pragma: no cover - depends on interpreter
    _tomllib = None


class TOMLDecodeError(ValueError):
    pass


def load(f) -> Dict[str, Any]:
    data = f.read()
    if isinstance(data, bytes):
        data = data.decode()
    return loads(data)


def loads(text: str) -> Dict[str, Any]:
    if _tomllib is not None:
        try:
            return _tomllib.loads(text)
        except _tomllib.TOMLDecodeError as e:
            raise TOMLDecodeError(str(e)) from e
    return _loads_subset(text)


def _loads_subset(text: str) -> Dict[str, Any]:
    root: Dict[str, Any] = {}
    current = root
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = _strip_comment(raw).strip()
        if not line:
            continue
        if line.startswith("[") and line.endswith("]"):
            current = root
            for part in line[1:-1].strip().split("."):
                part = part.strip().strip('"')
                if not part:
                    raise TOMLDecodeError(f"line {lineno}: empty table name")
                current = current.setdefault(part, {})
            continue
        key, eq, value = line.partition("=")
        if not eq:
            raise TOMLDecodeError(f"line {lineno}: expected key = value")
        current[key.strip().strip('"')] = _value(value.strip(), lineno)
    return root


def _strip_comment(line: str) -> str:
    """Drop a trailing comment — a ``#`` outside any quoted string."""
    out = []
    in_str = False
    for ch in line:
        if ch == '"' and (not out or out[-1] != "\\"):
            in_str = not in_str
        elif ch == "#" and not in_str:
            break
        out.append(ch)
    return "".join(out)


def _value(tok: str, lineno: int) -> Any:
    if tok.startswith('"') and tok.endswith('"') and len(tok) >= 2:
        return tok[1:-1].replace('\\"', '"').replace("\\\\", "\\")
    if tok in ("true", "false"):
        return tok == "true"
    if tok.startswith("[") and tok.endswith("]"):
        inner = tok[1:-1].strip()
        if not inner:
            return []
        return [_value(p.strip(), lineno) for p in _split_list(inner)]
    try:
        return int(tok)
    except ValueError:
        pass
    try:
        return float(tok)
    except ValueError:
        raise TOMLDecodeError(f"line {lineno}: cannot parse value {tok!r}")


def _split_list(inner: str):
    """Split a one-line list body on commas outside quotes."""
    parts, buf, in_str = [], [], False
    for ch in inner:
        if ch == '"' and (not buf or buf[-1] != "\\"):
            in_str = not in_str
        if ch == "," and not in_str:
            parts.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    if buf:
        parts.append("".join(buf))
    return parts

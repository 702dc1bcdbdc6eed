"""Toy sizes and the control's switches, merged over a loaded cell's files.

A cell's files may hold a ``toy`` section: the same keys at sizes the CPU can
run, merged over the real ones by ``--cpu-toy``.  A configuration whose program
has a lower-precision path of its own names the switches that turn it on under
``control.overrides``; ``--control`` merges them, so that the program with that
path on stands where the program stood.  Neither is used by the driver's runs.
"""

from __future__ import annotations

from typing import Any, Dict


def merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def apply_toy(section: Dict[str, Any]) -> Dict[str, Any]:
    return merge(section, section.get("toy", {}))


def apply_control(config: Dict[str, Any]) -> Dict[str, Any]:
    return merge(config, (config.get("control") or {}).get("overrides", {}))

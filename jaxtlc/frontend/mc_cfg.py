"""TLC configuration (MC.cfg) parser.

Parses the TLC config DSL as exercised by the reference
(/root/reference/KubeAPI.toolbox/Model_1/MC.cfg:1-15) and by the
published models of `tlaplus/Examples`: CONSTANT declarations and
substitutions, SPECIFICATION (or INIT / NEXT), INVARIANT and PROPERTY
lists, `SYMMETRY <definition>`, `CHECK_DEADLOCK TRUE|FALSE` and
`CONSTRAINT <definitions>` (one or several names, on one line or many:
their conjunction bounds the states a run keeps; the seam is the expand
stage of engine.backend).  ACTION_CONSTRAINT and VIEW are recognised
and refused by name: each changes which states a run visits, and
neither has a seam here.
This file pair (MC.cfg + MC.tla) is "the plugin boundary the TPU backend
must accept unchanged" (SURVEY.md §1 L4->L3); the reference artifacts parse
as-is.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional


class CfgError(ValueError):
    """A cfg this parser reads but the checker cannot honour."""


@dataclasses.dataclass
class TLCConfig:
    constants: Dict[str, str]  # CONSTANT name = value
    substitutions: Dict[str, str]  # CONSTANT name <- definition-name
    specification: Optional[str]
    invariants: List[str]
    properties: List[str]
    init: Optional[str] = None
    next: Optional[str] = None
    symmetry: Optional[str] = None  # SYMMETRY definition-name
    check_deadlock: Optional[bool] = None  # CHECK_DEADLOCK, None = unsaid
    # CONSTRAINT definition-names, in the cfg's order (their conjunction)
    constraints: List[str] = dataclasses.field(default_factory=list)


_REFUSED = ("ACTION_CONSTRAINT", "VIEW")
_SECTION = re.compile(
    r"^(CONSTANTS?|SPECIFICATION|INVARIANTS?|PROPERTY|PROPERTIES|INIT|NEXT"
    r"|SYMMETRY|CHECK_DEADLOCK|CONSTRAINTS?|ACTION_CONSTRAINTS?|VIEW)\b"
)


def parse_cfg(text: str) -> TLCConfig:
    cfg = TLCConfig({}, {}, None, [], [])
    section = None
    for raw in text.splitlines():
        line = raw.split("\\*")[0].strip()  # \* comments
        if not line:
            continue
        m = _SECTION.match(line)
        if m:
            section = m.group(1)
            if section.rstrip("S") in _REFUSED:
                raise CfgError(
                    f"not supported: {section.rstrip('S')} (of the cfg "
                    "keywords that bound a run only CONSTRAINT is "
                    "honoured)")
            line = line[m.end():].strip()
            if not line:
                continue
        if section is None:
            continue
        if section.startswith("CONSTANT"):
            if "<-" in line:
                name, val = (x.strip() for x in line.split("<-", 1))
                cfg.substitutions[name] = val
            elif "=" in line:
                name, val = (x.strip() for x in line.split("=", 1))
                cfg.constants[name] = val
            else:
                # bare model-value declaration
                cfg.constants[line] = line
        elif section == "SPECIFICATION":
            cfg.specification = line
        elif section.startswith("INVARIANT"):
            cfg.invariants.extend(line.split())
        elif section in ("PROPERTY", "PROPERTIES"):
            cfg.properties.extend(line.split())
        elif section == "INIT":
            cfg.init = line
        elif section == "NEXT":
            cfg.next = line
        elif section == "SYMMETRY":
            if cfg.symmetry is not None or len(line.split()) != 1:
                raise CfgError(
                    f"SYMMETRY names one definition, got {line!r}"
                    + (f" after {cfg.symmetry!r}" if cfg.symmetry else ""))
            cfg.symmetry = line
        elif section.startswith("CONSTRAINT"):
            cfg.constraints.extend(
                n for n in line.split() if n not in cfg.constraints)
        elif section == "CHECK_DEADLOCK":
            if line not in ("TRUE", "FALSE"):
                raise CfgError(
                    f"CHECK_DEADLOCK is TRUE or FALSE, got {line!r}")
            cfg.check_deadlock = line == "TRUE"
    return cfg


def parse_cfg_file(path: str) -> TLCConfig:
    with open(path, "r", encoding="utf-8") as f:
        return parse_cfg(f.read())

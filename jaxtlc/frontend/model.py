"""Model resolution: MC.cfg + MC.tla (+ .launch) -> an executable run spec.

This is the L4 model-configuration layer (SURVEY.md §1): the three nested
config layers of the reference - .launch (Toolbox knobs) -> MC.cfg (TLC
DSL) -> MC.tla (constant definitions) - resolved against the spec the
engine can execute.

Spec frontend scope (SURVEY.md §7 item 9): the engine executes the KubeAPI
action system via hand-written codegen of the committed TLA translation
(/root/reference/KubeAPI.tla:373-768), generalized over the constants and
the scaled bounds.  Loading an MC for a different root spec is a clear
error, not a silent misrun.

The .pmap file (Java-serialized pcal.TLAtoPCalMapping) is the Toolbox's
generated-TLA -> PlusCal source map used to render traces at PlusCal level;
our action identifiers *are* the PlusCal labels (the translation names its
actions after them), so the mapping semantics are native here: traces are
reported with PlusCal labels + the reference's line numbers (io.tlc_log).
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional

from ..config import ModelConfig, make_scaled
from ..engine.fingerprint import DEFAULT_FP_INDEX
from .launch import LaunchConfig, parse_launch_file
from .mc_cfg import TLCConfig, parse_cfg_file
from .mc_tla import eval_constant, parse_mc_tla_file

KNOWN_INVARIANTS = ("TypeOK", "OnlyOneVersion")
KNOWN_PROPERTIES = ("ReconcileCompletes", "CleansUpProperly")
# the process set of the hand frontend as two integer constants of an
# MC.cfg CONSTANT line or of CheckRequest.constants; neither = the
# source's own Model_1 (one Client, one PVCController)
SCALING_CONSTANTS = ("N_RECONCILERS", "N_BINDERS")


@dataclasses.dataclass
class RunSpec:
    model: ModelConfig
    invariants: List[str]
    properties: List[str]  # declared; liveness checking is deferred (E8)
    check_deadlock: bool
    workers: str  # "tpu" | "auto" | int-as-string
    fp_index: int
    spec_name: str
    model_name: str


@dataclasses.dataclass
class GenRunSpec:
    """A resolved run for the generic frontend (non-KubeAPI root spec)."""

    genspec: object  # gen.ir.GenSpec
    invariants: List[str]
    properties: List[str]
    check_deadlock: bool
    workers: str
    fp_index: int
    spec_name: str
    model_name: str
    tla_path: str = ""  # module source (coverage line numbers)


@dataclasses.dataclass
class StructRunSpec:
    """A resolved run for the structural frontend: the full-module path
    (records, sets of records, procedure stacks, CHOOSE) that executes
    specs outside the gen subset - the reference's own KubeAPI.tla
    included (-frontend struct)."""

    structmodel: object  # struct.loader.StructModel
    invariants: List[str]
    properties: List[str]
    check_deadlock: bool
    workers: str
    fp_index: int
    spec_name: str
    model_name: str


def resolve(
    cfg_path: str,
    launch_path: Optional[str] = None,
    workers: str = "tpu",
    fp_index: Optional[int] = None,
    check_deadlock: bool = True,
    frontend: str = "auto",
    const_overrides: Optional[dict] = None,
) -> RunSpec:
    """Resolve a run from an MC.cfg (with sibling MC.tla) like TLC would.

    frontend: "auto" picks the hand-tuned KubeAPI path for the KubeAPI
    root spec, the gen-subset compiler for subset specs, and falls back
    to the structural frontend for anything else; "hand"/"gen"/"struct"
    force a path (struct runs ANY spec, KubeAPI included).

    const_overrides: already-evaluated CONSTANT values layered on top
    of the cfg's (the serve tier's per-job overrides); they win over
    both the cfg assignments and the MC.tla substitutions, on every
    frontend path."""
    if frontend not in ("auto", "hand", "gen", "struct"):
        raise ValueError(f"unknown -frontend {frontend!r}")
    cfg: TLCConfig = parse_cfg_file(cfg_path)
    if cfg.check_deadlock is False:
        # the cfg's `CHECK_DEADLOCK FALSE` or the caller's -nodeadlock:
        # either switches the deadlock check off
        check_deadlock = False
    if cfg.symmetry:
        # only the structural frontend reduces: a cfg that says SYMMETRY
        # never gets an unreduced verdict from another one
        if frontend in ("hand", "gen"):
            raise ValueError(
                f"the cfg declares SYMMETRY {cfg.symmetry}: only the "
                "structural frontend reduces (re-run with -frontend "
                "struct)")
        frontend = "struct"
    if cfg.constraints:
        # likewise a CONSTRAINT: only the structural frontend compiles
        # the predicate, and no other ever runs the model unconstrained
        if frontend in ("hand", "gen"):
            raise ValueError(
                f"the cfg declares CONSTRAINT {' '.join(cfg.constraints)}"
                ": only the structural frontend honours it (re-run with "
                "-frontend struct)")
        frontend = "struct"
    model_dir = os.path.dirname(os.path.abspath(cfg_path))
    mc_tla_path = os.path.join(model_dir, "MC.tla")
    consts = dict(cfg.constants)
    extends: List[str] = []
    if os.path.exists(mc_tla_path):
        mc = parse_mc_tla_file(mc_tla_path)
        extends = mc.extends
        for name, defname in cfg.substitutions.items():
            if defname in mc.definitions:
                consts[name] = mc.definitions[defname]
    if const_overrides:
        consts.update(const_overrides)

    launch: Optional[LaunchConfig] = None
    if launch_path is None:
        toolbox_dir = os.path.dirname(model_dir)
        for f in sorted(os.listdir(toolbox_dir)) if os.path.isdir(toolbox_dir) else []:
            if f.endswith(".launch"):
                launch_path = os.path.join(toolbox_dir, f)
                break
    if launch_path and os.path.exists(launch_path):
        launch = parse_launch_file(launch_path)

    spec_name = launch.spec_name if launch else (extends[0] if extends else "")
    if spec_name in ("", "KubeAPI") and not extends and not os.path.exists(
        mc_tla_path
    ):
        # no MC.tla: the cfg may sit next to a bare root module; prefer
        # TLC's Foo.cfg <-> Foo.tla convention, then a module named like
        # the toolbox dir ("Foo.toolbox" -> Foo.tla), and refuse to guess
        # among several unrelated candidates (the alphabetically-first
        # pick could silently grab a helper module)
        cands = sorted(
            f[:-4] for f in os.listdir(model_dir) if f.endswith(".tla")
        )
        cfg_base = os.path.splitext(os.path.basename(cfg_path))[0]
        toolbox = os.path.basename(os.path.dirname(model_dir))
        toolbox = toolbox[:-8] if toolbox.endswith(".toolbox") else toolbox
        preferred = [p for p in (cfg_base, toolbox) if p in cands]
        if preferred:
            spec_name = preferred[0]
        elif len(cands) == 1:
            spec_name = cands[0]
        elif cands:
            raise ValueError(
                f"ambiguous root spec: several .tla modules next to the "
                f"config ({', '.join(cands)}) and none matches the config "
                f"name {cfg_base!r} or toolbox name {toolbox!r}; add a "
                ".launch file or an "
                "MC.tla naming the root module"
            )
    if frontend == "struct" or (
        frontend == "auto" and spec_name not in ("", "KubeAPI")
        and not os.path.exists(
            os.path.join(model_dir, f"{spec_name}.tla"))
        and os.path.exists(mc_tla_path)
    ):
        # forced structural path, or a non-KubeAPI MC whose root module
        # resolves through EXTENDS rather than a sibling file
        return _resolve_struct(cfg_path, cfg, launch, spec_name,
                               check_deadlock, workers, fp_index,
                               model_dir, const_overrides)
    if frontend in ("hand", "gen") and cfg.properties and (
            frontend == "gen" or spec_name not in ("", "KubeAPI")):
        # likewise a PROPERTY that states a specification (an action
        # property `I /\\ [][A]_v`): only the structural frontend judges
        # it, and no other gives a verdict that leaves it out
        from ..struct.loader import action_property_names

        stated = action_property_names(cfg_path)
        if stated:
            raise ValueError(
                f"the cfg's PROPERTY {' '.join(stated)} is an action "
                "property (I /\\ [][A]_v): only the structural frontend "
                "judges it (re-run with -frontend struct)")
    if frontend == "hand" and spec_name not in ("", "KubeAPI"):
        raise ValueError(
            f"-frontend hand supports only the KubeAPI root spec, "
            f"not {spec_name!r}"
        )
    if spec_name not in ("", "KubeAPI") or frontend == "gen":
        # generic frontend (E1): execute any PlusCal-translation-subset
        # module found next to the config; outside-subset specs fall
        # back to the structural frontend (full expression language)
        tla_path = os.path.join(model_dir, f"{spec_name}.tla")
        if not os.path.exists(tla_path):
            raise ValueError(
                f"root spec {spec_name!r}: no {spec_name}.tla next to the "
                "config (the generic frontend loads the module from there)"
            )
        from ..gen.tla_parse import SpecParseError, load_genspec

        try:
            genspec = load_genspec(
                tla_path, consts, list(cfg.invariants), list(cfg.properties)
            )
        except SpecParseError as e:
            if frontend == "gen":
                raise ValueError(
                    f"root spec {spec_name!r} is outside the supported "
                    f"PlusCal-translation subset: {e}"
                )
            return _resolve_struct(cfg_path, cfg, launch, spec_name,
                                   check_deadlock, workers, fp_index,
                                   model_dir, const_overrides)
        if launch:
            # launch-file knobs apply to generic specs exactly as to the
            # KubeAPI path (deadlock switch, fpIndex)
            check_deadlock = launch.check_deadlock
            if fp_index is None:
                fp_index = launch.fp_index
        return GenRunSpec(
            genspec=genspec,
            invariants=list(cfg.invariants),
            properties=list(cfg.properties),
            check_deadlock=check_deadlock,
            workers=workers,
            fp_index=DEFAULT_FP_INDEX if fp_index is None else fp_index,
            spec_name=spec_name,
            model_name=os.path.basename(model_dir),
            tla_path=tla_path,
        )
    if cfg.specification not in (None, "Spec"):
        raise ValueError(f"unsupported SPECIFICATION {cfg.specification!r}")

    def constant(name: str, default):
        v = consts.get(name, default)
        return eval_constant(v) if isinstance(v, str) else v

    def boolify(name: str, default: bool) -> bool:
        v = constant(name, default)
        if not isinstance(v, bool):
            raise ValueError(f"constant {name} must be BOOLEAN, got {v!r}")
        return v

    def count(name: str) -> int:
        # a count left out is 1
        v = constant(name, 1)
        if isinstance(v, bool) or not isinstance(v, int) or v < 1:
            raise ValueError(
                f"constant {name} must be an integer >= 1, got {v!r}")
        return v

    fail = boolify("REQUESTS_CAN_FAIL", True)
    timeout = boolify("REQUESTS_CAN_TIMEOUT", True)
    if any(name in consts for name in SCALING_CONSTANTS):
        # this repo's scaling rule (config.make_scaled), not the
        # source's: N copies of `process Client`, M of `PVCController`
        n, m = (count(name) for name in SCALING_CONSTANTS)
        model = make_scaled(n, m, fail, timeout)
    else:
        model = ModelConfig(requests_can_fail=fail,
                            requests_can_timeout=timeout)

    invariants = [i for i in cfg.invariants if i]
    for inv in invariants:
        if inv not in KNOWN_INVARIANTS:
            raise ValueError(f"unknown INVARIANT {inv!r}")
    properties = list(cfg.properties)
    if launch:
        # launch-level enable/disable flags refine the cfg lists (launch:18-23)
        enabled_inv = {n for n, on in launch.invariants if on}
        if launch.invariants:
            invariants = [i for i in invariants if i in enabled_inv]
        properties = [n for n, on in launch.properties if on]
        check_deadlock = launch.check_deadlock
        if fp_index is None:
            fp_index = launch.fp_index

    return RunSpec(
        model=model,
        invariants=invariants,
        properties=properties,
        check_deadlock=check_deadlock,
        workers=workers,
        fp_index=DEFAULT_FP_INDEX if fp_index is None else fp_index,
        spec_name=spec_name or "KubeAPI",
        model_name=(launch.model_name if launch else os.path.basename(model_dir)),
    )


def _resolve_struct(cfg_path, cfg, launch, spec_name, check_deadlock,
                    workers, fp_index, model_dir,
                    const_overrides=None) -> StructRunSpec:
    from ..struct.loader import StructLoadError, load as load_struct
    from ..struct.parser import StructParseError

    try:
        sm = load_struct(cfg_path, const_overrides=const_overrides)
    except (StructLoadError, StructParseError) as e:
        raise ValueError(
            f"root spec {spec_name!r}: structural frontend cannot load "
            f"the module: {e}"
        )
    if launch:
        check_deadlock = launch.check_deadlock
        if fp_index is None:
            fp_index = launch.fp_index
    return StructRunSpec(
        structmodel=sm,
        invariants=list(cfg.invariants),
        # the temporal ones; a PROPERTY that states a specification is
        # the model's (sm.action_props), judged by the safety search
        properties=[p for p in cfg.properties
                    if p not in sm.action_props],
        check_deadlock=check_deadlock,
        workers=workers,
        fp_index=DEFAULT_FP_INDEX if fp_index is None else fp_index,
        spec_name=sm.root_name or spec_name,
        model_name=os.path.basename(model_dir),
    )

"""KubeAPI device coverage plane: TLC's span counters, on the chip.

The host coverage walker (spec.coverage) reproduces the reference
MC.out per-expression dump EXACTLY by re-walking the whole state space
a third time with an instrumented evaluator.  This module moves the
deterministic part of that accounting INTO the compiled kernels: every
span whose visit count is a pure function of per-state facts the codec
already holds - label occupancy, request/list statuses, apiState
membership, version-vector bits, shouldReconcile - becomes a device
site whose per-block increment is computed alongside the vmapped step
and accumulated in the carry's cumulative coverage tensor.  The tracked
table is pinned SITE-FOR-SITE against the host walker on the FF corner
in tier-1 (tests/test_coverage_device.py) and against the Model_1 walk
in the slow suite.

What stays host-only (tracked=False, by design not omission): spans
inside SHORT-CIRCUITING enumerations whose visit count depends on
TLC's element iteration order mid-scan (`\\E o \\in apiState` existence
probes, the PVCListedPVCs `\\A` body, the Update `\\E` body).  Every
non-short-circuiting enumeration (set comprehensions, the Get CHOOSE,
the Delete filter) IS tracked - their loops visit every element, so the
counts are sums over apiState the device computes exactly, including
the IsVersionOf short-circuit structure via name/kind-equality tables.

Site keys are the span keys spec/coverage_spans.py pins, so the device
counters, the host walker and the committed MC.out all speak one
vocabulary; render through obs.coverage.render_site_dump or diff with
tools/covdiff.py.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from ..config import RECONCILER, ModelConfig
from ..obs.coverage import CoveragePlane, Site
from .codec import get_codec
from .labels import LABEL_ID, LABELS, VERB_ID

# reconciler-machine labels (the Client label machine CStart..C5) and
# binder-machine labels, in walker order
_RECON_LABELS = ("CStart", "C1", "C10", "C11", "c12", "C13", "C2",
                 "C3", "C8", "C6", "C7", "C4", "C5")
_BINDER_LABELS = ("PVCStart", "PVCListedPVCs", "PVCHavePVCs", "PVCDone")
_PROC_LABELS = ("DoRequest", "DoReply", "DoListRequest", "DoListReply")
_PROC_KEY = {"DoRequest": "DR", "DoReply": "DRp",
             "DoListRequest": "DLR", "DoListReply": "DLRp"}
_RECON_KEY = {lbl: lbl for lbl in _RECON_LABELS}
_RECON_KEY["CStart"] = "CS"
_BINDER_KEY = {"PVCStart": "PS", "PVCListedPVCs": "PL",
               "PVCHavePVCs": "PH", "PVCDone": "PD"}


def _span_locs() -> Dict[str, str]:
    """span key -> source loc from the generated span table (KubeAPI
    only; other configs render the key)."""
    try:
        from .coverage_spans import SPANS
    except ImportError:  # pragma: no cover
        return {}
    out: Dict[str, str] = {}
    for _name, _code, _loc, lines in SPANS:
        for _dep, loc, key, _lcode, _hc, _ce in lines:
            out.setdefault(key, loc)
    return out


def kubeapi_coverage_plane(cfg: ModelConfig) -> CoveragePlane:
    """Build the device coverage plane for one KubeAPI configuration.

    The site table opens with one "action" site per label (the
    per-action generated counts - the PR 3 coverage lines are a prefix
    view), followed by the tracked span-key sites.  count() computes
    every increment from the popped batch's decoded fields + lane
    validity - no extra kernel work, no host sync."""
    import jax.numpy as jnp

    cdc = get_codec(cfg)
    nc, ni, ls, nr = cdc.nc, cdc.ni, cdc.ls, cdc.nr
    np_procs = nc + 1
    n_bind = nc - nr

    api_off = cdc.offsets["api"]
    req_off = cdc.offsets["req"]
    lm_off = cdc.offsets["lreq_meta"]
    lo_off = cdc.offsets["lreq_obj"]
    pc_off = cdc.offsets["pc"]
    sr_off = cdc.offsets["sr"]

    imask = (1 << cdc.ib) - 1

    # identity tables: name/kind equality + (name, kind) strict order
    # (the _enum_key scan position of _object_exists) + PVC-kind flags
    names = [n for _, n in cfg.identities]
    kinds = [k for k, _ in cfg.identities]
    NEQ = np.asarray([[a == b for b in names] for a in names])
    KEQ = np.asarray([[a == b for b in kinds] for a in kinds])
    NKEQ = NEQ & KEQ
    LT = np.asarray([
        [(na, ka) < (nb, kb)
         for nb, kb in zip(names, kinds)]
        for na, ka in zip(names, kinds)
    ])
    IS_PVC = np.asarray([k == "PVC" for k in kinds])
    KIND_ID = np.asarray([cdc.kind_id[k] for k in kinds], np.int32)

    fail_t = int(cfg.requests_can_fail) + int(cfg.requests_can_timeout)
    timeout = int(cfg.requests_can_timeout)

    # ------------------------------------------------------------------
    # the tracked-site registry: (key, action, fn) where fn(ctx) is a
    # per-state [ck] int32 contribution or an int constant-per-state
    # ------------------------------------------------------------------
    entries: List[tuple] = []

    def site(key, action, fn):
        entries.append((key, action, fn))

    # ---- context builder -------------------------------------------------

    def build_ctx(batch):
        ctx = {}
        aw = batch[:, api_off:api_off + ni]
        ctx["api_present"] = ((aw >> cdc.o_present) & 1).astype(bool)
        ctx["api_ident"] = (aw >> cdc.o_ident) & imask
        ctx["api_vv"] = aw  # vv bit c of slot: (aw >> (o_vv + c)) & 1
        ctx["api_n"] = ctx["api_present"].sum(axis=1)

        ctx["req_w"] = batch[:, req_off:req_off + nc]
        ctx["lm_w"] = batch[:, lm_off:lm_off + nc]
        ctx["pc"] = batch[:, pc_off:pc_off + nc + 1]
        ctx["sr"] = batch[:, sr_off:sr_off + nr]
        ctx["lo_w"] = batch[:, lo_off:lo_off + nc * ls]
        return ctx

    def _memo(ctx, key, fn):
        """Emit a shared subexpression into the block's graph ONCE:
        the ~300 site formulas lean on a few dozen leaf vectors, and
        the CPU backend pays per-op dispatch, so deduplication at
        trace time (not XLA CSE) is what keeps the hook cheap."""
        v = ctx.get(key)
        if v is None:
            v = fn()
            ctx[key] = v
        return v

    def req_present(ctx, i):
        return _memo(ctx, ("rp", i), lambda: (
            (ctx["req_w"][:, i] >> cdc.r_present) & 1).astype(bool))

    def req_status(ctx, i):
        return _memo(ctx, ("rs", i), lambda: (
            ctx["req_w"][:, i] >> cdc.r_status) & 3)

    def req_op(ctx, i):
        return _memo(ctx, ("ro", i), lambda: (
            ctx["req_w"][:, i] >> cdc.r_op) & 7)

    def req_obj_ident(ctx, i):
        return _memo(ctx, ("roi", i), lambda: (
            (ctx["req_w"][:, i] >> cdc.r_obj) >> cdc.o_ident) & imask)

    def req_obj_has_spec(ctx, i):
        return _memo(ctx, ("rospec", i), lambda: (
            ((ctx["req_w"][:, i] >> cdc.r_obj) >> cdc.o_spec) & 1
        ).astype(bool))

    def lm_present(ctx, i):
        return _memo(ctx, ("lmp", i), lambda: (
            (ctx["lm_w"][:, i] >> cdc.lm_present) & 1).astype(bool))

    def lm_status(ctx, i):
        return _memo(ctx, ("lms", i), lambda: (
            ctx["lm_w"][:, i] >> cdc.lm_status) & 3)

    def lm_kind(ctx, i):
        return _memo(ctx, ("lmk", i), lambda: (
            ctx["lm_w"][:, i] >> cdc.lm_kind) & ((1 << cdc.kb) - 1))

    def lobj_present(ctx, i, s):
        return _memo(ctx, ("lop", i, s), lambda: (
            (ctx["lo_w"][:, i * ls + s] >> cdc.o_present) & 1
        ).astype(bool))

    def lobj_has_spec(ctx, i, s):
        return _memo(ctx, ("lospec", i, s), lambda: (
            (ctx["lo_w"][:, i * ls + s] >> cdc.o_spec) & 1
        ).astype(bool))

    def occ(ctx, i, label):
        return _memo(ctx, ("occ", i, label), lambda: (
            ctx["pc"][:, i] == LABEL_ID[label]).astype(jnp.int32))

    # matches of obj-ident t over the api slots, per state
    def api_count(ctx, pred_table, ident, key=None):
        """Sum over api slots of present & pred_table[slot_ident,
        ident] (pred_table [ni, ni]); memoized under `key`."""
        def build():
            t = jnp.asarray(pred_table)
            per = t[ctx["api_ident"], ident[:, None]]
            return (per & ctx["api_present"]).sum(axis=1).astype(
                jnp.int32)
        if key is None:
            return build()
        return _memo(ctx, ("apic",) + key, build)

    # ---- procedure labels (DR/DRp/DLR/DLRp) ------------------------------

    def proc_occ(ctx, label):
        def build():
            out = 0
            for i in range(nc):
                out = out + occ(ctx, i, label)
            return out
        return _memo(ctx, ("proc_occ", label), build)

    def ready_count(ctx, label, status_fn):
        def build():
            out = 0
            for i in range(nc):
                out = out + occ(ctx, i, label) * (
                    status_fn(ctx, i) != 0
                ).astype(jnp.int32)
            return out
        return _memo(ctx, ("ready", label), build)

    def _mk_proc_sites():
        # DoRequest / DoListRequest: fire whenever occupied; paths =
        # 1 + fail + timeout per firing
        for label, meta_s in (("DoRequest", "b"), ("DoListRequest", "b")):
            k = _PROC_KEY[label]
            fire = (lambda c, lb=label: proc_occ(c, lb))
            site(f"{k}.g", label,
                 lambda c, f=fire: np_procs + f(c))
            site(f"{k}.gs", label, np_procs)
            site(f"{k}.b1", label, fire)
            site(f"{k}.b2g", label, fire)
            site(f"{k}.b2b", label,
                 lambda c, f=fire: f(c) * fail_t)
            paths = (lambda c, f=fire: f(c) * (1 + fail_t))
            site(f"{k}.pc", label, paths)
            site(f"{k}.un", label, paths)
        # DoReply / DoListReply: await logs occupancy + fire re-visit,
        # fire iff the (list) request is no longer Pending; paths =
        # 1 + timeout per firing
        for label, st_fn in (("DoReply", req_status),
                             ("DoListReply", lm_status)):
            k = _PROC_KEY[label]
            o = (lambda c, lb=label: proc_occ(c, lb))
            fire = (lambda c, lb=label, sf=st_fn:
                    ready_count(c, lb, sf))
            site(f"{k}.g", label,
                 lambda c, f=fire: np_procs + f(c))
            site(f"{k}.gs", label, np_procs)
            site(f"{k}.aw", label,
                 lambda c, oc=o, f=fire: oc(c) + f(c))
            site(f"{k}.aws", label, o)
            site(f"{k}.b1g", label, fire)
            site(f"{k}.b1b", label, fire)
            site(f"{k}.b2", label, fire)
            paths = (lambda c, f=fire: f(c) * (1 + timeout))
            for sub in (("pc", "op", "obj", "st", "un")
                        if label == "DoReply"
                        else ("pc", "kind", "st", "un")):
                site(f"{k}.{sub}", label, paths)

    _mk_proc_sites()

    # ---- reconciler client machine ---------------------------------------

    recon = [(i, cfg.sr_index(i), cfg.targets[i])
             for i, r in enumerate(cfg.roles) if r == RECONCILER]

    _rsum_n = [0]

    def rsum(fn):
        """Sum fn(ctx, i, ri, (si, pi)) over reconciler clients;
        the summed vector is memoized per closure so sites sharing an
        aggregate emit it once."""
        _rsum_n[0] += 1
        key = ("rsum", _rsum_n[0])

        def out(ctx):
            def build():
                acc = 0
                for i, ri, tg in recon:
                    acc = acc + fn(ctx, i, ri, tg)
                return acc
            return _memo(ctx, key, build)
        return out

    def _attempt(key, label, fire_fn):
        site(f"{key}.g", label,
             lambda c, f=fire_fn: nr + f(c))
        site(f"{key}.gs", label, nr)

    def _mk_recon_sites():
        # CStart: two either-paths per firing; branch by shouldReconcile
        o_cs = rsum(lambda c, i, ri, tg: occ(c, i, "CStart"))
        _attempt("CS", "CStart", o_cs)
        for sub in ("b1", "b2g", "b2b"):
            site(f"CS.{sub}", "CStart", o_cs)
        site("CS.if", "CStart", lambda c: 2 * o_cs(c))
        site("CS.un", "CStart", lambda c: 2 * o_cs(c))
        site("CS.then", "CStart", rsum(
            lambda c, i, ri, tg:
            occ(c, i, "CStart") * (1 + c["sr"][:, ri])))
        cs_else = rsum(
            lambda c, i, ri, tg:
            occ(c, i, "CStart") * (1 - c["sr"][:, ri]))
        site("CS.else", "CStart", cs_else)
        site("CS.epc", "CStart", cs_else)
        site("CS.eun", "CStart", cs_else)

        # request-status IF labels: C1/C11 (then = not-Ok), C3 on list
        for label, key, st_fn in (("C1", "C1", req_status),
                                  ("C11", "C11", req_status),
                                  ("C3", "C3", lm_status)):
            o = rsum(lambda c, i, ri, tg, lb=label: occ(c, i, lb))
            ok = rsum(lambda c, i, ri, tg, lb=label, sf=st_fn:
                      occ(c, i, lb) * (sf(c, i) == 1).astype(jnp.int32))
            _attempt(key, label, o)
            site(f"{key}.if", label, o)
            site(f"{key}.then", label, lambda c, oc=o, okc=ok:
             oc(c) - okc(c))
            site(f"{key}.else", label, ok)
            site(f"{key}.un", label, o)

        # straight-line labels
        for label, key, subs in (
            ("C10", "C10", ("asg", "pc", "un")),
            ("c12", "c12", ("asg", "pc", "un")),
            ("C2", "C2", ("sr", "as", "pc", "un")),
            ("C5", "C5", ("pc", "un")),
        ):
            o = rsum(lambda c, i, ri, tg, lb=label: occ(c, i, lb))
            _attempt(key, label, o)
            for sub in subs:
                site(f"{key}.{sub}", label, o)

        # C13: Get reply triage through IsUnboundPVC
        o13 = rsum(lambda c, i, ri, tg: occ(c, i, "C13"))
        ok13 = rsum(lambda c, i, ri, tg:
                    occ(c, i, "C13")
                    * (req_status(c, i) == 1).astype(jnp.int32))
        _attempt("C13", "C13", o13)
        site("C13.if", "C13", o13)
        site("C13.o1", "C13", o13)
        site("C13.o2", "C13", ok13)
        site("C13.ubarg", "C13", ok13)
        site("C13.ub.w", "C13", ok13)
        site("C13.ub.k", "C13", ok13)

        def _c13(fn):
            return rsum(lambda c, i, ri, tg:
                        occ(c, i, "C13")
                        * (req_status(c, i) == 1).astype(jnp.int32)
                        * fn(c, i))

        is_pvc_t = jnp.asarray(IS_PVC)
        ub_or = _c13(lambda c, i:
                     is_pvc_t[req_obj_ident(c, i)].astype(jnp.int32))
        site("C13.ub.or", "C13", ub_or)
        site("C13.ub.o1", "C13", ub_or)
        site("C13.ub.o2", "C13", _c13(
            lambda c, i: (is_pvc_t[req_obj_ident(c, i)]
                          & req_obj_has_spec(c, i)).astype(jnp.int32)))
        unbound = lambda c, i: (  # noqa: E731
            is_pvc_t[req_obj_ident(c, i)]
            & ~req_obj_has_spec(c, i)).astype(jnp.int32)
        bad13 = rsum(lambda c, i, ri, tg:
                     occ(c, i, "C13") * jnp.where(
                         req_status(c, i) == 1, unbound(c, i), 1))
        site("C13.then", "C13", bad13)
        site("C13.else", "C13", lambda c: o13(c) - bad13(c))
        site("C13.un", "C13", o13)

        # C8: branch on whether the listed object set is empty
        o8 = rsum(lambda c, i, ri, tg: occ(c, i, "C8"))
        def _nobjs(c, i):
            def build():
                n = 0
                for s in range(ls):
                    n = n + lobj_present(c, i, s).astype(jnp.int32)
                return n
            return _memo(c, ("nobjs", i), build)
        empty8 = rsum(lambda c, i, ri, tg:
                      occ(c, i, "C8")
                      * (_nobjs(c, i) == 0).astype(jnp.int32))
        _attempt("C8", "C8", o8)
        site("C8.if", "C8", o8)
        site("C8.then", "C8", empty8)
        site("C8.else", "C8", lambda c: o8(c) - empty8(c))
        site("C8.un", "C8", o8)

        # C6: one `with` path per listed object; fire-entry re-visit
        # only when the list is nonempty
        o6ne = rsum(lambda c, i, ri, tg:
                    occ(c, i, "C6")
                    * (_nobjs(c, i) > 0).astype(jnp.int32))
        site("C6.g", "C6", lambda c: nr + o6ne(c))
        site("C6.gs", "C6", nr)
        paths6 = rsum(lambda c, i, ri, tg: occ(c, i, "C6") * _nobjs(c, i))
        site("C6.with", "C6", paths6)
        site("C6.un", "C6", paths6)

        # C7: retry unless the delete succeeded AND one object remains
        o7 = rsum(lambda c, i, ri, tg: occ(c, i, "C7"))
        ok7 = rsum(lambda c, i, ri, tg:
                   occ(c, i, "C7")
                   * (req_status(c, i) == 1).astype(jnp.int32))
        _attempt("C7", "C7", o7)
        site("C7.if", "C7", o7)
        site("C7.o1", "C7", o7)
        site("C7.o2", "C7", ok7)
        retry7 = rsum(lambda c, i, ri, tg:
                      occ(c, i, "C7") * jnp.where(
                          req_status(c, i) == 1,
                          (_nobjs(c, i) > 1).astype(jnp.int32), 1))
        site("C7.then", "C7", retry7)
        site("C7.else", "C7", lambda c: o7(c) - retry7(c))
        site("C7.un", "C7", o7)

        # C4: the ObjectExists scan - position of the first (n, k)
        # match in the walker's sorted enumeration, or |api| when none
        o4 = rsum(lambda c, i, ri, tg: occ(c, i, "C4"))
        _attempt("C4", "C4", o4)
        for sub in ("as", "neg", "oe", "pc", "un"):
            site(f"C4.{sub}", "C4", o4)
        site("C4.oed.w", "C4", o4)
        site("C4.oed.dom", "C4", o4)

        def _oed_iters(c, i, si):
            tgt = jnp.full(c["api_n"].shape, si, jnp.int32)
            match = api_count(c, NKEQ, tgt)
            less = api_count(c, LT, tgt)  # slots with (n,k) < target
            return jnp.where(match > 0, less + 1, c["api_n"])

        oed = rsum(lambda c, i, ri, tg:
                   occ(c, i, "C4") * _oed_iters(c, i, tg[0]))
        site("C4.oed.body", "C4", oed)
        site("C4.oed.arg", "C4", oed)

    _mk_recon_sites()

    # ---- binder machine --------------------------------------------------

    binders = [i for i, r in enumerate(cfg.roles) if r != RECONCILER]

    _bsum_n = [0]

    def bsum(fn):
        _bsum_n[0] += 1
        key = ("bsum", _bsum_n[0])

        def out(ctx):
            def build():
                acc = 0
                for i in binders:
                    acc = acc + fn(ctx, i)
                return acc
            return _memo(ctx, key, build)
        return out

    def _battempt(key, label, fire_fn):
        site(f"{key}.g", label,
             lambda c, f=fire_fn: n_bind + f(c))
        site(f"{key}.gs", label, n_bind)

    def _mk_binder_sites():
        for label, key, subs in (("PVCStart", "PS", ("asg", "pc", "un")),
                                 ("PVCDone", "PD", ("pc", "un"))):
            o = bsum(lambda c, i, lb=label: occ(c, i, lb))
            _battempt(key, label, o)
            for sub in subs:
                site(f"{key}.{sub}", label, o)

        # PVCListedPVCs: retry on list failure OR everything bound
        opl = bsum(lambda c, i: occ(c, i, "PVCListedPVCs"))
        okpl = bsum(lambda c, i:
                    occ(c, i, "PVCListedPVCs")
                    * (lm_status(c, i) == 1).astype(jnp.int32))
        _battempt("PL", "PVCListedPVCs", opl)
        site("PL.if", "PVCListedPVCs", opl)
        site("PL.o1", "PVCListedPVCs", opl)
        for sub in ("all", "all2", "dom", "var"):
            site(f"PL.{sub}", "PVCListedPVCs", okpl)

        def _any_unbound(c, i):
            any_u = jnp.zeros(c["api_n"].shape, bool)
            for s in range(ls):
                any_u = any_u | (lobj_present(c, i, s)
                                 & ~lobj_has_spec(c, i, s))
            return any_u

        retry_pl = bsum(lambda c, i:
                        occ(c, i, "PVCListedPVCs") * jnp.where(
                            lm_status(c, i) == 1,
                            (~_any_unbound(c, i)).astype(jnp.int32), 1))
        site("PL.then", "PVCListedPVCs", retry_pl)
        site("PL.else", "PVCListedPVCs",
             lambda c: opl(c) - retry_pl(c))
        site("PL.un", "PVCListedPVCs", opl)

        # PVCHavePVCs: one \E path per unbound listed PVC
        def _n_unbound(c, i):
            n = 0
            for s in range(ls):
                n = n + (lobj_present(c, i, s)
                         & ~lobj_has_spec(c, i, s)).astype(jnp.int32)
            return n

        ph_ne = bsum(lambda c, i:
                     occ(c, i, "PVCHavePVCs")
                     * (_n_unbound(c, i) > 0).astype(jnp.int32))
        site("PH.g", "PVCHavePVCs",
             lambda c: n_bind + ph_ne(c))
        site("PH.gs", "PVCHavePVCs", n_bind)
        ph_paths = bsum(lambda c, i:
                        occ(c, i, "PVCHavePVCs") * _n_unbound(c, i))
        site("PH.ex", "PVCHavePVCs", ph_paths)
        site("PH.un", "PVCHavePVCs", ph_paths)

    _mk_binder_sites()

    # ---- the API server --------------------------------------------------

    def _pending(c, i):
        return (req_present(c, i)
                & (req_status(c, i) == 0)).astype(jnp.int32)

    def _lpending(c, i):
        return (lm_present(c, i)
                & (lm_status(c, i) == 0)).astype(jnp.int32)

    def _op_is(c, i, verb):
        return (_pending(c, i)
                * (req_op(c, i) == VERB_ID[verb]).astype(jnp.int32))

    _csum_n = [0]

    def csum(fn):
        _csum_n[0] += 1
        key = ("csum", _csum_n[0])

        def out(ctx):
            def build():
                acc = 0
                for i in range(nc):
                    acc = acc + fn(ctx, i)
                return acc
            return _memo(ctx, key, build)
        return out

    def _mk_server_sites():
        pend = csum(_pending)
        lpend = csum(_lpending)
        paths = lambda c: pend(c) + lpend(c)  # noqa: E731
        fires = lambda c: (paths(c) > 0).astype(jnp.int32)  # noqa: E731
        site("AS.g", "APIStart", lambda c: 1 + fires(c))
        site("AS.gs", "APIStart", 1)
        for sub in ("pcref", "pcdef", "pcdom"):
            site(f"AS.{sub}", "APIStart", 1)
        site("AS.pcpred", "APIStart",
             csum(lambda c, i: req_present(c, i).astype(jnp.int32)))
        for sub in ("plref", "pldef", "pldom"):
            site(f"AS.{sub}", "APIStart", 1)
        site("AS.plpred", "APIStart",
             csum(lambda c, i: lm_present(c, i).astype(jnp.int32)))
        site("AS.bind", "APIStart", pend)
        site("AS.unl", "APIStart", pend)
        site("AS.unr", "APIStart", lpend)
        site("AS.pc", "APIStart", paths)
        site("AS.un", "APIStart", paths)

        # op dispatch: Create is never issued by this family's
        # processes, so the Force/Get/Delete/Update ladder is exact
        site("AS.fif", "APIStart", pend)
        force = csum(lambda c, i: _op_is(c, i, "Force"))
        site("AS.f.if", "APIStart", force)

        def _exists(c, i):
            return _memo(c, ("exists", i), lambda: api_count(
                c, NKEQ, req_obj_ident(c, i), key=("nkeq", i)) > 0)

        f_ex = csum(lambda c, i:
                    _op_is(c, i, "Force")
                    * _exists(c, i).astype(jnp.int32))
        site("AS.f.add", "APIStart", lambda c: force(c) - f_ex(c))
        site("AS.f.ok", "APIStart", force)
        for sub in ("set", "setc", "dom"):
            site(f"AS.f.{sub}", "APIStart", f_ex)
        f_elems = csum(lambda c, i:
                       _op_is(c, i, "Force")
                       * _exists(c, i).astype(jnp.int32) * c["api_n"])
        for sub in ("elif", "cond", "co", "cr"):
            site(f"AS.f.{sub}", "APIStart", f_elems)
        site("AS.f.civo.w", "APIStart", f_elems)
        site("AS.f.civo.1", "APIStart", f_elems)
        f_nmatch = csum(lambda c, i:
                        _op_is(c, i, "Force")
                        * _exists(c, i).astype(jnp.int32)
                        * api_count(c, NEQ, req_obj_ident(c, i), key=("neq", i)))
        site("AS.f.civo.2", "APIStart", f_nmatch)
        f_match = csum(lambda c, i:
                       _op_is(c, i, "Force")
                       * _exists(c, i).astype(jnp.int32)
                       * api_count(c, NKEQ, req_obj_ident(c, i), key=("nkeq", i)))
        site("AS.f.wr", "APIStart", f_match)
        site("AS.f.o", "APIStart", lambda c: f_elems(c) - f_match(c))

        get = csum(lambda c, i: _op_is(c, i, "Get"))
        site("AS.gif", "APIStart", lambda c: pend(c) - force(c))
        site("AS.g.if", "APIStart", get)
        g_ex = csum(lambda c, i:
                    _op_is(c, i, "Get") * _exists(c, i).astype(jnp.int32))
        site("AS.g.err", "APIStart", lambda c: get(c) - g_ex(c))
        site("AS.g.unch", "APIStart", lambda c: get(c) - g_ex(c))
        for sub in ("req", "req2", "api1", "cho", "cho2", "chod",
                    "st", "set", "setc", "dom"):
            site(f"AS.g.{sub}", "APIStart", g_ex)
        g_elems = csum(lambda c, i:
                       _op_is(c, i, "Get")
                       * _exists(c, i).astype(jnp.int32) * c["api_n"])
        for sub in ("chob", "choo", "chor", "elif", "cond", "co"):
            site(f"AS.g.{sub}", "APIStart", g_elems)
        # the primed requests'[c].obj deref logs one extra visit per
        # comprehension evaluation (spec.coverage's AS.g.cr note)
        site("AS.g.cr", "APIStart", lambda c: g_elems(c) + g_ex(c))
        g_nmatch = csum(lambda c, i:
                        _op_is(c, i, "Get")
                        * _exists(c, i).astype(jnp.int32)
                        * api_count(c, NEQ, req_obj_ident(c, i), key=("neq", i)))
        site("AS.g.chivo.w", "APIStart", g_elems)
        site("AS.g.chivo.1", "APIStart", g_elems)
        site("AS.g.chivo.2", "APIStart", g_nmatch)
        site("AS.g.civo.w", "APIStart", g_elems)
        site("AS.g.civo.1", "APIStart", g_elems)
        site("AS.g.civo.2", "APIStart", g_nmatch)
        g_match = csum(lambda c, i:
                       _op_is(c, i, "Get")
                       * _exists(c, i).astype(jnp.int32)
                       * api_count(c, NKEQ, req_obj_ident(c, i), key=("nkeq", i)))
        site("AS.g.rd", "APIStart", g_match)
        site("AS.g.o", "APIStart", lambda c: g_elems(c) - g_match(c))

        delete = csum(lambda c, i: _op_is(c, i, "Delete"))
        site("AS.dif", "APIStart", lambda c: pend(c) - force(c) - get(c))
        for sub in ("set", "setc", "dom", "ok"):
            site(f"AS.d.{sub}", "APIStart", delete)
        d_elems = csum(lambda c, i: _op_is(c, i, "Delete") * c["api_n"])
        for sub in ("neg", "negi", "co", "cr", "ivo.w", "ivo.1"):
            site(f"AS.d.{sub}", "APIStart", d_elems)
        d_nmatch = csum(lambda c, i:
                        _op_is(c, i, "Delete")
                        * api_count(c, NEQ, req_obj_ident(c, i), key=("neq", i)))
        site("AS.d.ivo.2", "APIStart", d_nmatch)

        upd = csum(lambda c, i: _op_is(c, i, "Update"))
        site("AS.uif", "APIStart",
             lambda c: pend(c) - force(c) - get(c) - delete(c))
        site("AS.u.if", "APIStart", upd)
        site("AS.u.dom", "APIStart", upd)

        def _found(c, i):
            """Some api object matches robj AND already lists client i
            in its version vector (the Update success condition)."""
            t = jnp.asarray(NKEQ)
            per = t[c["api_ident"], req_obj_ident(c, i)[:, None]]
            vv = ((c["api_vv"] >> (cdc.o_vv + i)) & 1).astype(bool)
            return (per & c["api_present"] & vv).any(axis=1)

        u_found = csum(lambda c, i:
                       _op_is(c, i, "Update")
                       * _found(c, i).astype(jnp.int32))
        for sub in ("set", "set2", "filt", "fdom", "wr", "ok"):
            site(f"AS.u.{sub}", "APIStart", u_found)
        site("AS.u.err", "APIStart", lambda c: upd(c) - u_found(c))
        site("AS.u.unch", "APIStart", lambda c: upd(c) - u_found(c))
        u_elems = csum(lambda c, i:
                       _op_is(c, i, "Update")
                       * _found(c, i).astype(jnp.int32) * c["api_n"])
        for sub in ("fneg", "fnegi", "fo", "fr", "fivo.w", "fivo.1"):
            site(f"AS.u.{sub}", "APIStart", u_elems)
        u_nmatch = csum(lambda c, i:
                        _op_is(c, i, "Update")
                        * _found(c, i).astype(jnp.int32)
                        * api_count(c, NEQ, req_obj_ident(c, i), key=("neq", i)))
        site("AS.u.fivo.2", "APIStart", u_nmatch)

        # list serving: every site on the list path iterates the full
        # apiState (no short-circuit), so all counts are exact
        for sub in ("l.req", "l.req2", "l.exc", "l.objs", "l.filt",
                    "l.fdom", "l.st", "l.set", "l.setc", "l.dom"):
            site(f"AS.{sub}", "APIStart", lpend)
        l_elems = csum(lambda c, i: _lpending(c, i) * c["api_n"])
        site("AS.l.pred", "APIStart", l_elems)
        site("AS.l.elif", "APIStart", l_elems)
        site("AS.l.cond", "APIStart", l_elems)

        def _kind_matches(c, i):
            kid = jnp.asarray(KIND_ID)[c["api_ident"]]
            per = kid == lm_kind(c, i)[:, None]
            return (per & c["api_present"]).sum(axis=1).astype(jnp.int32)

        l_rd = csum(lambda c, i: _lpending(c, i) * _kind_matches(c, i))
        site("AS.l.rd", "APIStart", l_rd)
        site("AS.l.o", "APIStart", lambda c: l_elems(c) - l_rd(c))

    _mk_server_sites()

    # ---- invariants (one evaluation per expanded = distinct state) -------

    def _mk_inv_sites():
        for sub in ("w", "c1", "c1dom", "c2", "c2dom", "c3", "c3dom"):
            site(f"TY.{sub}", "TypeOK", 1)
        site("TY.c1body", "TypeOK", lambda c: c["api_n"])
        site("TY.c2body", "TypeOK",
             csum(lambda c, i: req_present(c, i).astype(jnp.int32)))
        lm_n = csum(lambda c, i: lm_present(c, i).astype(jnp.int32))
        site("TY.c3body", "TypeOK", lm_n)
        for sub in ("vlr", "vlr1", "vlr2", "vlr2q", "vlr3", "vlrarg"):
            site(f"TY.{sub}", "TypeOK", lm_n)

        def _lobj_total(c):
            n = 0
            for i in range(nc):
                for s in range(ls):
                    n = n + lobj_present(c, i, s).astype(jnp.int32)
            return n

        site("TY.vlr2b", "TypeOK", _lobj_total)
        site("OV.w", "OnlyOneVersion", 1)
        site("OV.dom", "OnlyOneVersion", 1)
        site("OV.body", "OnlyOneVersion",
             lambda c: c["api_n"] * c["api_n"])
        site("OV.o1", "OnlyOneVersion",
             lambda c: c["api_n"] * c["api_n"])
        site("OV.o2", "OnlyOneVersion",
             lambda c: c["api_n"] * (c["api_n"] - 1))

    _mk_inv_sites()

    # ------------------------------------------------------------------
    # assemble the plane
    # ------------------------------------------------------------------
    locs = _span_locs() if cfg.identities == MODEL1_IDENTITIES else {}
    action_sites = [Site(key=a, kind="action", action=a)
                    for a in LABELS]
    fine_sites = [
        Site(key=k, kind="span", action=a, loc=locs.get(k, ""))
        for k, a, _fn in entries
    ]
    init_keys = ["I.api", "I.req", "I.lreq", "I.stk", "I.opobj",
                 "I.kind", "I.sr", "I.pc", "I.rest"]
    init_sites = [Site(key=k, kind="init", action="Init")
                  for k in init_keys]
    sites = tuple(action_sites) + tuple(init_sites) + tuple(fine_sites)

    n_labels = len(LABELS)
    label_ids_np = np.arange(n_labels, dtype=np.int32)
    APISTART_ID = LABEL_ID["APIStart"]

    def count(batch, mask, valid):
        # per-action generated prefix: the same factorized fold as
        # kubeapi_backend.gen_counts (one accounting, two renderings)
        label_ids = jnp.asarray(label_ids_np)
        CL_ = (valid.shape[1] - 2 * nc) // nc
        act = jnp.zeros(n_labels, jnp.uint32)
        for ci in range(nc):
            vc = valid[:, ci * CL_:(ci + 1) * CL_].sum(axis=1)
            pcs = batch[:, pc_off + ci]
            act = act + (
                (pcs[:, None] == label_ids[None, :]) * vc[:, None]
            ).sum(axis=0).astype(jnp.uint32)
        act = act.at[APISTART_ID].add(
            valid[:, nc * CL_:].sum().astype(jnp.uint32)
        )

        ctx = build_ctx(batch)
        ctx["_E"] = mask.sum().astype(jnp.int32)
        m = mask.astype(jnp.int32)
        ck = batch.shape[0]
        # one [S, ck] stack + ONE masked matvec instead of S separate
        # multiply-reduces: the per-site arithmetic fuses into a
        # handful of elementwise ops and a single dot (-coverage has
        # no benchmark cell: its cost on the chip is not measured)
        cols = []
        for _k, _a, fn in entries:
            v = fn(ctx) if callable(fn) else jnp.int32(fn)
            if getattr(v, "ndim", 0) == 0:
                v = jnp.broadcast_to(v[None], (ck,))
            cols.append(v.astype(jnp.int32))
        if cols:
            fine = jnp.stack(cols) @ m
            fine = fine.astype(jnp.uint32)
        else:
            fine = jnp.zeros(0, jnp.uint32)
        init_zeros = jnp.zeros(len(init_sites), jnp.uint32)
        return jnp.concatenate([act, init_zeros, fine])

    def init_count(inits: np.ndarray) -> np.ndarray:
        out = np.zeros(len(sites), np.uint32)
        n0 = inits.shape[0]
        base = len(action_sites)
        for j, k in enumerate(init_keys):
            out[base + j] = n0 if k in ("I.pc", "I.rest") else 1
        return out

    return CoveragePlane(sites=sites, count=count,
                         init_count=init_count, module="KubeAPI")


MODEL1_IDENTITIES = (("Secret", "foo"), ("PVC", "mypvc"))
